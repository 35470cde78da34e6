#!/usr/bin/env python
"""Benchmark regression gate (run in CI after the --smoke benches;
EXPERIMENTS.md §Bench-gate).

Compares the smoke-config metrics in results/*.json against the committed
baselines in benchmarks/baselines/*.json and fails the job when a metric
regresses beyond its stated tolerance. Timing metrics are gated as
*ratios* (during/steady, sharded/unsharded) or with generous factors so
runner-speed variance doesn't flap the gate; quality metrics (hit ratio,
SLO attainment) get tight absolute tolerances; exactness flags must hold
outright.

    python tools/check_bench_regression.py [repo_root]     # gate
    python tools/check_bench_regression.py --update        # rebaseline
    python tools/check_bench_regression.py --selftest      # prove the
        gate fails on an injected regression for every metric

Metric paths use dotted keys with [idx] list indexing, resolved against
the parsed JSON. Directions:
    higher  current must be >= bound(baseline)  (regression = drop)
    lower   current must be <= bound(baseline)  (regression = rise)
    true    current must be truthy (no baseline involved)
Tolerance kinds:
    factor f   bound = baseline * f   (f < 1 for "higher", > 1 for "lower")
    abs d      bound = baseline -/+ d
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import re
import sys

# (results file, metric path, direction, kind, tolerance, note)
METRICS = [
    ("BENCH_refresh.json", "wallclock[-1].speedup",
     "higher", "factor", 0.4,
     "vectorized refresh speedup vs seed path"),
    ("BENCH_refresh.json", "p99.p99_during_over_steady_async",
     "lower", "factor", 2.5,
     "p99 submit() during refresh / steady-state (async pipeline)"),
    ("BENCH_slo.json", "scenarios.repeat_heavy.siso.hit_ratio",
     "higher", "abs", 0.05,
     "SISO hit ratio on the repeat_heavy live-gateway scenario"),
    ("BENCH_slo.json", "scenarios.repeat_heavy.siso.slo_attainment",
     "higher", "abs", 0.05,
     "SISO SLO attainment on repeat_heavy"),
    ("BENCH_shard.json", "s_max_over_s1_p50",
     "lower", "factor", 3.0,
     "sharded lookup p50 overhead ratio (max shards / 1 shard)"),
    ("BENCH_shard.json", "s_max_over_s1_p99",
     "lower", "factor", 3.0,
     "sharded lookup p99 tail-flatness ratio (max shards / 1 shard)"),
    ("BENCH_shard.json", "capacity[-1].rows_capacity",
     "higher", "factor", 1.0,
     "total cache rows at max shard count (deterministic)"),
    ("BENCH_shard.json", "latency[-1].equal_to_reference",
     "true", None, None,
     "sharded lookup element-wise identical to 1-device reference"),
    ("BENCH_restart.json", "drill.identical",
     "true", None, None,
     "warm restart element-wise identical to the uninterrupted run"),
    ("BENCH_restart.json", "drill.hit_ratio_warm_b",
     "higher", "abs", 0.05,
     "post-restart hit ratio (phase after recovery)"),
    ("BENCH_restart.json", "drill.warm_minus_cold_early",
     "higher", "abs", 0.05,
     "warm-restart hit-ratio advantage over a cold start, early window"),
    ("BENCH_restart.json", "drill.recovery_s",
     "lower", "factor", 10.0,
     "warm-restart recovery wall-clock (generous: runner variance)"),
    ("BENCH_restart.json", "crash.recovered",
     "true", None, None,
     "hard-crash (SIGKILL) recovery restored a serving snapshot"),
    ("BENCH_tiered.json", "hit_ratio_lift_10x",
     "higher", "abs", 0.05,
     "3-tier hit-ratio lift over device-only at 10x capacity pressure"),
    ("BENCH_tiered.json", "lift_positive",
     "true", None, None,
     "3-tier hit ratio strictly above device-only at equal device memory"),
    ("BENCH_tiered.json", "p99_within_2x",
     "true", None, None,
     "3-tier lookup p99 within 2x of the single-tier lookup p99"),
    ("BENCH_tenancy.json", "weighted_rel_degradation",
     "lower", "abs", 0.05,
     "steady tenant's relative hit-ratio loss under flood, tenancy on"),
    ("BENCH_tenancy.json", "unweighted_rel_degradation",
     "higher", "abs", 0.10,
     "same loss on the unweighted shared pool (the failure must show)"),
    ("BENCH_tenancy.json", "isolation_holds",
     "true", None, None,
     "weighted degradation < 10% relative AND unweighted > 40%"),
    ("BENCH_tenancy.json", "no_tenant_identical",
     "true", None, None,
     "tenancy-configured SISO element-wise identical on tenant-free "
     "traffic"),
    ("BENCH_tenancy.json", "drill.identical",
     "true", None, None,
     "multi-tenant save/restore replay element-wise identical"),
    ("BENCH_quant.json", "capacity_per_byte_ratio",
     "higher", "factor", 0.9,
     "int8 plane capacity per device byte vs the f32 plane (>= ~4x "
     "at dim=256; the paper-level requirement is >= 2x)"),
    ("BENCH_quant.json", "decisions_exact",
     "true", None, None,
     "quant-plane lookup decisions element-wise identical to the dense "
     "f32 reference (every LookupResult field + hit/miss counters)"),
    ("BENCH_quant.json", "shard_p99_ratio",
     "lower", "factor", 3.0,
     "sharded quant lookup p99 flatness (max shards / 1 shard)"),
    ("BENCH_quant.json", "latency[-1].equal_to_reference",
     "true", None, None,
     "8-shard quant lookup element-wise identical to 1-device quant"),
    ("BENCH_replica.json", "hit_lift",
     "higher", "abs", 0.05,
     "cross-replica hit-ratio lift of the synced group over isolated "
     "replicas on the identical zipf-routed stream"),
    ("BENCH_replica.json", "lift_positive",
     "true", None, None,
     "replication log strictly lifts the aggregate hit ratio"),
    ("BENCH_replica.json", "agg_attainment_sync",
     "higher", "abs", 0.05,
     "aggregate SLO attainment of the synced replica group"),
    ("BENCH_replica.json", "attainment_ok",
     "true", None, None,
     "group attainment no worse than a single replica serving the "
     "whole stream"),
    ("BENCH_replica.json", "drill.converged",
     "true", None, None,
     "rejoined replica's lookup stream element-wise identical to the "
     "never-killed donor after warm_start + reconcile"),
    ("BENCH_replica.json", "socket.hit_lift",
     "higher", "abs", 0.05,
     "cross-replica hit-ratio lift over the TCP socket transport"),
    ("BENCH_replica.json", "socket.lift_within_10pct_of_inproc",
     "true", None, None,
     "socket-transport hit lift within 10% of the in-process transport "
     "on the identical workload"),
    ("BENCH_replica.json", "socket.converged",
     "true", None, None,
     "socket replicas' lookup content identical on a clean network"),
    ("BENCH_replica.json", "socket_faults.converged",
     "true", None, None,
     "socket group converged after injected delays/drops and a healed "
     "partition"),
    ("BENCH_replica.json", "socket_faults.faults_exercised",
     "true", None, None,
     "fault injection actually dropped/delayed records and tripped the "
     "gap-reconcile path"),
    ("BENCH_replica.json", "drill_socket.converged",
     "true", None, None,
     "SIGKILL'd replica rejoined over TCP (warm_start + fetch_state "
     "clone) element-wise identical to the surviving donor"),
]

_TOK = re.compile(r"([^.\[\]]+)|\[(-?\d+)\]")


def _tokens(path: str) -> list:
    return [(m.group(1), m.group(2)) for m in _TOK.finditer(path)]


def resolve(obj, path: str):
    for key, idx in _tokens(path):
        obj = obj[key] if key is not None else obj[int(idx)]
    return obj


def set_path(doc, path: str, value) -> None:
    toks = _tokens(path)
    obj = doc
    for key, idx in toks[:-1]:
        obj = obj[key] if key is not None else obj[int(idx)]
    key, idx = toks[-1]
    obj[key if key is not None else int(idx)] = value


def _mode(doc: dict) -> str:
    """smoke/full mode flag of a results document. bench_slo nests it
    under config; the others carry it at the top level."""
    smoke = doc.get("smoke", doc.get("config", {}).get("smoke"))
    return "smoke" if smoke else "full"


def check_one(cur, base, direction, kind, tol):
    """Returns (ok, bound) for a current value against its baseline."""
    if direction == "true":
        return bool(cur), True
    if kind == "factor":
        bound = base * tol
    else:
        bound = base - tol if direction == "higher" else base + tol
    ok = cur >= bound if direction == "higher" else cur <= bound
    return ok, bound


def run_gate(results_dir: pathlib.Path, base_dir: pathlib.Path,
             results_override: dict | None = None) -> list[str]:
    """Evaluate every metric; returns the list of failure messages."""
    failures, cache, mode_checked, bad_mode = [], {}, set(), set()

    def load(root, name):
        if (root, name) not in cache:
            p = root / name
            if not p.exists():
                cache[(root, name)] = None
            else:
                cache[(root, name)] = json.loads(p.read_text())
        return cache[(root, name)]

    for fname, path, direction, kind, tol, note in METRICS:
        if results_override and fname in results_override:
            cur_doc = results_override[fname]
        else:
            cur_doc = load(results_dir, fname)
        if cur_doc is None:
            failures.append(f"{fname}: missing from {results_dir} "
                            f"(did the bench run?)")
            continue
        base_doc = load(base_dir, fname)
        if base_doc is None and direction != "true":
            failures.append(f"{fname}: no baseline in {base_dir} "
                            f"(run with --update to create)")
            continue
        if base_doc is not None and fname not in mode_checked:
            mode_checked.add(fname)
            cur_mode = _mode(cur_doc)
            base_mode = _mode(base_doc)
            if cur_mode != base_mode:
                bad_mode.add(fname)
                failures.append(
                    f"{fname}: results are {cur_mode}-mode but baseline "
                    f"is {base_mode}-mode — bounds would be meaningless "
                    f"(rerun the benches with --smoke, or rebaseline)")
        if fname in bad_mode:
            continue
        try:
            cur = resolve(cur_doc, path)
            base = resolve(base_doc, path) if direction != "true" else None
        except (KeyError, IndexError, TypeError) as e:
            failures.append(f"{fname}:{path}: unresolvable ({e!r})")
            continue
        ok, bound = check_one(cur, base, direction, kind, tol)
        tag = "ok  " if ok else "FAIL"
        print(f"  [{tag}] {fname}:{path} = {cur} "
              f"({direction}, bound {bound})  # {note}")
        if not ok:
            failures.append(f"{fname}:{path}: {cur} regressed past "
                            f"{bound} (baseline {base}, {note})")
    return failures


def selftest(results_dir: pathlib.Path, base_dir: pathlib.Path) -> int:
    """Inject a beyond-tolerance regression for every metric and assert
    the gate catches each one — proves the gate can actually fail."""
    missed = []
    for fname, path, direction, kind, tol, note in METRICS:
        doc = copy.deepcopy(json.loads((results_dir / fname).read_text()))
        if direction == "true":
            bad = False
        elif direction == "higher":
            bad = resolve(doc, path) * 0.01 - 10.0
        else:
            bad = resolve(doc, path) * 100.0 + 10.0
        set_path(doc, path, bad)
        fails = run_gate(results_dir, base_dir,
                         results_override={fname: doc})
        # exact failure form: only a tolerance violation counts as caught
        # (an unresolvable-path or missing-file failure must not)
        if not any(path in f and "regressed past" in f for f in fails):
            missed.append(f"{fname}:{path}")
    if missed:
        print(f"SELFTEST FAILED: gate missed injected regressions: {missed}")
        return 1
    print(f"selftest OK: gate caught all {len(METRICS)} injected "
          f"regressions")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("root", nargs="?", default=".")
    ap.add_argument("--update", action="store_true",
                    help="copy current results over the baselines")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args()
    root = pathlib.Path(args.root).resolve()
    results_dir = root / "results"
    base_dir = root / "benchmarks" / "baselines"

    if args.update:
        # validate everything first: a refusal must not leave the
        # baselines half-updated
        texts = {}
        for fname in sorted({m[0] for m in METRICS}):
            src = results_dir / fname
            if not src.exists():
                print(f"cannot rebaseline {fname}: no current result")
                return 1
            texts[fname] = src.read_text()
            if _mode(json.loads(texts[fname])) != "smoke":
                print(f"cannot rebaseline {fname}: baselines are the "
                      f"smoke configs, but this result is full-mode "
                      f"(rerun the bench with --smoke)")
                return 1
        base_dir.mkdir(parents=True, exist_ok=True)
        for fname, text in texts.items():
            (base_dir / fname).write_text(text)
            print(f"rebaselined {fname}")
        return 0
    if args.selftest:
        return selftest(results_dir, base_dir)

    failures = run_gate(results_dir, base_dir)
    if failures:
        print(f"\n{len(failures)} benchmark regression(s):")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"bench gate OK: {len(METRICS)} metrics within tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
