"""Tiered hierarchy drill: device-only vs device→host→disk at equal
device memory (EXPERIMENTS.md §Tiered, DESIGN.md §13).

A topic-drift stream whose unique-question population is ~10× the
device capacity cycles through topics; revisits reach back to questions
the device tier evicted long ago. The device-only SISO thrashes —
Algorithm 1 keeps the current topics and every long-range revisit pays
an LLM call. The 3-tier SISO demotes evicted entries to the host tier
(full precision, locality-ordered ANN) and on to disk instead of
discarding them, serves the revisits from the lower tiers, and promotes
the hits back into the device mirror through the donated row-patch
path.

Measured, at the SAME device capacity (and the same fixed theta_R):

- steady-window hit ratio, device-only vs 3-tier (the lift is the
  headline: strictly positive at 10× capacity pressure, gated)
- per-request lookup latency; the 3-tier p99 must stay within 2× of the
  single-tier p99 (+0.5 ms timer-noise guard in smoke sizes)
- promotion apply latency p99 (host/disk row -> device spill row)

Writes results/BENCH_tiered.json. Full mode asserts the acceptance
bars; --smoke runs tiny sizes without assertions (the CI gate compares
the JSON against benchmarks/baselines/BENCH_tiered.json via
tools/check_bench_regression.py).

  PYTHONPATH=src python -m benchmarks.bench_tiered [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

DIM = 32
ADIM = 32
THETA_R = 0.92
NOISE = 0.06            # revisit jitter: sim ≈ 0.995, safely over theta
WARMUP_FRAC = 0.25      # hit ratio measured on the steady window


def norm(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def build_universe(rng, n_topics: int, per_topic: int):
    """Unique question bank: topic anchors + per-question offsets."""
    anchors = norm(rng.normal(size=(n_topics, DIM)).astype(np.float32))
    qs = norm(anchors.repeat(per_topic, axis=0)
              + 0.35 * rng.normal(
                  size=(n_topics * per_topic, DIM)).astype(np.float32))
    answers = rng.normal(size=(len(qs), ADIM)).astype(np.float32)
    topic = np.arange(n_topics).repeat(per_topic)
    return qs.astype(np.float32), answers, topic


def build_stream(rng, topic: np.ndarray, steps: int, phase_len: int,
                 p_revisit: float):
    """Topic-drift request schedule over question indices.

    Each phase camps on one topic (cycling); a request either draws an
    unseen-or-recent question from the live topic or revisits ANY
    previously seen question uniformly — the long-range revisits are
    what a single-tier cache of 1/10th the population cannot hold."""
    n_topics = int(topic.max()) + 1
    by_topic = [np.flatnonzero(topic == t) for t in range(n_topics)]
    seen: list[int] = []
    seen_set: set[int] = set()
    sched = np.empty(steps, np.int64)
    for i in range(steps):
        t = (i // phase_len) % n_topics
        if seen and rng.random() < p_revisit:
            q = int(seen[int(rng.integers(len(seen)))])
        else:
            q = int(by_topic[t][int(rng.integers(len(by_topic[t])))])
        sched[i] = q
        if q not in seen_set:
            seen_set.add(q)
            seen.append(q)
    return sched


def make_siso(capacity: int, tiered_cfg=None):
    from repro.core.siso import SISO
    from repro.serving.config import CacheConfig, RefreshConfig, \
        ServingConfig
    cfg = ServingConfig(
        cache=CacheConfig(dim=DIM, answer_dim=ADIM, capacity=capacity,
                          theta_r=THETA_R, dynamic_threshold=False),
        refresh=RefreshConfig(async_pipeline=False), tiering=tiered_cfg,
        slo_latency=1.0, llm_latency=0.5)
    return SISO.from_config(cfg)


def serve(siso, questions, answers, sched, rng_seed: int = 3) -> dict:
    """Drive the stream; returns hit mask + per-request lookup latency."""
    rng = np.random.default_rng(rng_seed)
    hits = np.zeros(len(sched), bool)
    lat = np.zeros(len(sched), np.float64)
    for i, q in enumerate(sched):
        v = norm(questions[q] + NOISE * rng.normal(size=DIM)
                 .astype(np.float32)).astype(np.float32)
        t0 = time.perf_counter()
        res = siso.handle_batch(v[None, :])
        lat[i] = time.perf_counter() - t0
        hits[i] = bool(res.hit[0])
        if not hits[i]:
            siso.record_llm_answer(v, answers[q], answer_id=int(q))
        # refresh + promotion work rides outside the timed lookup, as it
        # does in the gateway (refresh_tick between submits)
        siso.refresh_tick(0.0)
    siso.refresh_drain()
    w = int(len(sched) * WARMUP_FRAC)
    return {
        "hit_ratio": float(hits[w:].mean()),
        "hit_ratio_total": float(hits.mean()),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
    }


def run(capacity: int, n_topics: int, per_topic: int, steps: int,
        phase_len: int, p_revisit: float, workdir: str) -> dict:
    from repro.core.tiered import TieredCacheConfig
    rng = np.random.default_rng(0)
    questions, answers, topic = build_universe(rng, n_topics, per_topic)
    sched = build_stream(rng, topic, steps, phase_len, p_revisit)
    unique = len(questions)
    boot_n = min(capacity * 2, unique)
    boot = rng.choice(unique, size=boot_n, replace=False)

    results = {}
    for name in ("device_only", "tiered"):
        tiered_cfg = None
        if name == "tiered":
            tiered_cfg = TieredCacheConfig(
                host_capacity=4 * capacity,
                disk_capacity=16 * capacity,
                disk_dir=os.path.join(workdir, "cold"),
                device_reserve=max(4, capacity // 4),
                promote_budget=8)
        s = make_siso(capacity, tiered_cfg)
        s.bootstrap(questions[boot], answers[boot],
                    answer_ids=boot.astype(np.int64))
        out = serve(s, questions, answers, sched)
        if name == "tiered":
            out["tier_stats"] = s.cache.tier_stats()
        results[name] = out
        print(f"  {name:12s} hit_ratio {out['hit_ratio']:.3f} "
              f"p99 {out['p99_ms']:.2f}ms")

    d, t = results["device_only"], results["tiered"]
    return {
        "capacity": capacity,
        "unique_questions": unique,
        "pressure_x": unique / capacity,
        "steps": steps,
        "device_only": d,
        "tiered": t,
        "hit_ratio_lift_10x": t["hit_ratio"] - d["hit_ratio"],
        "lift_positive": bool(t["hit_ratio"] > d["hit_ratio"]),
        "p99_ratio": t["p99_ms"] / max(d["p99_ms"], 1e-9),
        # +0.5ms absolute guard: at smoke sizes both p99s are ~1ms and a
        # single GC pause would otherwise flap a pure-ratio bound
        "p99_within_2x": bool(t["p99_ms"] <= 2.0 * d["p99_ms"] + 0.5),
    }


def main(argv=None) -> int:
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny sizes, no acceptance assertions")
    # parse_known_args: benchmarks.run invokes main() with its own argv
    args, _ = ap.parse_known_args(argv)
    if args.smoke:
        spec = dict(capacity=32, n_topics=16, per_topic=20, steps=900,
                    phase_len=30, p_revisit=0.55)
    else:
        spec = dict(capacity=64, n_topics=32, per_topic=20, steps=4000,
                    phase_len=50, p_revisit=0.55)

    workdir = tempfile.mkdtemp(prefix="bench_tiered_")
    print(f"== tiered hierarchy drill ({spec['n_topics']*spec['per_topic']}"
          f" uniques / {spec['capacity']} device rows ==")
    t0 = time.perf_counter()
    payload = run(workdir=workdir, **spec)
    payload["wall_s"] = time.perf_counter() - t0
    payload["smoke"] = bool(args.smoke)
    os.makedirs("results", exist_ok=True)
    path = os.path.join("results", "BENCH_tiered.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path}")
    print(f"  lift {payload['hit_ratio_lift_10x']:+.3f} at "
          f"{payload['pressure_x']:.0f}x pressure; p99 ratio "
          f"{payload['p99_ratio']:.2f}")

    import shutil
    shutil.rmtree(workdir, ignore_errors=True)
    if not args.smoke:
        assert payload["lift_positive"], \
            "3-tier hit ratio not strictly above device-only at 10x"
        assert payload["hit_ratio_lift_10x"] >= 0.10, \
            "hierarchy lift under 10 points at 10x capacity pressure"
        assert payload["p99_within_2x"], \
            "3-tier lookup p99 above 2x the single-tier p99"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
