#!/usr/bin/env python3
"""The knee of a cell: serve it at several offered rates, in one process.

    python3 bench/sweep.py --workload faq-f32 --seconds 20 --rates 2,6,10

The first rate's span medians give the zero-load latencies: a first
token waits for the engine's current decode step, then embed, lookup and
(a miss) prefill; a token takes one decode step. The latency limits are
1.3 x those (the paper's SLO convention). For
each rate: the tails, the share of requests that met both limits (a
failed request misses), and the backlog (requests due but unfinished) at
half the window and at its close. The knee is the highest rate whose
backlog does not grow and where at least 90% met both limits. Writes
``bench_out/sweep_<workload>.json``. Not part of the benchmark's own
runs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness.entry import ROOT, chip, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    dev = chip(1)
    if dev is None:
        return 2
    from harness.cell import load_cell, run_cell
    from harness.report import percentile
    _, _, _, _, cell0 = load_cell(args.workload)
    rows, limits = [], None
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.deepcopy(cell0)
        cell["rate"] = rate
        probe = {}
        out = run_cell(args.workload, args.seed, args.seconds, False,
                       t_start=time.perf_counter(), dev=dev, cell=cell,
                       probe=probe, log=log)
        med = probe["span_median_ms"]
        if limits is None:
            limits = {"ttft_s": 1.3 * (med["decode"] + med["embed"]
                                       + med["lookup"] + med["prefill"]) / 1e3,
                      "tpot_s": 1.3 * med["decode"] / 1e3}
        ttft, tpot = probe["ttft"], probe["tpot"]
        ok = sum(1 for t in ttft if t <= limits["ttft_s"])
        tp_bad = sum(1 for t in tpot if not t <= limits["tpot_s"])
        row = {"rate": rate, "attempted": out["attempted"],
               "failed": out["failed"], "correct": out["correct"],
               "met_both": (ok - tp_bad) / max(len(ttft), 1),
               "met_ttft": ok / max(len(ttft), 1),
               "met_tpot": 1 - tp_bad / max(len(tpot), 1),
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "ttft_p99_ms": percentile(ttft, 99) * 1e3,
               "tpot_p50_ms": percentile(tpot, 50) * 1e3 if tpot else None,
               "tpot_p95_ms": percentile(tpot, 95) * 1e3 if tpot else None,
               "backlog_mid_close": probe["outstanding"],
               "span_median_ms": med,
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        row = {k: (None if isinstance(v, float) and math.isinf(v) else v)
               for k, v in row.items()}
        rows.append(row)
        log(f"sweep {json.dumps(row)}")
    res = {"workload": args.workload, "seconds": args.seconds,
           "limits": limits, "rows": rows}
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"sweep_{args.workload}.json").write_text(
        json.dumps(res, indent=1))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
