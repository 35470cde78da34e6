#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload faq-f32 --seed 7 --seconds 30 --trace 0

Run from the root of a checkout, on a machine whose JAX sees a TPU. One
process holds one chip. The run builds the cell's serving system (the
program under ``src/``) from its configuration with weights and a corpus
made from ``--seed``, warms up every shape the window uses, serves the
cell's traffic open-loop at the cell's fixed rate for ``--seconds``,
drains, then compares what the timed path produced with the plain
reference. ``--trace 1`` also traces part of the window with the profiler
and reports the per-layer metrics instead of the end-to-end ones.

Standard error carries the progress lines, with the numbers compared and
their limits last; the last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``. With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness.entry import ROOT, chip, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}.get(args.workload)
    if work is None:
        log(f"bench: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    dev = chip(work["chips"])
    if dev is None:
        return 2
    from harness.cell import run_cell
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, dev=dev, log=log)
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
