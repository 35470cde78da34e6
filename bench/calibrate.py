#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's numbers and the
lower-precision controls' numbers over many seeds, in one process.

    python3 bench/calibrate.py --workload faq-f32 --seconds 15 --seeds 1,2,3

For each seed one run of the cell as ``run.py`` makes it (same set-up,
window, drain and reference), plus the controls at the same samples: the
lookup's reference top-1 at ``high`` precision, the encoder and the model
with float8 weights. One JSON line per seed on stdout, and
``bench_out/calibrate_<workload>.jsonl``. Not part of the benchmark's
own runs.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    dev = chip(1)
    if dev is None:
        return 2
    from harness.cell import run_cell
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    path = out_dir / f"calibrate_{args.workload}.jsonl"
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = run_cell(args.workload, seed, args.seconds, False,
                       t_start=t0, dev=dev, controls=True, log=log)
        line = {"seed": seed, "correct": out["correct"],
                "control_correct": out["control_correct"],
                "checks": out["checks"], "controls": out["controls"],
                "metrics": out["metrics"], "failed": out["failed"]}
        print(json.dumps(line), flush=True)
        with path.open("a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
