"""The cell loop end to end at a tiny size on the CPU (the chip check is
skipped: tests call the harness below the command), and a new cell built
from added files alone."""
import json
import shutil
import time

import bench_testlib


def test_cell_runs_end_to_end_at_tiny_size():
    out = bench_testlib.run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 8 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms",
                      "output_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert m["ttft_p95_ms"]["value"] >= m["ttft_p50_ms"]["value"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell, each
    a new file, plus new entries in BENCHMARK.json: no file under bench/
    is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(bench_testlib.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((bench_testlib.ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    cfg, mix, cell = bench_testlib.tiny()
    mix = dict(mix, name="burst", arrivals={"kind": "gamma", "cv": 4.0})
    (bench / "configs" / "tiny-mla.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "burst.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny-burst.json").write_text(json.dumps(cell))
    (bench / "metrics" / "submit_ms.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    s = run.in_window('submit')\n"
        "    return float(np.mean([b - a for a, b, _ in s])) * 1e3 "
        "if s else None\n")
    spec["configs"].append({"name": "tiny-mla", "source": "test",
                            "file": "bench/configs/tiny-mla.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-burst", "config": "tiny-mla",
                              "traffic": "burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "submit_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "gateway", "moves": "ttft_p50_ms",
                              "workloads": ["tiny-burst"]})
    for m in spec["per_layer"]:
        if m["name"] in ("hit_share", "embed_ms"):
            m["workloads"].append("tiny-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    from harness.cell import run_cell
    out = run_cell("tiny-burst", 77, 4.0, True, t_start=time.perf_counter(),
                   peaks=bench_testlib.TEST_PEAKS, bench=bench,
                   out_dir=tmp_path / "out", log=lambda m: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"submit_ms", "hit_share", "embed_ms"}
    assert out["metrics"]["hit_share"]["value"] > 0
    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items()
               if "__pycache__" not in k.parts)
