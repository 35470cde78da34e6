"""The benchmark's command refuses to run without a TPU and prints no
result, and its percentiles count failed requests as infinite."""
import math
import os
import subprocess
import sys

import bench_testlib
from harness.report import percentile


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "faq-f32", "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=bench_testlib.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_unknown_workload_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nope", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_testlib.ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_percentiles_count_failures_as_infinite():
    done = [0.010, 0.020, 0.030, 0.040]
    assert percentile(done, 50) == 0.020
    assert percentile(done, 95) == 0.040
    # one of twenty unfinished: the 95th percentile is still finite ...
    lat = [0.01] * 19 + [math.inf]
    assert percentile(lat, 95) == 0.01
    # ... two of twenty unfinished: it is infinite
    lat = [0.01] * 18 + [math.inf] * 2
    assert math.isinf(percentile(lat, 95))
    assert math.isinf(percentile([math.inf] * 3, 50))
