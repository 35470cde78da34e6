"""chip_smoke.py: no CPU fallback, and its phases at a tiny size on CPU.

On the chip the script runs the served path at published widths. Here the
same phases run with a reduced engine and a small corpus (the encoder keeps
its published widths: reduced, its random embeddings of unrelated prompts
are too alike to tell hits from misses), so every PR exercises the
script's control flow and its correctness checks.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    """No fallback: on the CPU the script exits non-zero, prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not a TPU" in out.stderr


def test_one_chip_phases_on_cpu(smoke):
    """Build, corpus via shadow commit, batched + HTTP serving, and both
    checks pass; no program compiles after the warm-up (run_one_chip
    raises otherwise)."""
    import jax
    from repro.configs.base import get_config
    out = smoke.run_one_chip(get_config("minicpm3-4b").reduced(),
                             get_config("siso-embedder"), 2000, 0,
                             jax.devices()[0])
    look, toks = out["lookups"], out["first_tokens"]
    assert look["hits"] > 0 and look["flips"] == 0
    assert look["max_dsim"] <= smoke.SIM_TOL
    assert toks["misses"] > 0
    assert toks["exact"] + toks["near_ties"] == toks["misses"]


def test_sharded_phase_on_four_virtual_devices():
    """--chips 4's phase on four forced CPU devices: the sharded plane
    agrees with the single-device cache and the float64 reference."""
    code = f"""
import json, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
out = chip_smoke.run_sharded(2000, 0, n_shards=4)
print("RESULT", json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESULT" in out.stdout
    assert "decisions identical to the single-device cache" in out.stdout


def _record(smoke, store, q, hit, sim, aid, theta=0.95):
    from repro.core.semantic_cache import LookupResult
    B = len(q)
    ans = np.zeros((B, store.answer_dim), np.float32)
    for b in range(B):
        if hit[b]:
            ans[b] = store.answers[aid[b] - smoke.ID_BASE]
    res = LookupResult(np.asarray(hit), np.asarray(sim, np.float32), ans,
                       np.asarray(aid, np.int64), np.zeros(B, np.int64),
                       np.zeros(B, np.int8))
    empty = np.zeros((0, store.dim), np.float32)
    return smoke.LookupRecord(q, theta, res, empty, empty,
                              np.zeros(0, np.int64))


@pytest.mark.parametrize("fault", ["none", "flip", "sim", "row"])
def test_lookup_check_catches_wrong_results(smoke, fault):
    """The float64 check passes exact results and rejects a flipped
    decision, a drifted sim and a hit on the wrong row."""
    rng = np.random.default_rng(0)
    src = smoke.unit_rows(rng, 2, 64)
    store = smoke.build_corpus(rng, 500, 64, smoke.near_duplicates(rng, src))
    q = np.concatenate([src, smoke.unit_rows(rng, 2, 64)])
    sims = q.astype(np.float64) @ store.vectors.astype(np.float64).T
    row = sims.argmax(axis=1)
    best = sims.max(axis=1)
    hit = best >= 0.95
    assert hit.tolist() == [True, True, False, False]
    aid = np.where(hit, smoke.ID_BASE + row, -1)
    sim = best.copy()
    if fault == "flip":
        hit[2], aid[2] = True, smoke.ID_BASE + row[2]
    elif fault == "sim":
        sim[1] += 1e-3
    elif fault == "row":
        aid[0] = smoke.ID_BASE + int(np.argsort(sims[0])[-2])
        sim[0] = np.sort(sims[0])[-2]
    rec = _record(smoke, store, q, hit, sim, aid)
    if fault == "none":
        out = smoke.check_lookups(store, [rec])
        assert out["hits"] == 2 and out["flips"] == 0
    else:
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_lookups(store, [rec])
