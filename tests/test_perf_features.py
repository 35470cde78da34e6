"""§Perf features: chunked/shard_map MoE and int8 KV correctness."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import layers as L, lm

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_moe_chunked_matches_single_shot(rng):
    cfg = get_config("mixtral-8x7b").reduced().replace(
        dtype="float32", capacity_factor=8.0)
    p = L.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 32, cfg.d_model)).astype(np.float32))
    y1, a1 = L.moe_apply(p, cfg, x)
    y2, a2 = L.moe_apply(p, cfg.replace(moe_chunk_tokens=16), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_moe_shard_map_matches_plain():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.models import layers as L
mesh = jax.make_mesh((2, 4), ("data", "model"))
L.set_shard_mesh(mesh)
rng = np.random.default_rng(0)
for arch in ["mixtral-8x7b", "deepseek-v2-236b"]:
    cfg = get_config(arch).reduced().replace(dtype="float32",
                                             capacity_factor=8.0)
    p = L.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))
    y_ref, _ = L.moe_apply(p, cfg, x)
    cfg_sm = cfg.replace(moe_impl="shard_map", act_dp=("data",))
    with mesh:
        y_sm, _ = jax.jit(lambda p, x: L.moe_apply(p, cfg_sm, x))(p, x)
    err = np.abs(np.asarray(y_sm) - np.asarray(y_ref)).max()
    assert err < 1e-4, (arch, err)
    _, r_ref = L.moe_serve(p, cfg, x)
    with mesh:
        _, r_sm = jax.jit(lambda p, x: L.moe_serve(p, cfg_sm, x))(p, x)
    np.testing.assert_array_equal(np.asarray(r_sm), np.asarray(r_ref))
print("SM_MOE_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SM_MOE_OK" in out.stdout


def test_int8_kv_decode_close_to_fp(rng):
    cfg = get_config("qwen3-14b").reduced().replace(dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, L_ = 2, 24
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, L_)), jnp.int32)}
    full, _ = lm.forward(params, cfg, batch)
    cfg8 = cfg.replace(kv_dtype="int8")
    cache = lm.init_cache(cfg8, B, L_ + 4)
    assert cache["k"].dtype == jnp.int8
    lg, cache = lm.prefill(params, cfg8,
                           {"tokens": batch["tokens"][:, :L_ - 1]}, cache)
    lg2, _ = lm.decode_step(params, cfg8, batch["tokens"][:, L_ - 1:],
                            cache, jnp.asarray(L_ - 1, jnp.int32))
    ref = np.asarray(full[:, -1])
    rel = np.abs(np.asarray(lg2) - ref).max() / np.abs(ref).max()
    assert rel < 0.05
    assert (np.argmax(np.asarray(lg2), -1) == np.argmax(ref, -1)).all()


def test_kv_quant_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(2, 7, 4, 16)).astype(np.float32))
    q, s = lm.kv_quant(x)
    back = lm.kv_dequant(q, s, jnp.float32)
    rel = float(jnp.max(jnp.abs(back - x)) / jnp.max(jnp.abs(x)))
    assert rel < 0.02


def test_optimized_policies_resolve():
    from repro.launch.steps import OPTIMIZED, optimized_policy
    for (arch, shape) in OPTIMIZED:
        pol = optimized_policy(arch, shape)
        assert pol is not None
