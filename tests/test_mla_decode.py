"""MLA decode attention in latent space against the materialising form.

``layers.mla_decode`` folds W_uk into the query and applies W_uv after the
value contraction. The reference below rebuilds every position's per-head
K and V from the latent cache, then attends, as prefill does. The two are
the same attention, reassociated.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import layers as L

B, LMAX = 4, 24
KV_LEN = [1, LMAX, 7, 13]          # one row per mask edge and two between


def materialising_decode(p, cfg, x, latent_cache, krope_cache, kv_len,
                         positions):
    Bx, Lmax = latent_cache.shape[:2]
    H = cfg.n_heads
    q_nope, q_rope = L.mla_queries(p, cfg, x, positions)
    k_nope = (latent_cache @ p["wk_b"]).reshape(Bx, Lmax, H, cfg.qk_nope_dim)
    v = (latent_cache @ p["wv_b"]).reshape(Bx, Lmax, H, cfg.v_head_dim)
    k_rope = jnp.broadcast_to(krope_cache[:, :, None, :],
                              (Bx, Lmax, H, cfg.qk_rope_dim))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = L.decode_attention(q, k, v, kv_len=kv_len)
    return out.reshape(Bx, 1, -1) @ p["wo"]


# f32: the reassociation alone. bf16: both forms round their (B, H, *)
# intermediates and the output to bf16, so they may differ by a couple of
# bf16 epsilons (2^-7) on the output.
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2 * float(jnp.finfo(jnp.bfloat16).eps)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("arch", ["minicpm3-4b", "deepseek-v2-236b"])
def test_mla_decode_matches_materialising(arch, dtype):
    cfg = get_config(arch).reduced()
    p = L.mla_init(jax.random.PRNGKey(0), cfg, dtype)
    kx, kl, kr = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (B, 1, cfg.d_model)).astype(dtype)
    latent = jax.random.normal(kl, (B, LMAX, cfg.kv_lora_rank)).astype(dtype)
    krope = jax.random.normal(kr, (B, LMAX, cfg.qk_rope_dim)).astype(dtype)
    kv_len = jnp.asarray(KV_LEN, jnp.int32)
    positions = jnp.asarray([[LMAX - 1]], jnp.int32)
    got = L.mla_decode(p, cfg, x, latent, krope, kv_len, positions)
    want = materialising_decode(p, cfg, x, latent, krope, kv_len, positions)
    assert got.dtype == dtype and got.shape == (B, 1, cfg.d_model)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


SLOTS, SLOT_LEN = 16, 512          # the served engine's decode batch


def _mla_decode_shapes(cfg, batched: bool):
    bf = jnp.bfloat16
    spec = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: L.mla_init(jax.random.PRNGKey(0), cfg, bf))
    if batched:
        def step(p, x, latent, krope, kv_len):
            return L.mla_decode(p, cfg, x, latent, krope, kv_len,
                                jnp.zeros((1, 1), jnp.int32))
    else:
        # the engine's form: vmap over slots, each slot a batch of one
        def one(p, x, latent, krope, kv_len):
            return L.mla_decode(p, cfg, x[None], latent[None], krope[None],
                                kv_len[None], (kv_len - 1)[None, None])[0]
        step = jax.vmap(one, in_axes=(None, 0, 0, 0, 0))
    return step, (params, spec((SLOTS, 1, cfg.d_model), bf),
                  spec((SLOTS, SLOT_LEN, cfg.kv_lora_rank), bf),
                  spec((SLOTS, SLOT_LEN, cfg.qk_rope_dim), bf),
                  spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("batched", [True, False], ids=["batched", "per-slot"])
def test_mla_decode_flops_at_minicpm3_widths(batched):
    """One layer's decode at the served shapes stays in latent space: the
    materialising form reads 22.1 GFLOP here (every slot's K/V rebuilt for
    all 512 positions), the latent form about 0.8."""
    cfg = get_config("minicpm3-4b")
    step, args = _mla_decode_shapes(cfg, batched)
    cost = jax.jit(step).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < cost["flops"] < 2e9
