"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cosine_topk.ops import cosine_topk
from repro.kernels.cosine_topk.ref import cosine_topk_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

RNG = np.random.default_rng(7)


def _unit(n, d, dtype=np.float32):
    x = RNG.normal(size=(n, d)).astype(dtype)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# cosine_topk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,N,D,k", [
    (1, 64, 16, 1), (4, 1000, 64, 1), (7, 333, 48, 4),
    (16, 2048, 384, 8), (3, 129, 100, 2), (8, 512, 128, 16),
])
def test_cosine_topk_matches_ref(B, N, D, k):
    q, c = _unit(B, D), _unit(N, D)
    valid = (RNG.random(N) > 0.1).astype(np.int32)
    v1, i1 = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=k,
                         valid=jnp.asarray(valid), block_n=256)
    v2, i2 = cosine_topk_ref(jnp.asarray(q), jnp.asarray(c), k=k,
                             valid=jnp.asarray(valid))
    nvalid = int(valid.sum())
    kk = min(k, nvalid)
    np.testing.assert_allclose(np.asarray(v1)[:, :kk],
                               np.asarray(v2)[:, :kk], atol=3e-6)
    assert np.array_equal(np.asarray(i1)[:, :kk], np.asarray(i2)[:, :kk])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cosine_topk_dtypes(dtype):
    q = jnp.asarray(_unit(4, 64)).astype(dtype)
    c = jnp.asarray(_unit(300, 64)).astype(dtype)
    v, i = cosine_topk(q, c, k=2)
    vr, ir = cosine_topk_ref(q, c, k=2)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr),
                               atol=2e-2 if dtype == jnp.bfloat16 else 3e-6)


def test_cosine_topk_early_exit_returns_theta_hit():
    q = _unit(4, 64)
    near = q + 0.01 * RNG.normal(size=q.shape).astype(np.float32)
    c = np.concatenate([near / np.linalg.norm(near, axis=1, keepdims=True),
                        _unit(500, 64)])
    v, i = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=1, theta=0.9,
                       block_n=128, early_exit=True)
    assert (np.asarray(v)[:, 0] >= 0.9).all()
    assert (np.asarray(i)[:, 0] < 4).all()   # found in the hot first tile


def test_cosine_topk_all_invalid():
    q, c = _unit(2, 32), _unit(64, 32)
    v, i = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=1,
                       valid=jnp.zeros(64, jnp.int32))
    assert (np.asarray(i) == -1).all()


@pytest.mark.parametrize("rows", [[3, 5], [3, 700], [1500, 2], [1999]])
def test_cosine_topk_counts_the_tiles_before_every_query_cleared(rows):
    """With early exit the kernel computes tiles up to the one in which the
    last query's best first clears theta, and counts them; the top-1 is
    the one the kernel finds without the count."""
    c = _unit(2000, 32)
    q = c[rows]                       # each query's only match >= 0.99
    block = 128
    cleared = max(r // block for r in rows)
    v, i, h, tiles = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=1,
                                 theta=0.99, block_n=block, early_exit=True,
                                 return_hit=True, return_tiles=True)
    assert np.asarray(tiles).tolist() == [cleared + 1, 16]
    v0, i0, h0 = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=1,
                             theta=0.99, block_n=block, early_exit=True,
                             return_hit=True)
    assert np.asarray(i)[:, 0].tolist() == rows == np.asarray(i0)[:, 0]\
        .tolist()
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v0))
    assert np.asarray(h).all() and np.asarray(h0).all()


def test_cosine_topk_counts_the_whole_grid_without_early_exit():
    q, c = _unit(3, 32), _unit(1000, 32)
    v, i, tiles = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=2,
                              theta=-1.0, block_n=128, return_tiles=True)
    assert np.asarray(tiles).tolist() == [8, 8]
    rv, ri = cosine_topk_ref(jnp.asarray(q), jnp.asarray(c), k=2)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), atol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


CASES = [
    dict(B=2, Lq=64, Lkv=64, H=4, Hkv=2, Dh=32, causal=True),
    dict(B=1, Lq=100, Lkv=100, H=8, Hkv=1, Dh=64, causal=True),
    dict(B=2, Lq=128, Lkv=128, H=4, Hkv=4, Dh=16, causal=True, window=32),
    dict(B=1, Lq=96, Lkv=96, H=2, Hkv=2, Dh=48, causal=True, prefix_len=16),
    dict(B=2, Lq=32, Lkv=32, H=4, Hkv=2, Dh=32, causal=False),
    dict(B=1, Lq=7, Lkv=7, H=1, Hkv=1, Dh=8, causal=True),
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_matches_ref(case):
    c = dict(case)
    causal = c.pop("causal")
    window = c.pop("window", None)
    prefix = c.pop("prefix_len", 0)
    q = RNG.normal(size=(c["B"], c["Lq"], c["H"], c["Dh"])).astype(np.float32)
    k = RNG.normal(size=(c["B"], c["Lkv"], c["Hkv"], c["Dh"])).astype(np.float32)
    v = RNG.normal(size=(c["B"], c["Lkv"], c["Hkv"], c["Dh"])).astype(np.float32)
    o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal, window=window, prefix_len=prefix,
                         block_q=32, block_k=128)
    o2 = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       causal=causal, window=window, prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(2, 64, 4, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(2, 64, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(2, 64, 2, 32)), jnp.bfloat16)
    o1 = flash_attention(q, k, v, causal=True)
    o2 = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


def test_flash_attention_agrees_with_model_layer():
    """The jnp blockwise flash in models.layers must agree with the kernel."""
    from repro.models.layers import flash_attention as model_flash
    q = RNG.normal(size=(2, 96, 4, 32)).astype(np.float32)
    k = RNG.normal(size=(2, 96, 2, 32)).astype(np.float32)
    v = RNG.normal(size=(2, 96, 2, 32)).astype(np.float32)
    o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True, block_q=32, block_k=128)
    o2 = model_flash(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                     causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,Dh,Lc", [
    (2, 8, 2, 64, 300), (1, 4, 4, 32, 1000), (3, 16, 1, 128, 77),
    (4, 8, 8, 48, 512), (1, 2, 1, 16, 5),
])
def test_decode_attention_matches_ref(B, H, Hkv, Dh, Lc):
    q = RNG.normal(size=(B, H, Dh)).astype(np.float32)
    k = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    v = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    kv_len = RNG.integers(1, Lc + 1, size=B).astype(np.int32)
    o1 = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(kv_len), block_k=128)
    o2 = decode_attention_ref(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), jnp.asarray(kv_len))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("B,H,Hkv,Dh,Lc", [(2, 8, 2, 64, 300),
                                           (1, 4, 4, 32, 513)])
def test_decode_attention_int8_kv(B, H, Hkv, Dh, Lc):
    """int8 codes + scales stream through the kernel; error bounded by
    the quantization step (§Perf C1/C2)."""
    from repro.models.lm import kv_quant
    q = RNG.normal(size=(B, H, Dh)).astype(np.float32)
    k = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    v = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    kv_len = RNG.integers(1, Lc + 1, size=B).astype(np.int32)
    kq, ks = kv_quant(jnp.asarray(k))
    vq, vs = kv_quant(jnp.asarray(v))
    o = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(kv_len),
                         k_scale=ks, v_scale=vs, block_k=128)
    o_ref = decode_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(kv_len))
    assert np.abs(np.asarray(o) - np.asarray(o_ref)).max() < 0.05


def test_decode_attention_matches_model_layer():
    from repro.models.layers import decode_attention as model_decode
    B, H, Hkv, Dh, Lc = 2, 8, 2, 64, 200
    q = RNG.normal(size=(B, H, Dh)).astype(np.float32)
    k = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    v = RNG.normal(size=(B, Lc, Hkv, Dh)).astype(np.float32)
    kv_len = np.asarray([150, 60], np.int32)
    o1 = decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(kv_len))
    o2 = model_decode(jnp.asarray(q)[:, None], jnp.asarray(k),
                      jnp.asarray(v), kv_len=jnp.asarray(kv_len))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2)[:, 0],
                               atol=2e-5)
