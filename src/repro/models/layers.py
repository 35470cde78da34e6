"""Core NN layers, pure-functional JAX (no flax).

Conventions:
  * params are nested dicts of jnp arrays; init fns take a PRNGKey.
  * activations layout: (batch, seq, heads, head_dim) for attention.
  * compute dtype follows the inputs (bf16 for the big configs); softmax,
    norms and logsumexp accumulate in fp32.
  * attention uses a block-pair flash formulation: the set of (q_block,
    kv_block) tiles is enumerated statically (causal / window pruning at
    trace time), so the lowered HLO contains only useful tiles — no 2x
    causal waste — and `lax.scan` keeps HLO size O(1) in sequence length.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def split(key, n: int):
    return list(jax.random.split(key, n))


@jax.custom_vjp
def bf16_grad_barrier(x: jax.Array) -> jax.Array:
    """Identity forward; backward rounds the cotangent through bf16.
    Placed at layer boundaries it forces the cross-layer activation
    cotangents (which ride the TP all-reduces) to bf16 wire width
    (§Perf B2)."""
    return x


def _bgb_fwd(x):
    return x, None


def _bgb_bwd(_, g):
    return (g.astype(jnp.bfloat16).astype(g.dtype),)


bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)

# toggled by the launcher (CellPolicy.bf16_boundary)
_BF16_BOUNDARY: list = [False]


def set_bf16_boundary(on: bool) -> None:
    _BF16_BOUNDARY[0] = bool(on)


def dp_constrain(x: jax.Array, axes: tuple) -> jax.Array:
    """Pin the leading (batch) dim of an activation to the data-parallel
    mesh axes. Without this, GSPMD may resolve FSDP's weight/activation
    axis conflict by replicating the batch and sharding features over
    "data" (observed: 42 GiB temps on whisper train_4k) — constraining the
    layer-boundary activations forces the ZeRO-3 choice (per-layer weight
    all-gather) instead. No-op when axes is empty (single-host tests)."""
    if not axes:
        return x
    from jax.sharding import PartitionSpec as P
    ax = axes if len(axes) > 1 else axes[0]
    spec = P(ax, *([None] * (x.ndim - 1)))
    x = lax.with_sharding_constraint(x, spec)
    if _BF16_BOUNDARY[0] and x.dtype == jnp.bfloat16:
        x = bf16_grad_barrier(x)
    return x


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., L, H, D); positions: broadcastable to (..., L)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., L, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention (pure jnp, static block-pair enumeration)
# ---------------------------------------------------------------------------


def _valid_pairs(nq: int, nkv: int, bq: int, bkv: int, causal: bool,
                 window: Optional[int], q_offset: int) -> list[tuple[int, int]]:
    """Statically enumerate (q_block, kv_block) tiles with any valid entry."""
    pairs = []
    for i in range(nq):
        q_lo = q_offset + i * bq
        q_hi = q_offset + (i + 1) * bq - 1
        for j in range(nkv):
            k_lo = j * bkv
            k_hi = (j + 1) * bkv - 1
            if causal and k_lo > q_hi:
                continue
            if window is not None and k_hi <= q_lo - window:
                continue
            pairs.append((i, j))
    return pairs


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    prefix_len: int = 0, q_offset: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Blockwise online-softmax attention with GQA.

    q: (B, Lq, Hq, Dq); k: (B, Lkv, Hkv, Dq); v: (B, Lkv, Hkv, Dv).
    q_offset: global position of q[0] (prefill continuation / decode).
    kv_valid_len: optional (B,) count of valid kv positions (ragged batch).
    Returns (B, Lq, Hq, Dv).
    """
    B, Lq, Hq, Dq = q.shape
    _, Lkv, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dq)

    bq = min(block_q, Lq)
    bkv = min(block_kv, Lkv)
    # pad to block multiples
    pq = (-Lq) % bq
    pkv = (-Lkv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    Lqp, Lkvp = Lq + pq, Lkv + pkv
    nq, nkv = Lqp // bq, Lkvp // bkv

    pairs = _valid_pairs(nq, nkv, bq, bkv, causal, window, q_offset)
    pair_arr = jnp.asarray(pairs, dtype=jnp.int32)  # (P, 2)

    qb = q.reshape(B, nq, bq, Hq, Dq)
    kb = k.reshape(B, nkv, bkv, Hkv, Dq)
    vb = v.reshape(B, nkv, bkv, Hkv, Dv)

    acc0 = jnp.zeros((B, nq, bq, Hq, Dv), jnp.float32)
    m0 = jnp.full((B, nq, bq, Hq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, nq, bq, Hq), jnp.float32)

    kv_limit = None if kv_valid_len is None else kv_valid_len.astype(jnp.int32)

    def tile(carry, ij):
        acc, m, l = carry
        i, j = ij[0], ij[1]
        qt = lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)  # (B,bq,Hq,Dq)
        kt = lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)  # (B,bkv,Hkv,Dq)
        vt = lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        # GQA: (B,bq,Hkv,G,Dq) x (B,bkv,Hkv,Dq) -> (B,Hkv,G,bq,bkv)
        qg = qt.reshape(B, bq, Hkv, G, Dq)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kt,
                       preferred_element_type=jnp.float32) * scale
        qpos = q_offset + i * bq + lax.iota(jnp.int32, bq)[:, None]
        kpos = j * bkv + lax.iota(jnp.int32, bkv)[None, :]
        mask = kpos < Lkv  # kv padding  (bq, bkv)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        if prefix_len:
            mask = mask | ((kpos < prefix_len) & (kpos < Lkv))
        mask = mask[None, None, None]  # (1,1,1,bq,bkv)
        if kv_limit is not None:  # ragged batch: (B,1,1,1,bkv)
            mask = mask & (kpos[None, :] < kv_limit[:, None, None])[:, None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m_t = jnp.max(s, axis=-1)  # (B,Hkv,G,bq)
        m_t = jnp.transpose(m_t, (0, 3, 1, 2)).reshape(B, bq, Hq)
        m_i = lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        l_i = lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        a_i = lax.dynamic_index_in_dim(acc, i, 1, keepdims=False)
        m_new = jnp.maximum(m_i, m_t)
        m_b = jnp.transpose(m_new.reshape(B, bq, Hkv, G), (0, 2, 3, 1))[..., None]
        p = jnp.exp(s - m_b)  # (B,Hkv,G,bq,bkv) fp32
        p = jnp.where(jnp.isfinite(m_b), p, 0.0)
        l_t = jnp.sum(p, axis=-1)
        l_t = jnp.transpose(l_t, (0, 3, 1, 2)).reshape(B, bq, Hq)
        corr = jnp.exp(m_i - m_new)
        corr = jnp.where(jnp.isfinite(m_i), corr, 0.0)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), vt,
                        preferred_element_type=jnp.float32)
        pv = pv.reshape(B, bq, Hq, Dv)
        a_new = a_i * corr[..., None] + pv
        l_new = l_i * corr + l_t
        acc = lax.dynamic_update_index_in_dim(acc, a_new, i, 1)
        m = lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        return (acc, m, l), None

    (acc, m, l), _ = lax.scan(tile, (acc0, m0, l0), pair_arr)
    out = acc / jnp.maximum(l[..., None], 1e-37)
    out = out.reshape(B, Lqp, Hq, Dv)[:, :Lq]
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     kv_len: jax.Array, window: Optional[int] = None) -> jax.Array:
    """Single-token attention over a (possibly ring-buffered) KV cache.

    q: (B, 1, Hq, D); k_cache/v_cache: (B, Lmax, Hkv, D);
    kv_len: (B,) number of valid cache entries (for SWA ring buffers the
    validity mask covers the whole buffer once it has wrapped).
    """
    B, Lmax, Hkv, Dv = v_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(q.shape[-1])
    qg = q.reshape(B, Hkv, G, q.shape[-1])
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = lax.iota(jnp.int32, Lmax)[None, :]
    mask = kpos < kv_len[:, None]
    if window is not None:
        mask = mask & (kpos > kv_len[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype) -> Params:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split(key, 5)
    p: Params = {
        "wq": dense_init(ks[0], d, H * Dh, dtype),
        "wk": dense_init(ks[1], d, Hkv * Dh, dtype),
        "wv": dense_init(ks[2], d, Hkv * Dh, dtype),
        "wo": dense_init(ks[3], H * Dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((Hkv * Dh,), dtype)
        p["bv"] = jnp.zeros((Hkv * Dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(Dh, dtype)
        p["k_norm"] = rmsnorm_init(Dh, dtype)
    return p


def gqa_qkv(p: Params, cfg, x: jax.Array, positions: jax.Array,
            rope: bool = True):
    B, L, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, L, H, Dh)
    k = k.reshape(B, L, Hkv, Dh)
    v = v.reshape(B, L, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend(p: Params, cfg, x: jax.Array, positions: jax.Array, *,
               causal: bool = True, prefix_len: int = 0,
               block_q: int = 512, block_kv: int = 512) -> jax.Array:
    q, k, v = gqa_qkv(p, cfg, x, positions)
    out = flash_attention(q, k, v, causal=causal, window=cfg.window,
                          prefix_len=prefix_len, block_q=block_q,
                          block_kv=block_kv)
    B, L = x.shape[:2]
    return out.reshape(B, L, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) block
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = split(key, 8)
    p: Params = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[1], cfg.q_lora_rank, H * qd, dtype)
    else:
        p["wq"] = dense_init(ks[0], d, H * qd, dtype)
    p["wkv_a"] = dense_init(ks[2], d, cfg.kv_lora_rank, dtype)
    p["kv_norm"] = rmsnorm_init(cfg.kv_lora_rank, dtype)
    p["wk_rope"] = dense_init(ks[3], d, cfg.qk_rope_dim, dtype)
    p["wk_b"] = dense_init(ks[4], cfg.kv_lora_rank, H * cfg.qk_nope_dim, dtype)
    p["wv_b"] = dense_init(ks[5], cfg.kv_lora_rank, H * cfg.v_head_dim, dtype)
    p["wo"] = dense_init(ks[6], H * cfg.v_head_dim, d, dtype)
    return p


def mla_latent(p: Params, cfg, x: jax.Array, positions: jax.Array):
    """Compute the (latent, k_rope) pair that the MLA cache stores."""
    latent = rmsnorm(p["kv_norm"], x @ p["wkv_a"])  # (B,L,R)
    k_rope = (x @ p["wk_rope"])[:, :, None, :]       # (B,L,1,rope_d)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return latent, k_rope[:, :, 0, :]


def mla_queries(p: Params, cfg, x: jax.Array, positions: jax.Array):
    B, L, _ = x.shape
    H = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = rmsnorm(p["q_norm"], x @ p["wq_a"]) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, L, H, qd)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attend(p: Params, cfg, x: jax.Array, positions: jax.Array, *,
               causal: bool = True, block_q: int = 512,
               block_kv: int = 512) -> jax.Array:
    """Prefill/train path: materialize per-head K/V from the latent."""
    B, L, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = mla_queries(p, cfg, x, positions)
    latent, k_rope = mla_latent(p, cfg, x, positions)
    k_nope = (latent @ p["wk_b"]).reshape(B, L, H, cfg.qk_nope_dim)
    v = (latent @ p["wv_b"]).reshape(B, L, H, cfg.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, L, H, cfg.qk_rope_dim))],
        axis=-1)
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_kv=block_kv)
    return out.reshape(B, L, -1) @ p["wo"]


def mla_decode(p: Params, cfg, x: jax.Array, latent_cache: jax.Array,
               krope_cache: jax.Array, kv_len: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Single-token attention over the latent cache, in latent space.

    latent_cache: (B, Lmax, R); krope_cache: (B, Lmax, rope_d); kv_len: (B,).
    W_uk is folded into the query and W_uv applied after the value
    contraction, so no per-head K/V is built from the cache: the attention
    of ``mla_attend`` reassociated (which materialises K/V, as there L
    query tokens share each key). Contractions take the cache's dtype and
    accumulate in f32, as ``decode_attention`` does.
    """
    B = x.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    dt = latent_cache.dtype
    f32 = jnp.float32
    q_nope, q_rope = mla_queries(p, cfg, x, positions)  # (B,1,H,*)
    wk_b = p["wk_b"].reshape(R, H, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk_b,
                       preferred_element_type=f32).astype(dt)  # (B,H,R)
    s = jnp.einsum("bhr,blr->bhl", q_lat, latent_cache,
                   preferred_element_type=f32)
    s += jnp.einsum("bhd,bld->bhl", q_rope[:, 0], krope_cache,
                    preferred_element_type=f32)
    s *= 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    kpos = lax.iota(jnp.int32, latent_cache.shape[1])[None, :]
    s = jnp.where((kpos < kv_len[:, None])[:, None, :], s, -jnp.inf)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhl,blr->bhr", pattn.astype(dt), latent_cache,
                       preferred_element_type=f32)  # (B,H,R)
    wv_b = p["wv_b"].reshape(R, H, cfg.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(dt), wv_b,
                     preferred_element_type=f32).astype(x.dtype)
    return out.reshape(B, 1, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

_ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=True),
    "relu_sq": lambda x: jnp.square(jax.nn.relu(x)),
}


def mlp_init(key, d: int, d_ff: int, dtype, gated: bool = True) -> Params:
    ks = split(key, 3)
    p = {"w_up": dense_init(ks[0], d, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d, d_ff, dtype)
    return p


def mlp(p: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    a = _ACTS[act]
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = a(x @ p["w_gate"]) * h
    else:
        h = a(h)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (scatter-based dispatch; pjit-friendly). See DESIGN.md §3.
# ---------------------------------------------------------------------------


def moe_init(key, cfg, dtype) -> Params:
    d, E, dff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], d, E, dtype, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, dff), jnp.float32)
                   / math.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, dff), jnp.float32)
                 / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, dff, d), jnp.float32)
                   / math.sqrt(dff)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.d_ff_expert * cfg.n_shared_experts,
                               dtype)
    return p


def moe_gating(logits: jax.Array, top_k: int, renormalize: bool = True,
               n_group: int = 1, topk_group: int = 1, scale: float = 1.0):
    """Returns (gates (T,k), idx (T,k), aux_loss scalar).

    Softmax over all E router outputs in f32. With n_group > 1 the experts
    form n_group contiguous groups, a group scores its best expert, and
    only the experts of the topk_group best groups can be taken
    (DeepSeek-V2's group_limited_greedy). The gates are the top_k softmax
    scores, renormalised over them if ``renormalize``, times ``scale``.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if n_group > 1:
        T, E = probs.shape
        grouped = probs.reshape(T, n_group, E // n_group)
        _, best = lax.top_k(jnp.max(grouped, axis=-1), topk_group)
        kept = jnp.any(jax.nn.one_hot(best, n_group, dtype=jnp.bool_), axis=1)
        scores = jnp.where(kept[:, :, None], grouped, 0.0).reshape(T, E)
    gates, idx = lax.top_k(scores, top_k)
    if renormalize:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    gates = gates * scale
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


# mesh used by shard_map-based layers; set via set_shard_mesh() by the
# launcher before tracing (the legacy `with mesh:` context does not
# populate jax.sharding.get_abstract_mesh()).
_SHARD_MESH: list = [None]


def set_shard_mesh(mesh) -> None:
    _SHARD_MESH[0] = mesh


def moe_apply_shard_map(p: Params, cfg, x: jax.Array, dropless: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard MoE dispatch (§Perf A3): tokens stay sharded over the DP
    axes through dispatch — each shard scatters only its LOCAL tokens into
    a local-capacity (E, C_loc, d) buffer, so no dispatch-buffer
    all-reduce crosses the wire. Expert ffn dims stay TP over "model";
    the combine's partial sums psum over "model" exactly like a dense MLP.
    Returns what ``_moe`` returns, the routing counter over all experts.
    """
    mesh = _SHARD_MESH[0]
    if mesh is None or not mesh.axis_names:
        mesh = jax.sharding.get_abstract_mesh()
    dp = tuple(a for a in cfg.act_dp
               if mesh is not None and a in mesh.axis_names)
    if not dp or "model" not in getattr(mesh, "axis_names", ()):
        return _moe(p, cfg.replace(moe_impl="scatter"), x, None, dropless)
    dp_ax = dp if len(dp) > 1 else dp[0]
    local_cfg = cfg.replace(moe_impl="scatter", act_dp=())
    from jax.sharding import PartitionSpec as P
    tp = mesh.shape["model"]
    ep = cfg.n_experts % tp == 0 and cfg.n_experts >= tp  # expert parallel

    def kern(p_local, x_local):
        # EP: experts sharded over "model", dispatch to the local range;
        # else experts whole, ffn dim sliced over "model"
        lo = lax.axis_index("model") * (cfg.n_experts // tp) if ep else None
        y, aux, routed = _moe(p_local, local_cfg, x_local, lo, dropless)
        y = jax.lax.psum(y, "model")  # combine: EP partial outputs and/or
        #                               TP ffn partial sums (+ shared)
        aux = jax.lax.pmean(aux, dp_ax)
        routed = jax.lax.psum(routed, dp_ax)
        return y, aux, routed

    if ep:
        pspecs = {"router": P(), "w_gate": P("model", None, None),
                  "w_up": P("model", None, None),
                  "w_down": P("model", None, None)}
    else:
        pspecs = {"router": P(), "w_gate": P(None, None, "model"),
                  "w_up": P(None, None, "model"),
                  "w_down": P(None, "model", None)}
    if "shared" in p:
        pspecs["shared"] = {k: (P(None, "model") if k in ("w_gate", "w_up")
                                else P("model", None))
                            for k in p["shared"]}
    fn = jax.shard_map(kern, mesh=mesh,
                       in_specs=(pspecs, P(dp_ax, None, None)),
                       out_specs=(P(dp_ax, None, None), P(),
                                  P("model") if ep else P()),
                       check_vma=False)
    return fn(p, x)


def moe_apply(p: Params, cfg, x: jax.Array,
              expert_lo: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Training path: x (B, L, d) -> (out, aux_loss), with each expert
    taking at most its static capacity (``cfg.capacity_factor``) of
    assignments; the overflow is dropped. See ``_moe``."""
    out, aux, _ = _moe(p, cfg, x, expert_lo, dropless=False)
    return out, aux


def moe_serve(p: Params, cfg, x: jax.Array,
              expert_lo: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Served path (prefill, decode): x (B, L, d) -> (out, routed), where
    no assignment is dropped, so a token's output does not depend on the
    other tokens of its batch. ``routed`` (E_held,) int32 counts the
    assignments each held expert received. See ``_moe``."""
    out, _, routed = _moe(p, cfg, x, expert_lo, dropless=True)
    return out, routed


def _moe(p: Params, cfg, x: jax.Array, expert_lo: jax.Array | None,
         dropless: bool) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, L, d) -> (out, aux_loss, routed).

    The layer holds the experts [expert_lo, expert_lo + len(w_gate)) of
    the cfg.n_experts it routes over (expert_lo None: from 0). Assignments
    outside the range go to the trash slot and contribute zero (under the
    shard_map EP path the cross-shard psum completes them; on one chip
    they are the absent chips' part). The shared experts are computed in
    full. ``routed`` counts the assignments each held expert received.

    Sort-free scatter dispatch with static capacity C:
      1. router (f32 logits) -> top-k experts per token (``moe_gating``)
      2. per-(token,k) slot position inside its expert via sorted ranking
      3. scatter tokens into an (E, C, d) buffer (overflow dropped)
      4. grouped expert FFN as batched matmul (MXU-shaped)
      5. gather back + gate-weighted combine
    Dropless, C is the number of tokens: a token takes an expert at most
    once, so no expert can overflow. Else C comes from capacity_factor.
    The (E, C, d) buffer is sharded over the `model` axis (expert
    parallelism); with activations replicated over `model`, dispatch needs
    no all-to-all and combine rides the existing TP psum.

    cfg.moe_chunk_tokens > 0 bounds the live (E, C, *) buffers by scanning
    the token stream in chunks (§Perf A1: 1M-token prefill shrank 106 GiB
    -> fits, flops unchanged).
    """
    if cfg.moe_impl == "shard_map" and cfg.act_dp:
        return moe_apply_shard_map(p, cfg, x, dropless)
    B, L, d = x.shape
    T = B * L
    E_loc = p["w_gate"].shape[0]          # < E: this chip's share
    chunk = cfg.moe_chunk_tokens
    if chunk and T > chunk:
        while T % chunk:                  # largest divisor <= requested
            chunk -= 1
        xt = x.reshape(T // chunk, 1, chunk, d)

        def body(acc, xc):
            yc, a, r = _moe(p, cfg.replace(moe_chunk_tokens=0), xc,
                            expert_lo, dropless)
            return (acc[0] + a, acc[1] + r), yc

        (aux, routed), y = lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((E_loc,), jnp.int32)),
            xt)
        return y.reshape(B, L, d), aux / (T // chunk), routed
    E, k = cfg.n_experts, cfg.top_k
    C = T if dropless else \
        max(8, int(math.ceil(cfg.capacity_factor * T * k / E / 8.0)) * 8)
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt, p["router"], preferred_element_type=jnp.float32)
        gates, idx, aux = moe_gating(logits, k, cfg.norm_topk_prob,
                                     cfg.n_group, cfg.topk_group,
                                     cfg.routed_scaling_factor)
        flat_e = idx.reshape(-1)  # (T*k,)
        # rank of each assignment within its expert (stable by token order)
        order = jnp.argsort(flat_e, stable=True)  # (T*k,)
        ranks_sorted = lax.iota(jnp.int32, T * k)
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos_sorted = ranks_sorted - starts[flat_e[order]]
        pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < C
        lo = 0 if expert_lo is None else expert_lo
        routed = lax.dynamic_slice(counts, (lo,), (E_loc,)).astype(jnp.int32)
        le = flat_e - lo
        if expert_lo is not None or E_loc != E:
            keep = keep & (le >= 0) & (le < E_loc)
        slot = jnp.where(keep, le * C + pos, E_loc * C)  # E_loc*C = trash

        x_rep = jnp.repeat(xt, k, axis=0)  # (T*k, d)
        buf = jnp.zeros((E_loc * C + 1, d), x.dtype).at[slot].add(x_rep)
        buf = buf[:-1].reshape(E_loc, C, d)

    with jax.named_scope("moe.experts"):
        a = _ACTS[cfg.act]
        h = a(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", buf, p["w_up"])
        y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # (E_loc, C, d)

        y_flat = jnp.concatenate([y.reshape(E_loc * C, d),
                                  jnp.zeros((1, d), y.dtype)], axis=0)
        y_tok = y_flat[slot]  # (T*k, d) — dropped/foreign tokens read zeros
        y_tok = y_tok * gates.reshape(-1, 1).astype(y_tok.dtype)
        out = jnp.sum(y_tok.reshape(T, k, d), axis=1)

    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + mlp(p["shared"], xt, cfg.act)
    return out.reshape(B, L, d), aux, routed
