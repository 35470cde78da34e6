"""Unified config-driven LM: dense / MoE / RWKV6 / Mamba2-hybrid / VLM /
encoder-decoder, with stacked-parameter `lax.scan` over layers (HLO size is
O(1) in depth), per-layer remat, and separate train / prefill / decode paths.

Public entry points:
    init_params(key, cfg)                       -> params
    forward(params, cfg, batch)                 -> (logits, aux_loss)
    init_cache(cfg, batch, max_len, dtype)      -> cache
    prefill(params, cfg, batch, cache)          -> (last_logits, cache)
    decode_step(params, cfg, tokens, cache, pos)-> (logits, cache)

With ``routing=True`` prefill and decode_step also return the routing
counter of an MoE model (None for one without experts).

`batch` is a dict: tokens (B, L) int32, plus modality-stub inputs
(patch_embed for VLM, frames for audio) per DESIGN.md §5.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _norm_init(cfg, d, dtype):
    return (L.layernorm_init(d, dtype) if cfg.family == "audio"
            else L.rmsnorm_init(d, dtype))


def _norm(cfg, p, x):
    return L.layernorm(p, x) if cfg.family == "audio" else L.rmsnorm(p, x)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg, dtype):
    if cfg.attn_kind == "mla":
        return L.mla_init(key, cfg, dtype)
    return L.gqa_init(key, cfg, dtype)


def _block_init(key, cfg: ModelConfig, kind: str, dtype) -> Params:
    ks = L.split(key, 4)
    if kind == "rwkv6":
        return S.rwkv6_init(key, cfg, dtype)
    if kind == "mamba2":
        return S.mamba2_init(key, cfg, dtype)
    p: Params = {"ln1": _norm_init(cfg, cfg.d_model, dtype),
                 "attn": _attn_init(ks[0], cfg, dtype),
                 "ln2": _norm_init(cfg, cfg.d_model, dtype)}
    if kind == "moe":
        p["mlp"] = L.moe_init(ks[1], cfg, dtype)
    else:
        gated = cfg.act != "gelu" or cfg.family in ("vlm",)
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype, gated=gated)
    if cfg.is_encoder_decoder and kind == "decoder":
        p["ln_x"] = _norm_init(cfg, cfg.d_model, dtype)
        p["xattn"] = L.gqa_init(ks[2], cfg, dtype)
    return p


def _zamba_shared_init(key, cfg, dtype) -> Params:
    """Zamba2 weight-shared (attention+MLP) block over concat([x, x0])."""
    d2 = 2 * cfg.d_model
    H, Dh = cfg.n_heads, cfg.head_dim
    ks = L.split(key, 8)
    n_inv = cfg.n_layers // cfg.attn_every
    r = cfg.shared_lora_rank
    return {
        "ln": L.rmsnorm_init(d2, dtype),
        "wq": L.dense_init(ks[0], d2, H * Dh, dtype),
        "wk": L.dense_init(ks[1], d2, H * Dh, dtype),
        "wv": L.dense_init(ks[2], d2, H * Dh, dtype),
        "wo": L.dense_init(ks[3], H * Dh, cfg.d_model, dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "mlp": L.mlp_init(ks[4], cfg.d_model, cfg.d_ff, dtype),
        # per-invocation LoRA deltas on the fused qkv input
        "lora_a": (jax.random.normal(ks[5], (n_inv, d2, r), jnp.float32)
                   * 0.01).astype(dtype),
        "lora_b": jnp.zeros((n_inv, r, H * Dh), dtype),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = _dtype(cfg)
    ks = L.split(key, 12)
    d = cfg.d_model
    p: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.padded_vocab, d), jnp.float32)
                  * 0.02).astype(dtype),
        "final_norm": _norm_init(cfg, d, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], d, cfg.padded_vocab, dtype,
                                    scale=0.02)

    kind = _main_kind(cfg)
    n_scan = cfg.n_layers - cfg.first_dense_layers
    layer_keys = jnp.stack(L.split(ks[2], n_scan))
    p["blocks"] = jax.vmap(lambda k: _block_init(k, cfg, kind, dtype))(layer_keys)
    if cfg.first_dense_layers:
        dense_keys = L.split(ks[3], cfg.first_dense_layers)
        p["dense0"] = [_block_init(k, cfg, "dense", dtype)
                       for k in dense_keys]
    if cfg.family == "hybrid":
        p["shared_attn"] = _zamba_shared_init(ks[4], cfg, dtype)
    if cfg.is_encoder_decoder:
        enc_keys = jnp.stack(L.split(ks[5], cfg.enc_layers))
        p["enc_blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, "dense", dtype))(enc_keys)
        dec_keys = jnp.stack(L.split(ks[6], cfg.n_layers))
        p["blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, "decoder", dtype))(dec_keys)
        p["enc_norm"] = _norm_init(cfg, d, dtype)
    return p


def _main_kind(cfg: ModelConfig) -> str:
    if cfg.is_encoder_decoder:
        return "decoder"
    if cfg.ssm_kind == "rwkv6":
        return "rwkv6"
    if cfg.ssm_kind == "mamba2":
        return "mamba2"
    if cfg.is_moe:
        return "moe"
    return "dense"


# ---------------------------------------------------------------------------
# transformer block forward (full-sequence: train / prefill)
# ---------------------------------------------------------------------------


def _dense_block_fwd(p: Params, cfg, x, positions, *, causal=True,
                     prefix_len=0, memory=None):
    """Standard pre-norm block; memory != None adds cross-attention."""
    h = _norm(cfg, p["ln1"], x)
    if cfg.attn_kind == "mla":
        a = L.mla_attend(p["attn"], cfg, h, positions, causal=causal)
    else:
        a = L.gqa_attend(p["attn"], cfg, h, positions, causal=causal,
                         prefix_len=prefix_len)
    x = x + a
    if memory is not None:
        h = _norm(cfg, p["ln_x"], x)
        q, _, _ = L.gqa_qkv(p["xattn"], cfg, h, positions, rope=False)
        mem_pos = jnp.arange(memory.shape[1])
        _, k, v = L.gqa_qkv(p["xattn"], cfg, memory, mem_pos, rope=False)
        a = L.flash_attention(q, k, v, causal=False)
        x = x + a.reshape(x.shape[0], x.shape[1], -1) @ p["xattn"]["wo"]
    h = _norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe and "router" in p["mlp"]:
        m, aux = L.moe_apply(p["mlp"], cfg, h)
    else:
        m = L.mlp(p["mlp"], h, cfg.act)
    return x + m, aux


def _zamba_shared_fwd(sp: Params, cfg, x, x0, inv: jax.Array, positions,
                      kv_cache=None, pos=None, kv_len=None):
    """Shared attn+MLP block. inv: invocation index (traced). Returns
    (x, (k_new, v_new)) — caches returned for decode wiring."""
    B = x.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    cat = jnp.concatenate([x, x0], axis=-1)
    h = L.rmsnorm(sp["ln"], cat)
    la = lax.dynamic_index_in_dim(sp["lora_a"], inv, 0, keepdims=False)
    lb = lax.dynamic_index_in_dim(sp["lora_b"], inv, 0, keepdims=False)
    q = (h @ sp["wq"] + (h @ la) @ lb).reshape(B, -1, H, Dh)
    k = (h @ sp["wk"]).reshape(B, -1, H, Dh)
    v = (h @ sp["wv"]).reshape(B, -1, H, Dh)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if kv_cache is None:
        a = L.flash_attention(q, k, v, causal=True)
    else:
        k_full = _write_row(kv_cache[0], k, pos)
        v_full = _write_row(kv_cache[1], v, pos)
        a = L.decode_attention(q, k_full, v_full, kv_len=kv_len)
        k, v = k_full, v_full
    x = x + a.reshape(B, -1, H * Dh) @ sp["wo"]
    h2 = L.rmsnorm(sp["ln2"], x)
    x = x + L.mlp(sp["mlp"], h2, cfg.act)
    return x, (k, v)


# ---------------------------------------------------------------------------
# full forward (training)
# ---------------------------------------------------------------------------


def embed_tokens(p: Params, cfg, tokens: jax.Array) -> jax.Array:
    x = p["embed"][tokens]
    if cfg.family == "vlm":  # gemma convention
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(p: Params, cfg, x: jax.Array) -> jax.Array:
    logits = x @ (p["embed"].T if cfg.tie_embeddings else p["lm_head"])
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(logits.shape[-1]) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    return logits


def _assemble_input(p, cfg, batch):
    """tokens + modality stubs -> (x (B,L,d), prefix_len)."""
    if cfg.family == "vlm":
        x_txt = embed_tokens(p, cfg, batch["tokens"])
        x = jnp.concatenate([batch["patch_embed"].astype(x_txt.dtype), x_txt],
                            axis=1)
        return L.dp_constrain(x, cfg.act_dp), cfg.prefix_len
    return L.dp_constrain(embed_tokens(p, cfg, batch["tokens"]), cfg.act_dp), 0


def _encode(p: Params, cfg, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings."""
    x = frames.astype(_dtype(cfg))
    positions = jnp.arange(x.shape[1])

    def body(x, bp):
        x = L.dp_constrain(x, cfg.act_dp)
        x, _ = _dense_block_fwd(bp, cfg, x, positions, causal=False)
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = lax.scan(fn, L.dp_constrain(x, cfg.act_dp), p["enc_blocks"])
    return _norm(cfg, p["enc_norm"], x)


def forward(p: Params, cfg: ModelConfig, batch: dict) -> tuple[jax.Array, jax.Array]:
    """Training forward. Returns (logits (B,L,V over token positions), aux)."""
    x, aux, prefix_len = forward_features(p, cfg, batch)
    logits = unembed(p, cfg, x)
    if cfg.family == "vlm":
        logits = logits[:, prefix_len:]
    return logits, aux


def forward_features(p: Params, cfg: ModelConfig, batch: dict
                     ) -> tuple[jax.Array, jax.Array, int]:
    """Forward up to (and including) the final norm — no unembedding.
    Returns (features (B, Lx, d), aux_loss, prefix_len). The train step uses
    this with a CHUNKED cross-entropy so (B, L, vocab) logits are never
    materialized (vocab-TP + sequence chunking)."""
    x, prefix_len = _assemble_input(p, cfg, batch)
    B, Lx, d = x.shape
    positions = jnp.arange(Lx)
    memory = _encode(p, cfg, batch["frames"]) if cfg.is_encoder_decoder else None
    aux_total = jnp.zeros((), jnp.float32)
    kind = _main_kind(cfg)

    def _dense0_fwd(blk, x):
        # close over cfg/positions: jax.checkpoint must not trace cfg
        return _dense_block_fwd(blk, cfg, x, positions, causal=True)

    for blk in p.get("dense0", []):
        fwd = jax.checkpoint(_dense0_fwd) if cfg.remat else _dense0_fwd
        x, aux = fwd(blk, x)
        aux_total = aux_total + aux

    if kind in ("dense", "moe", "decoder"):
        def body(carry, bp):
            x, aux = carry
            x = L.dp_constrain(x, cfg.act_dp)
            x, a = _dense_block_fwd(bp, cfg, x, positions, causal=True,
                                    prefix_len=prefix_len, memory=memory)
            return (x, aux + a), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_total), _ = lax.scan(fn, (x, aux_total), p["blocks"])
    elif kind == "rwkv6":
        def body(x, bp):
            x = L.dp_constrain(x, cfg.act_dp)
            x, _ = S.rwkv6_block(bp, cfg, x, None, cfg.chunk_size)
            return x, None

        fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = lax.scan(fn, x, p["blocks"])
    elif kind == "mamba2":
        x = _hybrid_forward(p, cfg, x)
    return _norm(cfg, p["final_norm"], x), aux_total, prefix_len


def _hybrid_forward(p: Params, cfg, x):
    """Zamba2: Mamba2 stack with periodic shared attention (cond-in-scan)."""
    x0 = x
    n = cfg.n_layers
    positions = jnp.arange(x.shape[1])
    every = cfg.attn_every
    n_inv = n // every
    is_attn = jnp.array([(i % every == every - 1) and (i // every < n_inv)
                         for i in range(n)])
    inv_idx = jnp.array([min(i // every, n_inv - 1) for i in range(n)],
                        jnp.int32)

    def body(x, inp):
        bp, attn_flag, inv = inp
        x = L.dp_constrain(x, cfg.act_dp)
        x, _ = S.mamba2_block(bp, cfg, x, None, cfg.chunk_size)

        def with_attn(x):
            y, _ = _zamba_shared_fwd(p["shared_attn"], cfg, x, x0, inv,
                                     positions)
            return y

        x = lax.cond(attn_flag, with_attn, lambda x: x, x)
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = lax.scan(fn, x, (p["blocks"], is_attn, inv_idx))
    return x


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Ring-buffer length for SWA archs, else max_len."""
    if cfg.window is not None:
        return min(cfg.window, max_len)
    return max_len


def _ring_place(kv: jax.Array, seq_len: int, ring_len: int) -> jax.Array:
    """Align prefill's trailing-`ring_len` slice with decode's pos%ring slots.

    kv: (B, ring_len', ...) holding positions [seq_len-ring_len' .. seq_len).
    Token t must land at slot t % ring_len so later decode overwrites the
    oldest entry first (attention itself is slot-order invariant: RoPE is
    applied before caching)."""
    if kv.shape[1] < ring_len or seq_len <= ring_len:
        return kv
    return jnp.roll(kv, seq_len % ring_len, axis=1)


def _store(cache_arr: jax.Array, kv: jax.Array, layer_offset: int = 0
           ) -> jax.Array:
    """Write stacked per-layer kv (n?, B, L, ...) into cache (N, B, Lc, ...)
    at sequence offset 0 / layer offset `layer_offset`."""
    idx = (layer_offset,) + (0,) * (cache_arr.ndim - 1)
    return lax.dynamic_update_slice(cache_arr, kv.astype(cache_arr.dtype), idx)


def _write_row(leaf: jax.Array, row: jax.Array, write_pos: jax.Array
               ) -> jax.Array:
    """Write each batch row's new entry, row (B, 1, ...), into the cache
    leaf (B, Lc, ...) at write_pos.

    A scalar write_pos, shared by the rows, is one slice update. A (B,)
    write_pos, a position per row, is a one-hot select over the position
    axis: an indexed update at per-row positions lowers to a scatter,
    which XLA runs as a loop over the rows."""
    if jnp.ndim(write_pos) == 0:
        return lax.dynamic_update_slice(
            leaf, row, (0, write_pos) + (0,) * (leaf.ndim - 2))
    at = lax.broadcasted_iota(jnp.int32, leaf.shape[:2], 1) \
        == write_pos[:, None]
    return jnp.where(at.reshape(at.shape + (1,) * (leaf.ndim - 2)), row, leaf)


def kv_quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(…, H, D) -> (int8 codes, f16 per-(…, H) symmetric scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale.astype(jnp.float16)


def kv_dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Fuses into the attention matmul's operand stream on TPU (the
    Pallas decode kernel reads int8 directly)."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Params:
    dtype = dtype or _dtype(cfg)
    n = cfg.n_layers
    B = batch
    Lc = cache_len(cfg, max_len)
    kind = _main_kind(cfg)
    if kind in ("dense", "moe", "decoder"):
        if cfg.attn_kind == "mla":
            cache: Params = {
                "latent": jnp.zeros((n, B, Lc, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((n, B, Lc, cfg.qk_rope_dim), dtype),
            }
        elif cfg.kv_dtype == "int8":
            # KVQuant-style: int8 codes + per-(position, head) f16 scales
            # (scale arrays are KV/(2*Dh) bytes — negligible). §Perf C1.
            Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
            cache = {"k": jnp.zeros((n, B, Lc, Hkv, Dh), jnp.int8),
                     "v": jnp.zeros((n, B, Lc, Hkv, Dh), jnp.int8),
                     "k_scale": jnp.zeros((n, B, Lc, Hkv), jnp.float16),
                     "v_scale": jnp.zeros((n, B, Lc, Hkv), jnp.float16)}
        else:
            Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
            cache = {"k": jnp.zeros((n, B, Lc, Hkv, Dh), dtype),
                     "v": jnp.zeros((n, B, Lc, Hkv, Dh), dtype)}
        if cfg.first_dense_layers and cfg.attn_kind == "mla":
            pass  # dense0 layers are MLA too (deepseek) — share stacked cache
        if cfg.is_encoder_decoder:
            H = cfg.n_heads
            cache["xk"] = jnp.zeros((n, B, cfg.enc_len, H, cfg.head_dim), dtype)
            cache["xv"] = jnp.zeros((n, B, cfg.enc_len, H, cfg.head_dim), dtype)
        return cache
    if kind == "rwkv6":
        H, K = cfg.ssm_heads, cfg.ssm_head_dim
        return {"s": jnp.zeros((n, B, H, K, K), jnp.float32),
                "tm_x": jnp.zeros((n, B, cfg.d_model), dtype),
                "cm_x": jnp.zeros((n, B, cfg.d_model), dtype)}
    if kind == "mamba2":
        H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
        conv_dim = cfg.d_inner + 2 * N
        cache = {"s": jnp.zeros((n, B, H, N, P), jnp.float32),
                 "conv": jnp.zeros((n, B, cfg.conv_kernel - 1, conv_dim), dtype)}
        if cfg.attn_every:
            n_inv = cfg.n_layers // cfg.attn_every
            Hh, Dh = cfg.n_heads, cfg.head_dim
            cache["ak"] = jnp.zeros((n_inv, B, Lc, Hh, Dh), dtype)
            cache["av"] = jnp.zeros((n_inv, B, Lc, Hh, Dh), dtype)
        return cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


@jax.named_scope("lm.prefill")
def prefill(p: Params, cfg: ModelConfig, batch: dict, cache: Params,
            routing: bool = False) -> tuple:
    """Process the full prompt; fill the cache; return last-position logits.

    For SWA archs the cache keeps the trailing `window` positions. SSM /
    hybrid archs run their chunked forward and keep only final states.
    MoE layers drop no assignment (``layers.moe_serve``). With
    ``routing``, also return the assignments each held expert received in
    each MoE layer over the batch's tokens, (n_moe_layers, E_held) int32,
    or None for a model without experts.
    """
    logits, cache, routed = _prefill(p, cfg, batch, cache)
    return (logits, cache, routed) if routing else (logits, cache)


def _prefill(p: Params, cfg: ModelConfig, batch: dict, cache: Params):
    x, prefix_len = _assemble_input(p, cfg, batch)
    B, Lx, _ = x.shape
    positions = jnp.arange(Lx)
    kind = _main_kind(cfg)
    Lc = cache_len(cfg, Lx)

    if kind in ("dense", "moe", "decoder"):
        memory = (_encode(p, cfg, batch["frames"])
                  if cfg.is_encoder_decoder else None)

        n_dense0 = len(p.get("dense0", []))

        def layer(x, bp):
            x = L.dp_constrain(x, cfg.act_dp)
            h = _norm(cfg, bp["ln1"], x)
            if cfg.attn_kind == "mla":
                latent, krope = L.mla_latent(bp["attn"], cfg, h, positions)
                a = L.mla_attend(bp["attn"], cfg, h, positions)
                kv = {"latent": _ring_place(latent[:, -Lc:], Lx, Lc),
                      "krope": _ring_place(krope[:, -Lc:], Lx, Lc)}
            else:
                q, k, v = L.gqa_qkv(bp["attn"], cfg, h, positions)
                a = L.flash_attention(q, k, v, causal=True,
                                      window=cfg.window, prefix_len=prefix_len)
                a = a.reshape(B, Lx, -1) @ bp["attn"]["wo"]
                if cfg.kv_dtype == "int8":
                    kq, ks = kv_quant(k[:, -Lc:])
                    vq, vs = kv_quant(v[:, -Lc:])
                    kv = {"k": _ring_place(kq, Lx, Lc),
                          "v": _ring_place(vq, Lx, Lc),
                          "k_scale": _ring_place(ks, Lx, Lc),
                          "v_scale": _ring_place(vs, Lx, Lc)}
                else:
                    kv = {"k": _ring_place(k[:, -Lc:], Lx, Lc),
                          "v": _ring_place(v[:, -Lc:], Lx, Lc)}
            x = x + a
            if memory is not None:
                h = _norm(cfg, bp["ln_x"], x)
                q, _, _ = L.gqa_qkv(bp["xattn"], cfg, h, positions, rope=False)
                mem_pos = jnp.arange(memory.shape[1])
                _, mk, mv = L.gqa_qkv(bp["xattn"], cfg, memory, mem_pos,
                                      rope=False)
                a = L.flash_attention(q, mk, mv, causal=False)
                x = x + a.reshape(B, Lx, -1) @ bp["xattn"]["wo"]
                kv["xk"], kv["xv"] = mk, mv
            h = _norm(cfg, bp["ln2"], x)
            routed = None
            if cfg.is_moe and "router" in bp["mlp"]:
                m, routed = L.moe_serve(bp["mlp"], cfg, h)
                routed = routed.sum(axis=0)     # over the batch's tokens
            else:
                m = L.mlp(bp["mlp"], h, cfg.act)
            return x + m, (kv, routed)

        new_cache = dict(cache)
        x_cur = x
        for i, blk in enumerate(p.get("dense0", [])):
            x_cur, (kv, _) = layer(x_cur, blk)
            for key in kv:
                new_cache[key] = _store(new_cache[key], kv[key][None], i)

        def body(x, bp):
            return layer(x, bp)

        fn = jax.checkpoint(body) if cfg.remat else body
        x_cur, (kvs, routed) = lax.scan(fn, x_cur, p["blocks"])
        for key in kvs:
            new_cache[key] = _store(new_cache[key], kvs[key], n_dense0)
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur[:, -1:]))
        return logits[:, 0], new_cache, routed

    if kind == "rwkv6":
        def body(x, inp):
            bp = inp
            x = L.dp_constrain(x, cfg.act_dp)
            x, st = S.rwkv6_block(bp, cfg, x, None, cfg.chunk_size)
            return x, st

        fn = jax.checkpoint(body) if cfg.remat else body
        x_cur, states = lax.scan(fn, x, p["blocks"])
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur[:, -1:]))
        return logits[:, 0], states, None

    if kind == "mamba2":
        x0 = x
        every, n = cfg.attn_every, cfg.n_layers
        n_inv = n // every if every else 0
        is_attn = jnp.array([every and (i % every == every - 1)
                             and (i // every < n_inv) for i in range(n)])
        inv_idx = jnp.array([min(i // every, max(n_inv - 1, 0))
                             for i in range(n)], jnp.int32)
        ak = cache.get("ak")
        av = cache.get("av")

        def body(carry, inp):
            x, ak, av = carry
            bp, attn_flag, inv = inp
            x = L.dp_constrain(x, cfg.act_dp)
            x, st = S.mamba2_block(bp, cfg, x, None, cfg.chunk_size)

            def with_attn(args):
                x, ak, av = args
                y, (k, v) = _zamba_shared_fwd(p["shared_attn"], cfg, x, x0,
                                              inv, positions)
                ak = lax.dynamic_update_slice(
                    ak, k[:, -Lc:][None].astype(ak.dtype), (inv, 0, 0, 0, 0))
                av = lax.dynamic_update_slice(
                    av, v[:, -Lc:][None].astype(av.dtype), (inv, 0, 0, 0, 0))
                return (y, ak, av)

            if every:
                x, ak, av = lax.cond(attn_flag, with_attn,
                                     lambda a: a, (x, ak, av))
            return (x, ak, av), st

        fn = jax.checkpoint(body) if cfg.remat else body
        (x_cur, ak, av), states = lax.scan(
            fn, (x, ak, av), (p["blocks"], is_attn, inv_idx))
        new_cache = {"s": states["s"], "conv": states["conv"]}
        if every:
            new_cache["ak"], new_cache["av"] = ak, av
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur[:, -1:]))
        return logits[:, 0], new_cache, None

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


@jax.named_scope("lm.decode_step")
def decode_step(p: Params, cfg: ModelConfig, tokens: jax.Array, cache: Params,
                pos: jax.Array, kv_len: Optional[jax.Array] = None,
                routing: bool = False) -> tuple:
    """One decode step. tokens: (B,1); pos: int32 write index and rotary
    position, a scalar shared by the rows or (B,), one per row; kv_len:
    (B,) valid lengths (defaults to pos+1). Returns (logits (B,V), cache),
    and with ``routing`` the routing counter per token,
    (B, n_moe_layers, E_held) int32: the assignments each held expert
    received from each row's token (None for a model without experts)."""
    logits, cache, routed = _decode_step(p, cfg, tokens, cache, pos, kv_len)
    return (logits, cache, routed) if routing else (logits, cache)


def _decode_step(p: Params, cfg: ModelConfig, tokens: jax.Array,
                 cache: Params, pos: jax.Array, kv_len: Optional[jax.Array]):
    B = tokens.shape[0]
    x = embed_tokens(p, cfg, tokens)
    if kv_len is None:
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
    kind = _main_kind(cfg)
    positions = pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None]
    Lc = cache[next(iter(cache))].shape[2] if kind in ("dense", "moe", "decoder") else 0
    write_pos = jnp.mod(pos, Lc) if cfg.window is not None else pos

    if kind in ("dense", "moe", "decoder"):
        eff_len = kv_len if cfg.window is None else jnp.minimum(kv_len, Lc)

        def body(x, inp):
            bp, c = inp
            h = _norm(cfg, bp["ln1"], x)
            if cfg.attn_kind == "mla":
                latent, krope = L.mla_latent(bp["attn"], cfg, h, positions)
                c["latent"] = _write_row(c["latent"], latent, write_pos)
                c["krope"] = _write_row(c["krope"], krope, write_pos)
                a = L.mla_decode(bp["attn"], cfg, h, c["latent"], c["krope"],
                                 eff_len, positions)
            else:
                q, k, v = L.gqa_qkv(bp["attn"], cfg, h, positions)
                if cfg.kv_dtype == "int8":
                    kq, ks = kv_quant(k)
                    vq, vs = kv_quant(v)
                    c["k"] = _write_row(c["k"], kq, write_pos)
                    c["v"] = _write_row(c["v"], vq, write_pos)
                    c["k_scale"] = _write_row(c["k_scale"], ks, write_pos)
                    c["v_scale"] = _write_row(c["v_scale"], vs, write_pos)
                    k_full = kv_dequant(c["k"], c["k_scale"], h.dtype)
                    v_full = kv_dequant(c["v"], c["v_scale"], h.dtype)
                else:
                    c["k"] = _write_row(c["k"], k, write_pos)
                    c["v"] = _write_row(c["v"], v, write_pos)
                    k_full, v_full = c["k"], c["v"]
                a = L.decode_attention(
                    q, k_full, v_full, kv_len=eff_len,
                    window=None)  # ring buffer already bounds the window
                a = a.reshape(B, 1, -1) @ bp["attn"]["wo"]
            x = x + a
            if cfg.is_encoder_decoder:
                h = _norm(cfg, bp["ln_x"], x)
                q, _, _ = L.gqa_qkv(bp["xattn"], cfg, h, positions,
                                    rope=False)
                enc_len = jnp.full((B,), c["xk"].shape[1], jnp.int32)
                a = L.decode_attention(q, c["xk"], c["xv"], kv_len=enc_len)
                x = x + a.reshape(B, 1, -1) @ bp["xattn"]["wo"]
            h = _norm(cfg, bp["ln2"], x)
            routed = None
            if cfg.is_moe and "router" in bp["mlp"]:
                m, routed = L.moe_serve(bp["mlp"], cfg, h)
            else:
                m = L.mlp(bp["mlp"], h, cfg.act)
            return x + m, (c, routed)

        new_cache = dict(cache)
        x_cur = x
        n_dense0 = len(p.get("dense0", []))
        for i, blk in enumerate(p.get("dense0", [])):
            ci = jax.tree.map(lambda a: a[i], cache)
            x_cur, (ci, _) = body(x_cur, (blk, ci))
            for key in ci:
                new_cache[key] = new_cache[key].at[i].set(ci[key])
        if n_dense0:
            rest = jax.tree.map(lambda a: a[n_dense0:], cache)
        else:
            rest = cache
        x_cur, (rest_new, routed) = lax.scan(body, x_cur,
                                             (p["blocks"], rest))
        for key in rest_new:
            if n_dense0:
                new_cache[key] = lax.dynamic_update_slice(
                    new_cache[key], rest_new[key],
                    (n_dense0,) + (0,) * (new_cache[key].ndim - 1))
            else:
                new_cache[key] = rest_new[key]
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur))
        if routed is not None:                  # (n_moe, B, E) -> per token
            routed = jnp.swapaxes(routed, 0, 1)
        return logits[:, 0], new_cache, routed

    if kind == "rwkv6":
        def body(x, inp):
            bp, st = inp
            x, st = S.rwkv6_block(bp, cfg, x, st, cfg.chunk_size)
            return x, st

        x_cur, states = lax.scan(body, x, (p["blocks"], cache))
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur))
        return logits[:, 0], states, None

    if kind == "mamba2":
        every, n = cfg.attn_every, cfg.n_layers
        n_inv = n // every if every else 0
        is_attn = jnp.array([every and (i % every == every - 1)
                             and (i // every < n_inv) for i in range(n)])
        inv_idx = jnp.array([min(i // every, max(n_inv - 1, 0))
                             for i in range(n)], jnp.int32)
        x0 = x
        ak, av = cache.get("ak"), cache.get("av")
        Lc_a = ak.shape[2] if ak is not None else 0
        a_write = jnp.mod(pos, Lc_a) if (cfg.window is not None and ak is not None) else pos

        def body(carry, inp):
            x, ak, av = carry
            bp, st, attn_flag, inv = inp
            x, st = S.mamba2_decode_step(bp, cfg, x, st)

            def with_attn(args):
                x, ak, av = args
                ak_i, av_i = ak[inv], av[inv]
                y, (k_new, v_new) = _zamba_shared_fwd(
                    p["shared_attn"], cfg, x, x0, inv, positions,
                    kv_cache=(ak_i, av_i), pos=a_write, kv_len=kv_len)
                ak = lax.dynamic_update_index_in_dim(ak, k_new, inv, 0)
                av = lax.dynamic_update_index_in_dim(av, v_new, inv, 0)
                return (y, ak, av)

            if every:
                x, ak, av = lax.cond(attn_flag, with_attn, lambda a: a,
                                     (x, ak, av))
            return (x, ak, av), st

        mamba_cache = {"s": cache["s"], "conv": cache["conv"]}
        (x_cur, ak, av), states = lax.scan(
            body, (x, ak, av), (p["blocks"], mamba_cache, is_attn, inv_idx))
        new_cache = {"s": states["s"], "conv": states["conv"]}
        if every:
            new_cache["ak"], new_cache["av"] = ak, av
        logits = unembed(p, cfg, _norm(cfg, p["final_norm"], x_cur))
        return logits[:, 0], new_cache, None

    raise ValueError(kind)
