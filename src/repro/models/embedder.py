"""ALBERT-small-style sentence embedder (paraphrase-albert-small-v2 analog).

Factorized embedding (vocab -> 128 -> d), N transformer layers with
CROSS-LAYER WEIGHT SHARING (one parameter set applied n_layers times),
post-LN, GELU FFN, learned-free RoPE positions, masked mean pooling and
L2 normalization — the embedding model SISO uses for queries (Table 1).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.configs.base import ModelConfig
from repro.configs.siso_embedder import EMBED_FACTOR_DIM
from repro.models import layers as L

Params = dict[str, Any]


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = L.split(key, 8)
    d = cfg.d_model
    return {
        "tok_embed": (jax.random.normal(
            ks[0], (cfg.vocab_size, EMBED_FACTOR_DIM), jnp.float32) * 0.02
        ).astype(dtype),
        "embed_proj": L.dense_init(ks[1], EMBED_FACTOR_DIM, d, dtype),
        "embed_ln": L.layernorm_init(d, dtype),
        # ONE shared layer (ALBERT)
        "attn": L.gqa_init(ks[2], cfg, dtype),
        "ln1": L.layernorm_init(d, dtype),
        "mlp": L.mlp_init(ks[3], d, cfg.d_ff, dtype, gated=False),
        "ln2": L.layernorm_init(d, dtype),
    }


@jax.named_scope("embed.encode")
def encode(p: Params, cfg: ModelConfig, tokens: jax.Array,
           mask: jax.Array | None = None) -> jax.Array:
    """tokens: (B, L) int32; mask: (B, L) bool (True = real token).
    Returns L2-normalized sentence embeddings (B, d) float32."""
    B, Lseq = tokens.shape
    if mask is None:
        mask = tokens > 0
    x = p["tok_embed"][tokens] @ p["embed_proj"]
    x = L.layernorm(p["embed_ln"], x)
    positions = jnp.arange(Lseq)
    for _ in range(cfg.n_layers):  # shared weights: plain python loop
        a = L.gqa_attend(p["attn"], cfg, x, positions, causal=False,
                         block_q=128, block_kv=128)
        x = L.layernorm(p["ln1"], x + a)
        m = L.mlp(p["mlp"], x, cfg.act)
        x = L.layernorm(p["ln2"], x + m)
    # masked mean pooling
    w = mask.astype(jnp.float32)[..., None]
    pooled = jnp.sum(x.astype(jnp.float32) * w, axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def make_embed_fn(p: Params, cfg: ModelConfig, seq_len: int, batch: int
                  ) -> Callable[[Sequence[np.ndarray]], np.ndarray]:
    """The gateway's ``embed_fn`` over this encoder: a list of token arrays
    -> (n, d) unit f32 vectors. ``encode`` runs jitted at one fixed
    (batch, seq_len) bucket, so serving compiles it once: sequences are cut
    or zero-padded to ``seq_len`` (0 is the pad id) and the list is
    encoded ``batch`` rows at a time, the last chunk zero-padded."""
    enc = jax.jit(partial(encode, cfg=cfg))

    def embed(token_lists: Sequence[np.ndarray]) -> np.ndarray:
        n = len(token_lists)
        out = np.zeros((n, cfg.d_model), np.float32)
        with trace.span("embed", n=n):
            for s in range(0, n, batch):
                chunk = token_lists[s:s + batch]
                toks = np.zeros((batch, seq_len), np.int32)
                for i, t in enumerate(chunk):
                    t = np.asarray(t, np.int32)[:seq_len]
                    toks[i, :len(t)] = t
                e = enc(p, tokens=jnp.asarray(toks))
                with trace.span("embed.wait"):
                    out[s:s + len(chunk)] = np.asarray(e)[:len(chunk)]
        return out

    return embed
