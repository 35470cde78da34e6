"""The query encoder, written plainly: an ALBERT-small-style sentence
embedder (one transformer layer's weights applied ``num_hidden_layers``
times, factorized token embedding, post-LN, tanh-GELU MLP, rotary
positions, masked mean pooling, L2 normalization). The benchmark's own
weights, its reference encode and the lower-precision control. Imports
nothing of the program.

The serving encoder runs at one (batch, seq_len) bucket: prompts are cut
or zero-padded to seq_len, and attention runs over the whole bucket with
no padding mask (token 0 is attended like any other); only the pooling is
masked. The reference computes the same function.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from models.mla_dense import _key, _fp8

F32 = jnp.float32


def param_shapes(e: dict) -> dict:
    d, ff, f = e["hidden_size"], e["intermediate_size"], e["embedding_size"]
    return {
        "tok_embed": (e["vocab_size"], f),
        "embed_proj": (f, d),
        "embed_ln": {"scale": (d,), "bias": (d,)},
        "attn": {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
                 "bq": (d,), "bk": (d,), "bv": (d,)},
        "ln1": {"scale": (d,), "bias": (d,)},
        "mlp": {"w_up": (d, ff), "w_down": (ff, d)},
        "ln2": {"scale": (d,), "bias": (d,)},
    }


def init_weights(e: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed`` in one jitted call: matrices
    N(0, 1/fan_in), the token table N(0, 0.02^2), biases 0, scales 1."""
    shapes = param_shapes(e)
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    tree = jax.tree.structure(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shp) in zip(keys, flat):
            name = jax.tree_util.keystr(path)
            if "scale" in name:
                out.append(jnp.ones(shp, dtype))
            elif "bias" in name or name.endswith("['bq']") \
                    or name.endswith("['bk']") or name.endswith("['bv']"):
                out.append(jnp.zeros(shp, dtype))
            elif "tok_embed" in name:
                out.append((jax.random.normal(k, shp, F32) * 0.02).astype(
                    dtype))
            else:
                out.append((jax.random.normal(k, shp, F32)
                            / math.sqrt(shp[0])).astype(dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(_key(seed ^ 0x5EED))


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _rope(x, theta):
    T, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _encode(p, e: dict, tokens, wq):
    d, H = e["hidden_size"], e["num_attention_heads"]
    Dh, eps, theta = d // H, float(e["layer_norm_eps"]), float(e["rope_theta"])
    B, T = tokens.shape
    f = lambda a: a.astype(F32)                                   # noqa: E731
    x = wq(f(p["tok_embed"]))[tokens] @ wq(f(p["embed_proj"]))
    x = _ln(x, p["embed_ln"], eps)
    a_ = p["attn"]
    for _ in range(e["num_hidden_layers"]):
        q = (x @ wq(f(a_["wq"])) + f(a_["bq"])).reshape(B, T, H, Dh)
        k = (x @ wq(f(a_["wk"])) + f(a_["bk"])).reshape(B, T, H, Dh)
        v = (x @ wq(f(a_["wv"])) + f(a_["bv"])).reshape(B, T, H, Dh)
        q, k = _rope(q, theta), _rope(k, theta)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(Dh)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = _ln(x + o.reshape(B, T, d) @ wq(f(a_["wo"])), p["ln1"], eps)
        m = jax.nn.gelu(x @ wq(f(p["mlp"]["w_up"])), approximate=True) \
            @ wq(f(p["mlp"]["w_down"]))
        x = _ln(x + m, p["ln2"], eps)
    w = (tokens > 0).astype(F32)[..., None]
    pooled = jnp.sum(x * w, 1) / jnp.maximum(jnp.sum(w, 1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1,
                                                keepdims=True), 1e-9)


def _ekey(e: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in e.items()
                        if isinstance(v, (int, float))))


def _jit_encode(lowp: bool):
    def run(p, tokens, ekey):
        e = dict(ekey)
        wq = _fp8 if lowp else (lambda w: w)
        with jax.default_matmul_precision("highest"):
            return _encode(p, e, tokens, wq)
    return jax.jit(run, static_argnames=("ekey",))


_REF = _jit_encode(False)
_CONTROL = _jit_encode(True)


def bucket(token_lists: list, seq_len: int, batch: int) -> np.ndarray:
    """Token lists cut or zero-padded to the serving bucket."""
    n = -(-len(token_lists) // batch) * batch
    out = np.zeros((n, seq_len), np.int32)
    for i, t in enumerate(token_lists):
        t = np.asarray(t, np.int32)[:seq_len]
        out[i, :len(t)] = t
    return out


def encode(p, e: dict, token_lists: list, lowp: bool = False) -> np.ndarray:
    """(n, d) unit embeddings of ``token_lists`` at the serving bucket; the
    float8-weight control with ``lowp``."""
    fn = _CONTROL if lowp else _REF
    toks = bucket(token_lists, e["seq_len"], e["batch"])
    out = [np.asarray(fn(p, jnp.asarray(toks[s:s + e["batch"]]), _ekey(e)))
           for s in range(0, len(toks), e["batch"])]
    return np.concatenate(out)[:len(token_lists)].astype(np.float64)
