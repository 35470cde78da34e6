"""Dense decoder with multi-head latent attention (MiniCPM3 / DeepSeek-V2
style), written plainly: the benchmark's own weights, its reference
forward, and the lower-precision control. Imports nothing of the program.

Sizes come from the configuration file's ``model`` block (Hugging Face key
names). The equations, per layer, pre-norm with residuals:

    h  = rmsnorm(x) * ln1
    q  = rmsnorm(h @ wq_a) * q_norm @ wq_b          -> (H, nope + rope)
    c  = rmsnorm(h @ wkv_a) * kv_norm               -> latent (R)
    kr = rope(h @ wk_rope)                          -> shared by all heads
    k  = [c @ wk_b (per head nope), kr]; v = c @ wv_b
    x += softmax(q k^T / sqrt(nope + rope), causal) v @ wo
    h  = rmsnorm(x) * ln2
    x += (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = rmsnorm(x) * final_norm @ embed^T      (tied embeddings)

RoPE rotates the two halves of a head's rope part ([x1, x2] ->
[x1 cos - x2 sin, x2 cos + x1 sin]), with frequencies
theta^(-2i/d). The vocabulary is padded to a multiple of 128 and the pad
columns are masked out of the logits.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return dict(
        d=d, H=H, L=m["num_hidden_layers"], ff=m["intermediate_size"],
        V=m["vocab_size"], Vp=(m["vocab_size"] + 127) // 128 * 128,
        qr=m["q_lora_rank"], R=m["kv_lora_rank"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        vd=m["v_head_dim"], theta=float(m["rope_theta"]),
        eps=float(m["rms_norm_eps"]))


def param_shapes(m: dict) -> dict:
    """The parameter tree the serving engine takes: leaves stacked over
    layers under ``blocks``."""
    s = sizes(m)
    d, H, L = s["d"], s["H"], s["L"]
    qd = s["nope"] + s["rope"]
    return {
        "embed": (s["Vp"], d),
        "final_norm": {"scale": (d,)},
        "blocks": {
            "ln1": {"scale": (L, d)},
            "ln2": {"scale": (L, d)},
            "attn": {"wq_a": (L, d, s["qr"]), "q_norm": {"scale": (L, s["qr"])},
                     "wq_b": (L, s["qr"], H * qd),
                     "wkv_a": (L, d, s["R"]),
                     "kv_norm": {"scale": (L, s["R"])},
                     "wk_rope": (L, d, s["rope"]),
                     "wk_b": (L, s["R"], H * s["nope"]),
                     "wv_b": (L, s["R"], H * s["vd"]),
                     "wo": (L, H * s["vd"], d)},
            "mlp": {"w_gate": (L, d, s["ff"]), "w_up": (L, d, s["ff"]),
                    "w_down": (L, s["ff"], d)},
        },
    }


def _key(seed: int):
    """A key from any whole number up to 64 bits."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, made on the device in one jitted call,
    in the type they are served in. Matrices are N(0, 1/fan_in), the
    embedding N(0, 0.02^2), norm scales 1."""
    shapes = param_shapes(m)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shp, name in zip(keys, leaves, names):
            if "scale" in name:
                out.append(jnp.ones(shp, dtype))
            elif name == "['embed']":
                out.append((jax.random.normal(k, shp, F32) * 0.02).astype(
                    dtype))
            else:
                std = 1.0 / math.sqrt(shp[-2])
                out.append((jax.random.normal(k, shp, dtype)
                            * jnp.asarray(std, dtype)))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(_key(seed))


# --------------------------------------------------------------- reference


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs                   # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(params, m: dict, tokens, wq):
    """Final hidden states (B, T, d) in f32; ``wq`` maps each weight
    matrix to the values the matmuls use (identity for the reference)."""
    s = sizes(m)
    H, nope, rope, vd, eps = s["H"], s["nope"], s["rope"], s["vd"], s["eps"]
    B, T = tokens.shape
    pos = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = wq(params["embed"].astype(F32))[tokens]

    def layer(x, bp):
        f = lambda a: a.astype(F32)                               # noqa: E731
        a_ = bp["attn"]
        h = _rms(x, f(bp["ln1"]["scale"]), eps)
        q = _rms(h @ wq(f(a_["wq_a"])), f(a_["q_norm"]["scale"]), eps) \
            @ wq(f(a_["wq_b"]))
        q = q.reshape(B, T, H, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, s["theta"])
        c = _rms(h @ wq(f(a_["wkv_a"])), f(a_["kv_norm"]["scale"]), eps)
        kr = _rope((h @ wq(f(a_["wk_rope"])))[:, :, None, :], pos,
                   s["theta"])[:, :, 0]
        k_nope = (c @ wq(f(a_["wk_b"]))).reshape(B, T, H, nope)
        v = (c @ wq(f(a_["wv_b"]))).reshape(B, T, H, vd)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr)) \
            / math.sqrt(nope + rope)
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(B, T, H * vd) @ wq(f(a_["wo"]))
        h = _rms(x, f(bp["ln2"]["scale"]), eps)
        mp = bp["mlp"]
        x = x + (jax.nn.silu(h @ wq(f(mp["w_gate"]))) * (h @ wq(f(mp["w_up"]))))\
            @ wq(f(mp["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"]["scale"].astype(F32), eps)


def _logits(params, m, x, wq):
    s = sizes(m)
    lg = x @ wq(params["embed"].astype(F32)).T
    return jnp.where(jnp.arange(s["Vp"]) < s["V"], lg, -jnp.inf)


def _fp8(w):
    """Weight-only float8 (e4m3) with one scale per output column (per row
    of the embedding table): the lower-precision control."""
    axis = -2 if w.ndim >= 2 else -1
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    sc = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / sc).astype(jnp.float8_e4m3fn).astype(F32) * sc


def _embed_fp8(w):
    amax = jnp.max(jnp.abs(w), axis=-1, keepdims=True)
    sc = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / sc).astype(jnp.float8_e4m3fn).astype(F32) * sc


@partial(jax.jit, static_argnames=("mkey",))
def _ref_gaps(params, tokens, targets, mkey):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        lg = _logits(params, m, _forward(params, m, tokens, lambda w: w),
                     lambda w: w)
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return best - got


@partial(jax.jit, static_argnames=("mkey",))
def _control_argmax(params, tokens, mkey):
    m = dict(mkey)

    def wq(w):
        return _embed_fp8(w) if w.shape == params["embed"].shape else _fp8(w)
    with jax.default_matmul_precision("highest"):
        lg = _logits(params, m, _forward(params, m, tokens, wq), wq)
    return jnp.argmax(lg, -1).astype(jnp.int32)


def mkey(m: dict) -> tuple:
    """The numeric sizes of ``m`` as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float)) and not isinstance(
                            v, bool)))


def pack(seqs: list, width: int, rows: int):
    """Sequences right-padded with token 0 into (n, width) blocks of
    ``rows``: padding after a sequence's end cannot reach its positions
    under a causal mask, so one compiled shape serves every length."""
    n = -(-len(seqs) // rows) * rows
    out = np.zeros((n, width), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def served_gaps(params, m: dict, prompts: list, outs: list, width: int,
                rows: int = 4) -> list:
    """For each request, the gap by which each served token's reference
    logit lies below the reference's best at that position (>= 0; 0 when
    the served token is the reference's argmax)."""
    seqs = [np.concatenate([p, o[:-1]]) for p, o in zip(prompts, outs)]
    tgt = [np.concatenate([np.zeros(len(p) - 1, np.int32), o])
           for p, o in zip(prompts, outs)]
    toks, tg = pack(seqs, width, rows), pack(tgt, width, rows)
    k = mkey(m)
    gaps = []
    for s in range(0, len(toks), rows):
        g = np.asarray(_ref_gaps(params, jnp.asarray(toks[s:s + rows]),
                                 jnp.asarray(tg[s:s + rows]), k))
        gaps.extend(g)
    return [np.asarray(gaps[i][len(p) - 1:len(p) - 1 + len(o)], np.float64)
            for i, (p, o) in enumerate(zip(prompts, outs))]


def control_gaps(params, m: dict, prompts: list, outs: list, width: int,
                 rows: int = 4) -> list:
    """The control at the same prompts and served tokens: at each position
    the token the float8-weight forward puts first, and the reference's
    gap of that token."""
    seqs = [np.concatenate([p, o[:-1]]) for p, o in zip(prompts, outs)]
    toks = pack(seqs, width, rows)
    k = mkey(m)
    picks = []
    for s in range(0, len(toks), rows):
        picks.extend(np.asarray(_control_argmax(
            params, jnp.asarray(toks[s:s + rows]), k)))
    lp_outs = []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        lp_outs.append(np.asarray(picks[i][len(p) - 1:len(p) - 1 + len(o)],
                                  np.int32))
    tgt = [np.concatenate([np.zeros(len(p) - 1, np.int32), lo])
           for p, lo in zip(prompts, lp_outs)]
    tg = pack(tgt, width, rows)
    gaps = []
    for s in range(0, len(toks), rows):
        gaps.extend(np.asarray(_ref_gaps(params, jnp.asarray(toks[s:s + rows]),
                                         jnp.asarray(tg[s:s + rows]), k)))
    return [np.asarray(gaps[i][len(p) - 1:len(p) - 1 + len(o)], np.float64)
            for i, (p, o) in enumerate(zip(prompts, outs))]
