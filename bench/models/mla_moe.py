"""Decoder with multi-head latent attention and a group-routed expert layer
(DeepSeek-V2), written plainly: the benchmark's own weights, its reference
forward, and the lower-precision control. Imports nothing of the program;
the helpers it shares with ``mla_dense`` (RoPE, RMSNorm, float8, packing,
seeding) are the benchmark's own.

Sizes come from the configuration file's ``model`` block (Hugging Face key
names). ``n_routed_experts`` experts are held here, experts [0, n) of the
``n_routed_experts * ep_size`` the router scores: one chip's share of an
expert-parallel deployment over ``ep_size`` chips. The equations, per
layer, pre-norm with residuals:

    h  = rmsnorm(x) * ln1
    x += MLA attention of h (as in ``mla_dense``)
    h  = rmsnorm(x) * ln2
    x += mlp(h)                                   first_k_dense_replace layers
    x += sum_e w_e(h) expert_e(h) + shared(h)     the rest

where mlp, expert_e and shared are (silu(h @ w_gate) * (h @ w_up)) @ w_down
and the gates w_e follow DeepSeek-V2's group_limited_greedy rule:

    p  = softmax(h @ router)                      all E router outputs, f32
    group g scores max_{e in g} p_e               n_group contiguous groups
    keep the topk_group best groups; take the num_experts_per_tok experts
    with the largest p among the kept groups' experts
    w_e = p_e * routed_scaling_factor for a taken e, else 0
          (p_e renormalised over the taken experts first if norm_topk_prob)

The sum runs over the held experts only: what the other chips' experts add
is left out here as in the program. Logits are rmsnorm(x) * final_norm @
lm_head (untied). Departures from the published model, as the program
runs it: no YaRN rope scaling, RoPE on rotated halves of the rope part.

Routing is a discrete choice, so a bf16 program cannot follow the f32
reference where the reference's own choice is a near tie: its router
logits lie 0.011 (median) to 0.032 (nine in ten) from the reference's at
the top-6 boundary (measured at widths 256 and 1024 alike), and one expert
taken in place of another moves a token's hidden state by a scaled
expert's output. A position is judged only where the reference decides
the held experts' gates with a margin: every router logit moved by up to
ROUTE_MARGIN / 2 leaves them the same, in every expert layer. Elsewhere
its gap reads 0, as a lookup may name any row within its tolerance of the
best.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from models.mla_dense import _embed_fp8, _fp8, _key, _rms, _rope, pack

F32 = jnp.float32
ROUTE_MARGIN = 0.04     # router logits, above the bf16 program's deviation


def sizes(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    Eh = m["n_routed_experts"]
    return dict(
        d=d, H=H, L=m["num_hidden_layers"], n_dense=m["first_k_dense_replace"],
        ff=m["intermediate_size"], fe=m["moe_intermediate_size"],
        Eh=Eh, E=Eh * m["ep_size"], k=m["num_experts_per_tok"],
        groups=m["n_group"], topk_group=m["topk_group"],
        scale=float(m["routed_scaling_factor"]),
        renorm=bool(m["norm_topk_prob"]),
        f_shared=m["moe_intermediate_size"] * m["n_shared_experts"],
        V=m["vocab_size"], Vp=(m["vocab_size"] + 127) // 128 * 128,
        qr=m["q_lora_rank"], R=m["kv_lora_rank"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        vd=m["v_head_dim"], theta=float(m["rope_theta"]),
        eps=float(m["rms_norm_eps"]))


def _attn_shapes(s: dict, lead: tuple) -> dict:
    d, H = s["d"], s["H"]
    qd = s["nope"] + s["rope"]
    return {"wq_a": lead + (d, s["qr"]),
            "q_norm": {"scale": lead + (s["qr"],)},
            "wq_b": lead + (s["qr"], H * qd), "wkv_a": lead + (d, s["R"]),
            "kv_norm": {"scale": lead + (s["R"],)},
            "wk_rope": lead + (d, s["rope"]),
            "wk_b": lead + (s["R"], H * s["nope"]),
            "wv_b": lead + (s["R"], H * s["vd"]),
            "wo": lead + (H * s["vd"], d)}


def _mlp_shapes(d: int, f: int, lead: tuple) -> dict:
    return {"w_gate": lead + (d, f), "w_up": lead + (d, f),
            "w_down": lead + (f, d)}


def param_shapes(m: dict) -> dict:
    """The parameter tree the serving engine takes: the leading dense
    layers as a list under ``dense0``, the expert layers' leaves stacked
    under ``blocks``, the held experts stacked inside them."""
    s = sizes(m)
    d, n = s["d"], s["L"] - s["n_dense"]
    L1, Ln = (), (n,)
    dense = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
             "attn": _attn_shapes(s, L1), "mlp": _mlp_shapes(d, s["ff"], L1)}
    experts = _mlp_shapes(d, s["fe"], (n, s["Eh"]))
    return {
        "embed": (s["Vp"], d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, s["Vp"]),
        "dense0": [dense] * s["n_dense"],
        "blocks": {
            "ln1": {"scale": (n, d)},
            "ln2": {"scale": (n, d)},
            "attn": _attn_shapes(s, Ln),
            "mlp": {"router": (n, d, s["E"]), **experts,
                    "shared": _mlp_shapes(d, s["f_shared"], Ln)},
        },
    }


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, made on the device in one jitted call,
    in the type they are served in. Matrices are N(0, 1/fan_in), the
    embedding N(0, 0.02^2), norm scales 1."""
    shapes = param_shapes(m)
    is_shape = lambda x: isinstance(x, tuple)                     # noqa: E731
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                       is_leaf=is_shape)
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    leaves = [shp for _, shp in paths]

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


def gate_weights(logits, s: dict):
    """(..., E) router outputs -> (..., E) gate of every expert (0 where
    not taken), by the group_limited_greedy rule of the module docstring.
    A group (an expert) is kept (taken) when fewer than topk_group
    (num_experts_per_tok) others score strictly higher."""
    p = jax.nn.softmax(logits.astype(F32), -1)
    g = p.reshape(p.shape[:-1] + (s["groups"], s["E"] // s["groups"]))
    best = g.max(-1)
    beaten = (best[..., None, :] > best[..., :, None]).sum(-1)
    cand = jnp.where((beaten < s["topk_group"])[..., None], g, 0.0)
    cand = cand.reshape(p.shape)
    taken = ((cand[..., None, :] > cand[..., :, None]).sum(-1) < s["k"]) \
        & (cand > 0)
    w = jnp.where(taken, p, 0.0)
    if s["renorm"]:
        w = w / w.sum(-1, keepdims=True)
    return w * s["scale"]


def held_gates_decided(logits, s: dict, margin: float = ROUTE_MARGIN):
    """(..., E) router outputs -> (...,) bool: whether the held experts'
    gates stay the same for every change of the logits by at most
    ``margin`` / 2 each (so of any two logits' difference by at most
    ``margin``). A group is surely kept when fewer than topk_group others
    can beat its best, possibly kept when fewer than topk_group surely do;
    a held expert is surely taken when its group is surely kept and fewer
    than num_experts_per_tok experts of possibly kept groups can beat it,
    surely not taken when its group is not possibly kept or that many of
    surely kept groups surely do. Held experts are group 0's."""
    G, E, Eh = s["groups"], s["E"], s["Eh"]
    l = logits.astype(F32)
    gm = l.reshape(l.shape[:-1] + (G, E // G)).max(-1)
    gd = gm[..., None, :] - gm[..., :, None]          # [j, i]: i's best - j's
    others = ~jnp.eye(G, dtype=bool)
    may_keep = (gd > margin).sum(-1) < s["topk_group"]
    sure_keep = ((gd > -margin) & others).sum(-1) < s["topk_group"]
    of = jnp.arange(E) // (E // G)
    ed = l[..., None, :] - l[..., :Eh, None]           # [e, e']: e' - e
    not_self = ~jnp.eye(Eh, E, dtype=bool)
    can_beat = ((ed > -margin) & may_keep[..., None, of] & not_self).sum(-1)
    must_beat = ((ed > margin) & sure_keep[..., None, of]).sum(-1)
    taken = (can_beat < s["k"]) & sure_keep[..., :1]
    not_taken = (must_beat >= s["k"]) | ~may_keep[..., :1]
    return jnp.all(taken | not_taken, -1)


def _swiglu(h, mp, wq):
    f = lambda a: wq(a.astype(F32))                               # noqa: E731
    return (jax.nn.silu(h @ f(mp["w_gate"])) * (h @ f(mp["w_up"]))) \
        @ f(mp["w_down"])


def _attention(x, a_, ln1, s, pos, causal, wq):
    B, T, _ = x.shape
    H, nope, rope, vd, eps = s["H"], s["nope"], s["rope"], s["vd"], s["eps"]
    f = lambda a: a.astype(F32)                                   # noqa: E731
    h = _rms(x, f(ln1), eps)
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
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr)) / math.sqrt(nope + rope)
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return x + o.reshape(B, T, H * vd) @ wq(f(a_["wo"]))


def moe_layer(h, mp, s: dict, wq=lambda w: w):
    """The expert layer's output for normed inputs h (..., d): the held
    experts' gated sum plus the shared experts."""
    f = lambda a: wq(a.astype(F32))                               # noqa: E731
    w = gate_weights(h @ f(mp["router"]), s)[..., :s["Eh"]]
    a = jax.nn.silu(jnp.einsum("...d,edf->...ef", h, f(mp["w_gate"]))) \
        * jnp.einsum("...d,edf->...ef", h, f(mp["w_up"]))
    routed = jnp.einsum("...ef,efd->...d", a * w[..., None], f(mp["w_down"]))
    return routed + _swiglu(h, mp["shared"], wq)


def _forward(params, m: dict, tokens, lowp: bool):
    """Final hidden states (B, T, d) in f32, and (B, T) whether every
    expert layer decides the held experts' gates at each position with a
    margin (``held_gates_decided``); with ``lowp`` every weight matrix is
    rounded to float8 first (the control)."""
    s = sizes(m)
    wq = _fp8 if lowp else (lambda w: w)
    B, T = tokens.shape
    pos = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = params["embed"][tokens].astype(F32)
    if lowp:
        x = _embed_fp8(x)           # per row, as over the whole table
    for bp in params["dense0"]:
        x = _attention(x, bp["attn"], bp["ln1"]["scale"], s, pos, causal, wq)
        x = x + _swiglu(_rms(x, bp["ln2"]["scale"].astype(F32), s["eps"]),
                        bp["mlp"], wq)

    def layer(x, bp):
        x = _attention(x, bp["attn"], bp["ln1"]["scale"], s, pos, causal, wq)
        h = _rms(x, bp["ln2"]["scale"].astype(F32), s["eps"])
        decided = held_gates_decided(h @ wq(bp["mlp"]["router"].astype(F32)),
                                     s)
        return x + moe_layer(h, bp["mlp"], s, wq), decided

    x, decided = jax.lax.scan(layer, x, params["blocks"])
    return (_rms(x, params["final_norm"]["scale"].astype(F32), s["eps"]),
            jnp.all(decided, 0))


def _logits(params, m, x, lowp: bool):
    s = sizes(m)
    w = params["lm_head"].astype(F32)
    lg = x @ (_fp8(w) if lowp else w)
    return jnp.where(jnp.arange(s["Vp"]) < s["V"], lg, -jnp.inf)


@partial(jax.jit, static_argnames=("mkey",))
def _ref_gaps(params, tokens, targets, mkey):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        x, decided = _forward(params, m, tokens, False)
        lg = _logits(params, m, x, False)
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return jnp.where(decided, best - got, 0.0)


@partial(jax.jit, static_argnames=("mkey",))
def _control_argmax(params, tokens, mkey):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        lg = _logits(params, m, _forward(params, m, tokens, True)[0], True)
    return jnp.argmax(lg, -1).astype(jnp.int32)


def mkey(m: dict) -> tuple:
    """The numeric and boolean sizes of ``m`` as a hashable static
    argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool))))


def _gaps(params, m: dict, prompts: list, outs: list, width: int,
          rows: int, picks: list = None) -> list:
    """The reference's gap, at each position of the served ``outs``, of
    the token there, or of the token ``picks`` gives for it."""
    seqs = [np.concatenate([p, o[:-1]]) for p, o in zip(prompts, outs)]
    tgt = [np.concatenate([np.zeros(len(p) - 1, np.int32), o])
           for p, o in zip(prompts, picks or outs)]
    toks, tg = pack(seqs, width, rows), pack(tgt, width, rows)
    k = mkey(m)
    gaps = []
    for s in range(0, len(toks), rows):
        gaps.extend(np.asarray(_ref_gaps(params, jnp.asarray(toks[s:s + rows]),
                                         jnp.asarray(tg[s:s + rows]), k)))
    return [np.asarray(gaps[i][len(p) - 1:len(p) - 1 + len(o)], np.float64)
            for i, (p, o) in enumerate(zip(prompts, outs))]


def served_gaps(params, m: dict, prompts: list, outs: list, width: int,
                rows: int = 4) -> list:
    """For each request, the gap by which each served token's reference
    logit lies below the reference's best at that position (>= 0; 0 when
    the served token is the reference's argmax, or where the reference's
    routing is a near tie, see the module docstring). Each position depends
    only on the tokens before it: attention is causal and every token is
    routed on its own, so padding and the rows packed beside a request
    cannot reach it."""
    return _gaps(params, m, prompts, outs, width, rows)


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
    lp_outs = [np.asarray(picks[i][len(p) - 1:len(p) - 1 + len(o)], np.int32)
               for i, (p, o) in enumerate(zip(prompts, outs))]
    return _gaps(params, m, prompts, outs, width, rows, lp_outs)
