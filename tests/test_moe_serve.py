"""The expert layer on the served path: DeepSeek-V2's group-limited gating,
dropless dispatch, a chip's share of the experts, and the routing counter
the engine returns with each step. Small widths, published routing counts
(160 experts in 8 groups, 3 kept, top-6, gates scaled by 16)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.configs.base import get_config
from repro.models import layers as L, lm
from repro.serving.engine import ModelEngine


def dsv2(**kw):
    """deepseek-v2-236b with its routing as published and small widths."""
    return get_config("deepseek-v2-236b").replace(
        d_model=64, d_ff_expert=16, dtype="float32", **kw)


def np_gating(logits, k, n_group, topk_group, scale, renorm):
    """The group_limited_greedy rule, one token at a time, in float64."""
    gates, idx = [], []
    for row in np.asarray(logits, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        groups = p.reshape(n_group, -1)
        kept = np.argsort(-groups.max(axis=1), kind="stable")[:topk_group]
        cand = np.zeros_like(groups)
        cand[kept] = groups[kept]
        top = np.argsort(-cand.reshape(-1), kind="stable")[:k]
        w = p[top]
        if renorm:
            w = w / w.sum()
        gates.append(w * scale)
        idx.append(top)
    return np.array(gates), np.array(idx)


@pytest.mark.parametrize("n_group,topk_group,scale,renorm", [
    (8, 3, 16.0, False),        # DeepSeek-V2 as published
    (1, 1, 1.0, True),          # the defaults: plain top-k, renormalised
])
def test_gating_follows_the_group_limited_rule(rng, n_group, topk_group,
                                               scale, renorm):
    logits = rng.normal(size=(64, 160)).astype(np.float32) * 2.0
    gates, idx, _ = L.moe_gating(jnp.asarray(logits), 6, renorm, n_group,
                                 topk_group, scale)
    want_g, want_i = np_gating(logits, 6, n_group, topk_group, scale, renorm)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    # f32 softmax against float64: a few ulps of the largest gate
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-5,
                               atol=1e-6 * scale)
    if n_group > 1:             # every pick lies in one of 3 groups of 20
        assert all(len(set(i // 20)) <= topk_group for i in np.asarray(idx))


def test_default_gating_is_unchanged(rng):
    """With one group, renormalisation and scale 1 the gates are the
    renormalised top-k softmax scores, bit for bit."""
    logits = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    gates, idx, _ = L.moe_gating(logits, 2)
    probs = jax.nn.softmax(logits, axis=-1)
    g, i = jax.lax.top_k(probs, 2)
    g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(g))


def _crowded(cfg, rng, T=64):
    """A layer whose router sends every token to expert 3 (and 5 others):
    64 tokens x 6 picks give expert 3 all 64 tokens, where the training
    path's capacity holds max(8, ceil(1.25 * 64 * 6 / 160 / 8) * 8) = 8."""
    p = L.moe_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    p["router"] = p["router"].at[:, 3].add(5.0)
    x = rng.normal(size=(1, T, cfg.d_model)).astype(np.float32)
    x[..., :] += 1.0            # h @ router's column 3 is large for all
    return p, jnp.asarray(x)


def test_served_layer_drops_nothing(rng):
    cfg = dsv2()
    p, x = _crowded(cfg, rng)
    _, idx, _ = L.moe_gating(x[0] @ p["router"], 6, False, 8, 3, 16.0)
    assert (np.asarray(idx) == 3).sum() == 64      # 8x the old capacity
    out, routed = L.moe_serve(p, cfg, x)
    alone = jnp.concatenate([L.moe_serve(p, cfg, x[:, t:t + 1])[0]
                             for t in range(x.shape[1])], axis=1)
    # each token alone: its output cannot depend on the rest of the batch;
    # f32 at width 64, only the order of the matmuls' sums differs: a few
    # ulps of the largest output
    np.testing.assert_allclose(np.asarray(out), np.asarray(alone), rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(out).max()))
    assert int(routed[3]) == 64 and int(routed.sum()) == 64 * 6
    dropped, _ = L.moe_apply(p, cfg, x)             # training: capacity 8
    assert not np.allclose(np.asarray(dropped), np.asarray(out), atol=1e-3)


def test_the_group_shares_add_up_to_the_whole_layer(rng):
    """Eight chips, one routing group each: the routed parts of the eight
    shares, with the shared experts (computed on every chip) counted once,
    give the uncut layer; each share's counter is its slice of the whole
    counter."""
    cfg = dsv2()
    p = L.moe_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32))
    whole, routed = L.moe_serve(p, cfg, x)
    shared = L.mlp(p["shared"], x, cfg.act)
    total = -7 * shared
    for g in range(8):
        share = {k: (v if k in ("router", "shared") else v[20 * g:20 * g + 20])
                 for k, v in p.items()}
        out, r = L.moe_serve(share, cfg, x, expert_lo=20 * g)
        total = total + out
        np.testing.assert_array_equal(np.asarray(r),
                                      np.asarray(routed[20 * g:20 * g + 20]))
    # f32 at width 64: eight partial sums in place of one, a few ulps of
    # the largest output
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(whole).max()))
    held = {k: (v if k in ("router", "shared") else v[:20])
            for k, v in p.items()}
    out0, _ = L.moe_serve(held, cfg, x)             # default offset: group 0
    np.testing.assert_array_equal(
        np.asarray(out0),
        np.asarray(L.moe_serve(held, cfg, x, expert_lo=0)[0]))


def test_engine_returns_the_routing_counter_of_active_slots(rng):
    """Prefills add every prompt token's assignments to a running total; a
    decode step notes on its span the assignments of its active slots'
    tokens only (idle slots decode too)."""
    cfg = dsv2(n_layers=3, n_heads=4, n_kv_heads=4, q_lora_rank=32,
               kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
               d_ff=128, vocab_size=256, remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ModelEngine(params, cfg, n_slots=4, max_len=32)
    n_moe, k = cfg.n_layers - cfg.first_dense_layers, cfg.top_k
    lens = [5, 9, 3]
    toks = np.zeros(4, np.int32)
    for s, n in enumerate(lens):
        toks[s] = eng.prefill_into(s, rng.integers(0, 256, n))
    assert eng.prefill_routed.shape == (n_moe, 160)
    np.testing.assert_array_equal(eng.prefill_routed.sum(axis=1),
                                  [sum(lens) * k] * n_moe)
    trace.clear()
    with trace.recording():
        eng.decode_active(toks)
    (span,) = [s for s in trace.spans() if s.name == "engine.decode"]
    trace.clear()
    routed = span.counts["routed"]
    assert routed.shape == (n_moe, 160)
    np.testing.assert_array_equal(routed.sum(axis=1), [3 * k] * n_moe)
