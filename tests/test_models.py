"""Per-arch smoke tests (reduced configs) + prefill/decode consistency.

Every assigned architecture: one forward / train-grad / prefill / decode
pass on CPU asserting shapes and no NaNs; plus the strong consistency
check that prefill+decode reproduces the full-forward logits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models import embedder, lm


def make_batch(cfg, rng, B=2, L=32, labels=False):
    batch = {}
    if cfg.family == "vlm":
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, L - cfg.prefix_len)),
            jnp.int32)
        batch["patch_embed"] = jnp.asarray(
            rng.normal(size=(B, cfg.prefix_len, cfg.d_model)), jnp.float32)
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32)
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.asarray(
                rng.normal(size=(B, cfg.enc_len, cfg.d_model)), jnp.float32)
    if labels:
        batch["labels"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, batch["tokens"].shape), jnp.int32)
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            # float32 so prefill/decode-vs-forward agreement is exact-ish
            # (bf16 logits differ by ~eps=0.008 between compute orders)
            cfg = get_config(name).reduced().replace(dtype="float32")
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            cache[name] = (cfg, params)
        return cache[name]

    return get


def test_bf16_forward_no_nan(rng):
    cfg = get_config("qwen3-14b").reduced()      # bf16 default
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    logits, _ = lm.forward(params, cfg, make_batch(cfg, rng, 2, 16))
    assert logits.dtype == jnp.bfloat16
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch, built, rng):
    cfg, params = built(arch)
    B, L = 2, 32
    batch = make_batch(cfg, rng, B, L)
    logits, aux = lm.forward(params, cfg, batch)
    exp_L = L - (cfg.prefix_len if cfg.family == "vlm" else 0)
    assert logits.shape == (B, exp_L, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert bool(jnp.all(jnp.isfinite(aux)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, built, rng):
    """decode_step after prefill must reproduce full-forward logits."""
    cfg, params = built(arch)
    B, L = 2, 24
    batch = make_batch(cfg, rng, B, L)
    full_logits, _ = lm.forward(params, cfg, batch)

    toks = batch["tokens"]
    Lt = toks.shape[1]
    pre = {**batch, "tokens": toks[:, :Lt - 2]}
    cache = lm.init_cache(cfg, B, L + 4)
    lg, cache = lm.prefill(params, cfg, pre, cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full_logits[:, -3]),
                               atol=2e-4, rtol=2e-3)
    pos0 = L - 2 if cfg.family == "vlm" else Lt - 2
    lg1, cache = lm.decode_step(params, cfg, toks[:, Lt - 2: Lt - 1],
                                cache, jnp.asarray(pos0, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg1),
                               np.asarray(full_logits[:, -2]),
                               atol=2e-4, rtol=2e-3)
    lg2, cache = lm.decode_step(params, cfg, toks[:, Lt - 1:],
                                cache, jnp.asarray(pos0 + 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg2),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("arch", ["qwen3-14b", "mixtral-8x7b", "rwkv6-7b",
                                  "zamba2-7b", "whisper-base"])
def test_train_grad_finite(arch, built, rng):
    from repro.launch.steps import chunked_ce_loss
    cfg, params = built(arch)
    batch = make_batch(cfg, rng, 2, 16, labels=True)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: chunked_ce_loss(p, cfg, batch, chunk=8),
        has_aux=True)(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


def test_loss_decreases_tiny_train(rng):
    """Few steps of the real train_step on a reduced model: loss drops."""
    from repro.launch.steps import make_train_step
    from repro.training.optimizer import AdamWConfig
    cfg = get_config("qwen3-14b").reduced().replace(remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    from repro.training import optimizer as opt
    state = opt.init_state(params)
    step = jax.jit(make_train_step(
        cfg, optc=AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=30),
        ce_chunk=16))
    # fixed batch: loss must fall when memorizing
    batch = make_batch(cfg, rng, 4, 16, labels=True)
    losses = []
    for _ in range(8):
        params, state, metrics = step(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_sliding_window_ring_buffer(rng):
    """SWA arch (mixtral): decode past the window must match a full
    forward restricted to the window."""
    cfg = get_config("mixtral-8x7b").reduced().replace(dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, L = 1, 48         # window = 32 (reduced) < L
    batch = make_batch(cfg, rng, B, L)
    full_logits, _ = lm.forward(params, cfg, batch)
    pre = {"tokens": batch["tokens"][:, :L - 1]}
    cache = lm.init_cache(cfg, B, L)
    lg, cache = lm.prefill(params, cfg, pre, cache)
    lg2, _ = lm.decode_step(params, cfg, batch["tokens"][:, L - 1:], cache,
                            jnp.asarray(L - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg2),
                               np.asarray(full_logits[:, -1]),
                               atol=3e-4, rtol=3e-3)


# (arch, overrides, per-row positions): distinct positions of B = 4 rows
# in a cache of 16; the ring's window of 8 wraps rows 1 and 2
PER_ROW_POS = {
    "mla": ("minicpm3-4b", dict(dtype="float32"), [3, 0, 9, 15]),
    "gqa-bf16": ("qwen3-14b", {}, [3, 0, 9, 15]),
    "gqa-int8": ("qwen3-14b", dict(dtype="float32", kv_dtype="int8"),
                 [3, 0, 9, 15]),
    "ring": ("mixtral-8x7b", dict(dtype="float32", window=8), [3, 11, 20, 7]),
    "moe": ("deepseek-v2-236b", dict(dtype="float32"), [3, 0, 9, 15]),
}


def _random_cache(cfg, B, L, rng):
    """A cache full of values, so a row written where it must not be
    shows; int8 codes dequantize to about unit scale, as k and v do."""
    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        if a.dtype == jnp.float16:                  # int8 scales
            return jnp.asarray(rng.uniform(0.5, 2, a.shape) / 127, a.dtype)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)
    return jax.tree.map(fill, lm.init_cache(cfg, B, L))


@pytest.mark.parametrize("case", list(PER_ROW_POS))
def test_decode_step_at_per_row_positions_matches_one_row_steps(case, rng):
    """decode_step with a (B,) pos, a position per row, is B one-row steps
    at a scalar pos: the logits agree, each row's new cache entry lands
    at its own position, every other entry is untouched, and an MoE
    model's routing counter is each row's own."""
    arch, over, pos = PER_ROW_POS[case]
    cfg = get_config(arch).reduced().replace(remat=False, **over)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, Lc = len(pos), lm.cache_len(cfg, 16)
    cache = _random_cache(cfg, B, 16, rng)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    kv_len = jnp.minimum(pos + 1, 16)
    lg, new, routed = lm.decode_step(params, cfg, toks, cache, pos,
                                     kv_len=kv_len, routing=True)
    f32 = cfg.dtype == "float32"
    for b in range(B):
        one = jax.tree.map(lambda a: a[:, b:b + 1], cache)
        lg1, new1, routed1 = lm.decode_step(
            params, cfg, toks[b:b + 1], one, pos[b], kv_len=kv_len[b:b + 1],
            routing=True)
        np.testing.assert_allclose(
            np.asarray(lg[b], np.float32), np.asarray(lg1[0], np.float32),
            rtol=1e-5 if f32 else 2e-2, atol=1e-5 if f32 else 2e-2)
        at = int(pos[b]) % Lc
        for key in new:
            got, want = np.asarray(new[key][:, b]), np.asarray(new1[key][:, 0])
            before = np.asarray(cache[key][:, b])
            rest = np.arange(Lc) != at
            np.testing.assert_array_equal(got[:, rest], before[:, rest])
            np.testing.assert_array_equal(want[:, rest], before[:, rest])
            np.testing.assert_allclose(
                got[:, at].astype(np.float32), want[:, at].astype(np.float32),
                rtol=1e-5 if f32 else 1e-2, atol=1e-5 if f32 else 1e-2)
        if cfg.is_moe:
            np.testing.assert_array_equal(np.asarray(routed[b]),
                                          np.asarray(routed1[0]))
    if cfg.is_moe:
        n_moe = cfg.n_layers - cfg.first_dense_layers
        assert routed.shape == (B, n_moe, cfg.n_experts)
        np.testing.assert_array_equal(np.asarray(routed).sum(axis=-1),
                                      cfg.top_k)
    else:
        assert routed is None


@pytest.mark.parametrize("case", list(PER_ROW_POS))
def test_decode_step_at_a_scalar_position_writes_by_slice_update(case):
    """A scalar pos, shared by the rows, writes each cache leaf's new row
    with a slice update, as before (B,) positions existed: its program
    holds no select over a layer's cache leaf. A (B,) pos writes by such
    a select, which shows that the search finds one."""
    arch, over, _ = PER_ROW_POS[case]
    cfg = get_config(arch).reduced().replace(remat=False, **over)
    B = 2
    params = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, B, 37))
    leaf_shapes = {"x".join(map(str, a.shape[1:]))
                   for a in jax.tree.leaves(cache)}
    step = jax.jit(lambda p, t, c, q: lm.decode_step(p, cfg, t, c, q))

    def selects_over_a_leaf(pos_shape):
        text = step.lower(params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                          cache, jax.ShapeDtypeStruct(pos_shape, jnp.int32)
                          ).as_text()
        return [ln for ln in text.splitlines() if "stablehlo.select" in ln
                and any(f"tensor<{s}x" in ln for s in leaf_shapes)], text

    sel, text = selects_over_a_leaf(())
    assert not sel, sel
    assert "dynamic_update_slice" in text
    assert selects_over_a_leaf((B,))[0]


def test_embedder_unit_norm(rng):
    cfg = get_config("siso-embedder").reduced()
    params = embedder.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 16)), jnp.int32)
    emb = embedder.encode(params, cfg, toks)
    assert emb.shape == (3, cfg.d_model)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(emb, axis=-1)),
                               1.0, atol=1e-4)


def test_param_counts_match_public_sizes():
    """Total parameter counts should be in the right ballpark of the
    models' public sizes (loose: our analytic count, their naming)."""
    expect = {"qwen3-14b": (13e9, 16e9), "command-r-35b": (30e9, 40e9),
              "qwen2.5-14b": (12e9, 16e9), "mixtral-8x7b": (42e9, 50e9),
              "deepseek-v2-236b": (200e9, 250e9), "rwkv6-7b": (6e9, 9e9),
              "zamba2-7b": (6e9, 9e9), "paligemma-3b": (2e9, 3.5e9),
              "whisper-base": (5e7, 1.2e8), "minicpm3-4b": (3e9, 5e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).total_params
        assert lo <= n <= hi, (arch, n)


def test_make_embed_fn_matches_encode(rng):
    """The gateway's bucketed embed_fn equals a plain encode of each
    prompt, cut or zero-padded to the bucket, whatever batch it rides in."""
    cfg = get_config("siso-embedder").reduced()
    p = embedder.init_params(jax.random.PRNGKey(0), cfg)
    fn = embedder.make_embed_fn(p, cfg, seq_len=8, batch=4)
    toks = [rng.integers(1, cfg.vocab_size, size=n) for n in (3, 8, 12, 5, 1)]
    out = fn(toks)
    assert out.shape == (5, cfg.d_model) and out.dtype == np.float32
    encode = jax.jit(lambda p, t: embedder.encode(p, cfg, t))
    for t, o in zip(toks, out):
        padded = np.zeros((1, 8), np.int32)
        padded[0, :min(len(t), 8)] = t[:8]
        ref = np.asarray(encode(p, jnp.asarray(padded))[0])
        np.testing.assert_allclose(o, ref, atol=1e-6)
