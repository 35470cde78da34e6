"""Pallas TPU kernel: tiled brute-force cosine top-k (the cache lookup).

TPU-native adaptation of SISO's HNSW (DESIGN.md §4): instead of pointer
chasing, the query block stays resident in VMEM while centroid tiles stream
HBM -> VMEM and hit the MXU as (B, D) x (D, Ct) matmuls; a running top-k per
query lives in the (revisited) output block across sequential grid steps.

Semantic-locality layout: the caller orders centroids by descending
cluster_size, so the first tiles carry most of the hit mass — with
``early_exit`` the kernel skips a tile's compute once *every* query's best
similarity has already cleared theta_R (the same is-a-match-good-enough
semantics as the paper's HNSW upper-level early termination; exact top-k is
recovered with early_exit=False).

All intra-kernel reductions are min/max/select only (no sort/top_k inside
the kernel) so the body lowers on Mosaic as well as in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = float("-inf")
# f32 tile dots at full f32 precision on the MXU (several bf16 passes). At
# the default single bf16 pass, sims at dim 768 on a TPU v5e were off by up
# to 2.7e-4 against float64 (2.8e-8 at HIGHEST): enough to flip theta_R
# decisions against the dense path and an exact reference.
PRECISION = jax.lax.Precision.HIGHEST


def _merge_topk(run_vals, run_idx, sims, idx, k: int):
    """Merge a (B, Ct) score tile into the running (B, k) top-k.

    Iterative max-extraction: k rounds of (max, first-argmax, mask). Ties
    break toward the earliest candidate column, which (run-before-tile,
    ascending global idx) reproduces lax.top_k's smallest-index tie rule.
    """
    vals = jnp.concatenate([run_vals, sims], axis=1)        # (B, k+Ct)
    idxs = jnp.concatenate([run_idx, idx], axis=1)
    B, M = vals.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (B, M), 1)
    out_v, out_i = [], []
    for _ in range(k):
        m = jnp.max(vals, axis=1, keepdims=True)            # (B, 1)
        pos = jnp.min(jnp.where(vals == m, col, M), axis=1, keepdims=True)
        sel = col == pos                                     # one-hot winner
        out_v.append(m[:, 0])
        out_i.append(jnp.sum(jnp.where(sel, idxs, 0), axis=1))
        vals = jnp.where(sel, NEG, vals)
    return jnp.stack(out_v, axis=1), jnp.stack(out_i, axis=1).astype(jnp.int32)


def cosine_topk_kernel(theta_ref, q_ref, c_ref, valid_ref, vals_ref, idx_ref,
                       hit_ref, tiles_ref, *, k: int, block_n: int,
                       early_exit: bool):
    """Grid: (num_centroid_tiles,). q block (B, D) constant; c tile
    (block_n, D) streams; vals/idx/hit/tiles (B, k)/(B, k)/(B, 1)/(1, 1)
    revisited accumulators. ``tiles`` counts the tiles whose compute ran
    (the grid less the tiles early exit skipped).

    The hit mask is the theta_R early-accept (DESIGN.md §4): per query,
    ``best similarity >= theta`` the moment the tile that produced the best
    is merged — the serving cache reads it directly instead of re-comparing
    on the host. theta=2.0 (unreachable) keeps the mask all-false and
    degrades to plain exact top-k.
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.int32)
        tiles_ref[...] = jnp.zeros(tiles_ref.shape, jnp.int32)

    def _compute():
        tiles_ref[...] += 1
        q = q_ref[...]
        c = c_ref[...]
        sims = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), precision=PRECISION,
            preferred_element_type=jnp.float32)              # (B, Ct)
        v = valid_ref[...]                                   # (1, Ct)
        sims = jnp.where(v != 0, sims, NEG)
        base = t * block_n
        gcol = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1) + base
        rv, ri = _merge_topk(vals_ref[...], idx_ref[...], sims, gcol, k)
        vals_ref[...] = rv
        idx_ref[...] = ri
        hit_ref[...] = (rv[:, :1] >= theta_ref[0]).astype(jnp.int32)

    if early_exit:
        # worst (over queries) current-best similarity already >= theta:
        # every query has a serviceable hit -> skip this tile's matmul
        # (the hit mask is already all-ones and stays valid).
        done = jnp.logical_and(t > 0,
                               jnp.min(vals_ref[:, 0]) >= theta_ref[0])

        @pl.when(jnp.logical_not(done))
        def _():
            _compute()
    else:
        _compute()


def cosine_topk_q8_kernel(tm_ref, q_ref, c_ref, s_ref, valid_ref, vals_ref,
                          idx_ref, hit_ref, *, k: int, block_n: int,
                          early_exit: bool):
    """int8 variant of ``cosine_topk_kernel`` (DESIGN.md §15).

    Centroid tiles stream HBM -> VMEM as int8 codes (quarter the f32
    bandwidth/footprint) with per-row symmetric scales ``s_ref`` (1, Ct);
    dequant is fused into the tile compute — the same widen-then-scale
    pattern as the int8-KV path in kernels/decode_attention. The scale is
    applied *after* the (B, D) x (D, Ct) accumulation (one multiply per
    output element instead of per input element), so the quantized
    similarity is ``(q . codes_j) * scale_j`` exactly.

    ``tm_ref`` prefetches [theta, margin]: the hit mask (and early exit)
    compares against ``theta + margin`` so a kernel-reported hit is
    *conservative* — quantization error can never turn a true reject into
    an accept. Candidates inside the margin are exactly rescored by the
    caller against full-precision rows (see SemanticCache._rescore_exact).
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.int32)

    thr = tm_ref[0] + tm_ref[1]

    def _compute():
        q = q_ref[...]
        c = c_ref[...].astype(jnp.float32)                   # dequant widen
        sims = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), precision=PRECISION,
            preferred_element_type=jnp.float32)              # (B, Ct)
        sims = sims * s_ref[...]                             # per-row scale
        v = valid_ref[...]                                   # (1, Ct)
        sims = jnp.where(v != 0, sims, NEG)
        base = t * block_n
        gcol = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1) + base
        rv, ri = _merge_topk(vals_ref[...], idx_ref[...], sims, gcol, k)
        vals_ref[...] = rv
        idx_ref[...] = ri
        hit_ref[...] = (rv[:, :1] >= thr).astype(jnp.int32)

    if early_exit:
        done = jnp.logical_and(t > 0, jnp.min(vals_ref[:, 0]) >= thr)

        @pl.when(jnp.logical_not(done))
        def _():
            _compute()
    else:
        _compute()
