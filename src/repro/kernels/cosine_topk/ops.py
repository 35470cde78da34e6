"""jit'd wrapper around the cosine_topk Pallas kernel.

Pads (B, N, D) to TPU-friendly multiples, sets BlockSpecs, and runs in
interpret mode automatically off-TPU. ``theta`` only matters with
``early_exit=True`` (match-good-enough semantics, see kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cosine_topk.kernel import (cosine_topk_kernel,
                                              cosine_topk_q8_kernel)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def quantize_rows(rows: np.ndarray, width: int | None = None
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization of an (n, d) f32 matrix.

    Returns (codes (n, width) int8 — lane-padded with zero columns when
    ``width`` > d, scales (n,) f32, err (n,) f64) where
    ``row_j ~= codes_j * scale_j`` and ``err_j = ||row_j - codes_j *
    scale_j||_2`` computed in float64. ``err_j`` bounds the quantized-sim
    deviation for any query: |q . row_j - (q . codes_j) * scale_j|
    <= ||q||_2 * err_j (Cauchy-Schwarz), which is what makes the margin
    rescoring in SemanticCache exact (DESIGN.md §15).
    """
    rows = np.ascontiguousarray(np.asarray(rows, np.float32))
    n, d = rows.shape
    width = int(width if width is not None else d)
    amax = np.abs(rows).max(axis=1) if n else np.zeros((0,), np.float32)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    codes = np.zeros((n, width), np.int8)
    if n:
        codes[:, :d] = np.clip(np.rint(rows / scales[:, None]),
                               -127, 127).astype(np.int8)
    deq = codes[:, :d].astype(np.float32) * scales[:, None]
    err = np.linalg.norm(rows.astype(np.float64) - deq.astype(np.float64),
                         axis=1)
    return codes, scales, err


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret",
                                             "early_exit", "return_hit",
                                             "return_tiles"))
@jax.named_scope("cosine_topk")
def cosine_topk(queries: jax.Array, centroids: jax.Array, k: int = 1,
                valid: jax.Array | None = None,
                theta: float | jax.Array = 2.0,
                block_n: int = 512, interpret: bool | None = None,
                early_exit: bool = False, return_hit: bool = False,
                return_tiles: bool = False):
    """queries (B, D) x centroids (N, D) -> (sims (B, k) f32, idx (B, k) i32).

    valid: (N,) bool/int — rows to consider (default all). theta=2.0 (never
    reached) disables early exit even when compiled with early_exit=True.
    With ``return_hit`` a third output (B,) bool is appended: the kernel's
    theta_R early-accept mask (best sim >= theta), so the serving cache gets
    hit decisions straight off the device with no host re-compare. With
    ``return_tiles`` a last output (2,) int32 is appended: the grid tiles
    whose compute ran (early exit skips the rest) and the tiles in the grid.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, D = queries.shape
    N, Dc = centroids.shape
    if B == 0:
        out = (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
        if return_hit:
            out += (jnp.zeros((0,), bool),)
        return out + (jnp.zeros((2,), jnp.int32),) if return_tiles else out
    # --- padding: D to lane width, N to tile, B to sublane count ---
    Dp = _ceil_to(max(D, Dc, 1), 128)
    Bp = _ceil_to(max(B, 1), 8)
    block_n = min(block_n, _ceil_to(max(N, 1), 128))
    Np = _ceil_to(max(N, 1), block_n)
    # pad query rows by repeating the last real row (not zeros): padded rows
    # then track a real query, so the all-queries early-exit min is never
    # held back by padding that can't clear theta.
    rows = jnp.minimum(jnp.arange(Bp), B - 1)
    q = jnp.zeros((Bp, Dp), jnp.float32).at[:, :D].set(
        queries.astype(jnp.float32)[rows])
    # Pre-padded fast path: a persistent serving mirror hands us a matrix
    # already at (Np, Dp) f32 — re-padding it here would be O(N) host work
    # per lookup (it used to be; the caller's mirror is shaped for this).
    # The extra zero lane columns beyond the true D contribute exactly 0.0
    # to every dot product, so results are bit-identical either way.
    # Pre-padded callers must pass a ``valid`` mask covering the pad rows.
    if Dc == Dp and N == Np and centroids.dtype == jnp.float32:
        c = centroids
    else:
        c = jnp.zeros((Np, Dp), jnp.float32).at[:N, :Dc].set(
            centroids.astype(jnp.float32))
    if valid is None:
        v = jnp.zeros((1, Np), jnp.int32).at[0, :N].set(1)
    else:
        v = valid.astype(jnp.int32)
        v = (v.reshape(1, Np) if v.shape[0] == Np
             else jnp.zeros((1, Np), jnp.int32).at[0, :N].set(v))
    theta_arr = jnp.asarray([theta], jnp.float32)

    grid = (Np // block_n,)
    kern = functools.partial(cosine_topk_kernel, k=k, block_n=block_n,
                             early_exit=early_exit)
    vals, idx, hit, tiles = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((Bp, Dp), lambda t, *_: (0, 0)),      # queries
                pl.BlockSpec((block_n, Dp), lambda t, *_: (t, 0)),  # centroid tile
                pl.BlockSpec((1, block_n), lambda t, *_: (0, t)),   # valid tile
            ],
            out_specs=[
                pl.BlockSpec((Bp, k), lambda t, *_: (0, 0)),
                pl.BlockSpec((Bp, k), lambda t, *_: (0, 0)),
                pl.BlockSpec((Bp, 1), lambda t, *_: (0, 0)),
                pl.BlockSpec((1, 1), lambda t, *_: (0, 0)),         # tiles run
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(theta_arr, q, c, v)
    vals, idx = vals[:B], idx[:B]
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    out = (vals, idx)
    if return_hit:
        out += (hit[:B, 0].astype(bool),)
    if return_tiles:
        out += (jnp.stack([tiles[0, 0], jnp.int32(grid[0])]),)
    return out


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret",
                                             "early_exit", "return_hit"))
def cosine_topk_q8(queries: jax.Array, codes: jax.Array, scales: jax.Array,
                   k: int = 1, valid: jax.Array | None = None,
                   theta: float | jax.Array = 2.0,
                   margin: float | jax.Array = 0.0,
                   block_n: int = 512, interpret: bool | None = None,
                   early_exit: bool = False, return_hit: bool = False):
    """Quantized lookup: queries (B, D) x codes (N, Dc) int8 with per-row
    scales (N,) f32 -> (quant sims (B, k) f32, idx (B, k) i32).

    The similarity for row j is ``(q . codes_j) * scale_j`` — within
    ``||q||_2 * err_j`` of the exact f32 sim (see quantize_rows). The hit
    mask (``return_hit``) and early exit compare against ``theta + margin``
    so they are conservative: a reported hit is guaranteed to be a true
    accept at ``theta`` whenever ``margin >= ||q||_2 * max_j err_j``.
    Codes may arrive pre-padded (rows % block_n == 0, lanes % 128 == 0)
    from a persistent mirror — then no per-call O(N) padding happens.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, D = queries.shape
    N, Dc = codes.shape
    if B == 0:
        empty = (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
        return (*empty, jnp.zeros((0,), bool)) if return_hit else empty
    Dp = _ceil_to(max(D, Dc, 1), 128)
    Bp = _ceil_to(max(B, 1), 8)
    # int8 min tile is (32, 128): keep centroid tiles a multiple of 32 rows.
    block_n = min(block_n, _ceil_to(max(N, 1), 128))
    Np = _ceil_to(max(N, 1), block_n)
    rows = jnp.minimum(jnp.arange(Bp), B - 1)
    q = jnp.zeros((Bp, Dp), jnp.float32).at[:, :D].set(
        queries.astype(jnp.float32)[rows])
    if Dc == Dp and N == Np and codes.dtype == jnp.int8:
        c = codes
    else:
        c = jnp.zeros((Np, Dp), jnp.int8).at[:N, :Dc].set(
            codes.astype(jnp.int8))
    s = (scales.astype(jnp.float32).reshape(1, Np) if scales.shape[0] == Np
         else jnp.zeros((1, Np), jnp.float32).at[0, :N].set(
             scales.astype(jnp.float32)))
    if valid is None:
        v = jnp.zeros((1, Np), jnp.int32).at[0, :N].set(1)
    else:
        v = valid.astype(jnp.int32)
        v = (v.reshape(1, Np) if v.shape[0] == Np
             else jnp.zeros((1, Np), jnp.int32).at[0, :N].set(v))
    tm = jnp.stack([jnp.asarray(theta, jnp.float32),
                    jnp.asarray(margin, jnp.float32)])

    grid = (Np // block_n,)
    kern = functools.partial(cosine_topk_q8_kernel, k=k, block_n=block_n,
                             early_exit=early_exit)
    vals, idx, hit = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((Bp, Dp), lambda t, *_: (0, 0)),       # queries
                pl.BlockSpec((block_n, Dp), lambda t, *_: (t, 0)),  # codes
                pl.BlockSpec((1, block_n), lambda t, *_: (0, t)),   # scales
                pl.BlockSpec((1, block_n), lambda t, *_: (0, t)),   # valid
            ],
            out_specs=[
                pl.BlockSpec((Bp, k), lambda t, *_: (0, 0)),
                pl.BlockSpec((Bp, k), lambda t, *_: (0, 0)),
                pl.BlockSpec((Bp, 1), lambda t, *_: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(tm, q, c, s, v)
    vals, idx = vals[:B], idx[:B]
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    if return_hit:
        return vals, idx, hit[:B, 0].astype(bool)
    return vals, idx


def cosine_top1_local(queries: jax.Array, centroids: jax.Array,
                      valid: jax.Array | None = None,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Shard-local exact top-1 for the sharded cache plane (DESIGN.md §11).

    Runs inside shard_map over the ``cache`` axis, so early exit is
    disabled: the cross-shard argmax reduction needs each shard's *exact*
    best candidate, not the kernel's first match-good-enough row. Misses
    (no valid row on this shard) are clamped to row 0 with their -inf
    similarity kept, which loses every cross-shard comparison while
    letting the caller gather the candidate answer unconditionally.
    Returns ((B,) best sims, (B,) local rows).
    """
    vals, idx = cosine_topk(queries, centroids, k=1, valid=valid,
                            theta=2.0, early_exit=False,
                            interpret=interpret)
    return vals[:, 0], jnp.maximum(idx[:, 0], 0)
