"""Collective helpers used by the serving/training paths.

* ``sharded_topk`` — the distributed cache lookup (DESIGN.md §2): centroids
  sharded over an axis; each shard computes a local top-k, then only the
  k candidates per query cross the wire (all-gather of O(B*k*mesh) scalars
  instead of the full (B, N) score matrix), followed by a local merge.
* ``cross_shard_top1`` — the sharded cache plane's merge step (DESIGN.md
  §11/§15): each shard contributes only its local best (sim, host row);
  the winner is selected with the exact single-device tie-break (max sim,
  then lowest host row) and the answer is then fetched from the winning
  shard with one psum — O(B * mesh) candidate scalars plus O(B * A)
  answer bytes, instead of gathering every shard's answer payload.
* ``ring_allreduce_schedule`` — an explicit reduce-scatter + all-gather
  decomposition via collective_permute, for overlap experiments where XLA's
  fused all-reduce is replaced by a schedulable ring.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def local_topk(queries: jax.Array, centroids: jax.Array, k: int
               ) -> tuple[jax.Array, jax.Array]:
    """Dense local top-k: (B, D) x (N, D) -> ((B, k) sims, (B, k) idx)."""
    sims = jnp.einsum("bd,nd->bn", queries, centroids,
                      preferred_element_type=jnp.float32)
    vals, idx = jax.lax.top_k(sims, k)
    return vals, idx.astype(jnp.int32)


def sharded_topk(queries: jax.Array, centroids: jax.Array, k: int,
                 mesh: Mesh, axis: str = "model"
                 ) -> tuple[jax.Array, jax.Array]:
    """Exact global top-k with centroids row-sharded over `axis`.

    Wire cost per device: 2 * B * k * world * 4 bytes (the gathered
    candidate lists), independent of N — the collective-optimal exact
    lookup for cache-scale corpora.
    """
    n_shard = mesh.shape[axis]
    N = centroids.shape[0]
    assert N % n_shard == 0, "pad centroids to a multiple of the axis size"

    def kern(q, c_local):
        i = jax.lax.axis_index(axis)
        vals, idx = local_topk(q, c_local, k)
        idx = idx + i * (N // n_shard)          # globalize
        vals_g = jax.lax.all_gather(vals, axis, axis=1)   # (B, world, k)
        idx_g = jax.lax.all_gather(idx, axis, axis=1)
        B = q.shape[0]
        vals_f = vals_g.reshape(B, n_shard * k)
        idx_f = idx_g.reshape(B, n_shard * k)
        best, pos = jax.lax.top_k(vals_f, k)
        return best, jnp.take_along_axis(idx_f, pos, axis=1)

    spec_q = P()                      # queries replicated over the axis
    spec_c = P(axis, None)
    fn = jax.shard_map(kern, mesh=mesh, in_specs=(spec_q, spec_c),
                       out_specs=(P(), P()), check_vma=False)
    return fn(queries, centroids)


def cross_shard_top1(best: jax.Array, host_row: jax.Array,
                     answer: jax.Array, answer_id: jax.Array,
                     theta, axis: str = "cache"
                     ) -> tuple[jax.Array, jax.Array, jax.Array,
                                jax.Array, jax.Array]:
    """Cross-shard argmax reduction for the sharded cache lookup
    (DESIGN.md §11). Runs inside shard_map over ``axis``.

    Slim merge: each shard contributes only its (sim, host_row) top-1
    candidate per query — 2 * B * world scalars over the wire — and the
    winner is selected lexicographically (highest sim, then lowest host
    row), which is exactly the single-device ``jnp.argmax`` tie-break
    over the concatenated host-row order. The answer payload does NOT
    ride the all-gather: ``answer`` (pad, A) / ``answer_id`` (pad,) are
    the shard's *full local blocks*, and once the winning host row is
    known, only the owner shard contributes its row to one (B, A) psum —
    O(B * A) instead of the old O(B * world * A) gathered payload.
    Returns replicated (hit, best_sim, winning host row, answer,
    answer_id) with the fused theta compare + answer gather applied
    (zeros / -1 on miss).
    """
    world = jax.lax.axis_size(axis)
    bg = jax.lax.all_gather(best, axis, axis=1)          # (B, world)
    rg = jax.lax.all_gather(host_row, axis, axis=1)      # (B, world)
    m = jnp.max(bg, axis=1)
    # shards tied at the max compete on host row; losers get +inf rows
    key = jnp.where(bg == m[:, None], rg, jnp.iinfo(jnp.int32).max)
    win = jnp.argmin(key, axis=1)
    row_win = jnp.take_along_axis(rg, win[:, None], axis=1)[:, 0]
    # winner-owner answer fetch: every shard traces the gather, only the
    # owner's contribution is nonzero, the psum moves it to all shards
    me = jax.lax.axis_index(axis).astype(row_win.dtype)
    mine = (row_win % world) == me
    l = row_win // world                                  # local row
    ans_win = jax.lax.psum(
        jnp.where(mine[:, None], answer[l], 0.0), axis)
    aid_win = jax.lax.psum(
        jnp.where(mine, answer_id[l], 0).astype(answer_id.dtype), axis)
    hit = m >= theta
    answer_out = jnp.where(hit[:, None], ans_win, 0.0)
    aid_out = jnp.where(hit, aid_win, -1)
    return hit, m, row_win, answer_out, aid_out


def ring_allreduce_schedule(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter + all-gather ring via collective_permute (inside
    shard_map). Equivalent to psum; exists so the schedule is explicit and
    each hop can be interleaved with compute by the caller."""
    world = jax.lax.axis_size(axis)
    if world == 1:
        return x
    perm = [(i, (i + 1) % world) for i in range(world)]
    n = x.shape[0]
    pad = (-n) % world
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    chunks = xp.reshape(world, -1, *xp.shape[1:])
    me = jax.lax.axis_index(axis)

    # reduce-scatter: after w-1 hops, chunk (me+1) % w holds the full sum
    def rs_step(i, carry):
        acc, send = carry
        recv = jax.lax.ppermute(send, axis, perm)
        idx = (me - i - 1) % world
        acc = acc.at[idx].add(recv[idx])
        return acc, acc

    acc, _ = jax.lax.fori_loop(0, world - 1, rs_step, (chunks, chunks))
    own = (me + 1) % world            # fully-reduced chunk index

    # all-gather the reduced chunks around the ring
    def ag_step(i, carry):
        out, send = carry
        recv = jax.lax.ppermute(send, axis, perm)
        idx = (own - i - 1) % world
        out = out.at[idx].set(recv[idx])
        return out, out

    start = jnp.zeros_like(chunks).at[own].set(acc[own])
    out, _ = jax.lax.fori_loop(0, world - 1, ag_step, (start, start))
    flat = out.reshape(-1, *x.shape[1:])
    return flat[:n]
