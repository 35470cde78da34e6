"""Pipeline parallelism as a stage-scan (GPipe schedule).

Optional at 512 chips for the assigned sizes (DESIGN.md §6) but required
substrate for 1000+-node deployments where a layer stack no longer fits a
single model-parallel group. Stages hold contiguous layer spans; the
microbatch loop runs as a lax.scan with a collective_permute hop between
neighbouring stages, so the bubble is the standard (S-1)/(M+S-1) and
forward compute overlaps the ICI hop (XLA schedules the ppermute async).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, params_stacked, x: jax.Array,
                     *, mesh: Mesh, axis: str = "stage",
                     n_microbatches: int = 4) -> jax.Array:
    """Run x through S pipeline stages living on the `axis` mesh dim.

    stage_fn(stage_params, x_micro) -> x_micro: one stage's layers.
    params_stacked: pytree with a leading stage dim, sharded over `axis`.
    x: (B, ...) global batch; B % n_microbatches == 0.

    GPipe: T = M + S - 1 scan steps; at step t, stage s processes
    microbatch (t - s) when 0 <= t - s < M. Stage 0 feeds fresh
    microbatches; the last stage's outputs are collected in order.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    M = n_microbatches
    micro = x.reshape(M, B // M, *x.shape[1:])
    perm = [(i, i + 1) for i in range(S - 1)]     # downstream hop

    def kern(p_local, micro_local):
        p_stage = jax.tree.map(lambda a: a[0], p_local)  # this stage's span
        sid = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(micro_local[0])
        outs0 = jnp.zeros_like(micro_local)

        def step(carry, t):
            inflight, outs = carry
            mb_idx = t - sid                      # microbatch at this stage
            live = (mb_idx >= 0) & (mb_idx < M)
            feed = jnp.where(
                sid == 0,
                micro_local[jnp.clip(t, 0, M - 1)],   # fresh input
                inflight)                              # from upstream
            y = stage_fn(p_stage, feed)
            y = jnp.where(live, y, zero)
            # last stage emits; others forward downstream
            outs = jnp.where(
                (sid == S - 1) & live,
                outs.at[jnp.clip(mb_idx, 0, M - 1)].set(y), outs)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(step, (zero, outs0),
                                    jnp.arange(M + S - 1))
        return outs

    fn = jax.shard_map(
        kern, mesh=mesh,
        in_specs=(P(axis), P()),       # params stage-sharded; batch replicated
        out_specs=P(axis),             # (S*M, b, ...): per-stage out buffers
        check_vma=False)
    outs = fn(params_stacked, micro)
    outs = outs.reshape(S, M, B // M, *x.shape[1:])[-1]   # last stage's
    return outs.reshape(B, *x.shape[1:])


def stage_spans(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous [start, stop) layer spans, remainder to early stages."""
    base, rem = divmod(n_layers, n_stages)
    spans, s = [], 0
    for i in range(n_stages):
        e = s + base + (1 if i < rem else 0)
        spans.append((s, e))
        s = e
    return spans


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
