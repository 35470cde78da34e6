"""AdamW from scratch (no optax), pytree-native, FSDP-friendly.

Optimizer state mirrors the param tree (m, v) so the same PartitionSpecs
shard parameters and moments identically (ZeRO-style). Optional int8 / topk
gradient compression hooks live in repro/distributed/compression.py and are
applied to gradients *before* the update.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array          # scalar int32
    m: Any                   # pytree like params (fp32)
    v: Any                   # pytree like params (fp32)


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    # moment storage dtype: "float32" (default) or "bfloat16" (halves the
    # per-chip optimizer bytes — the §Perf B5 memory lever; math stays f32)
    moment_dtype: str = "float32"


def init_state(params, moment_dtype: str = "float32") -> AdamWState:
    dt = jnp.dtype(moment_dtype)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)
    return AdamWState(jnp.zeros((), jnp.int32), zeros,
                      jax.tree.map(jnp.copy, zeros))


def _decay_mask(path) -> bool:
    """No weight decay for 1D params (norms, biases) — standard practice."""
    name = str(path[-1])
    return not any(s in name for s in ("scale", "bias", "ln", "norm"))


def schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_frac."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def apply_updates(params, grads, state: AdamWState, cfg: AdamWConfig
                  ) -> tuple[Any, AdamWState, dict]:
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    step = state.step + 1
    lr = schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    flat_p, treedef = jax.tree.flatten_with_path(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    mdt = jnp.dtype(cfg.moment_dtype)
    new_p, new_m, new_v = [], [], []
    for (path, pval), g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        gf = g.astype(jnp.float32) * clip
        m = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
        v = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * jnp.square(gf)
        upd = (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps)
        if cfg.weight_decay and _decay_mask(path):
            upd = upd + cfg.weight_decay * pval.astype(jnp.float32)
        new_p.append((pval.astype(jnp.float32) - lr * upd).astype(pval.dtype))
        new_m.append(m.astype(mdt))
        new_v.append(v.astype(mdt))
    params = jax.tree.unflatten(treedef, [x for x in new_p])
    mtree = jax.tree.unflatten(treedef, new_m)
    vtree = jax.tree.unflatten(treedef, new_v)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return params, AdamWState(step, mtree, vtree), metrics
