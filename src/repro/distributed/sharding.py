"""Sharding rules: params / optimizer state / activations / caches.

Mesh axes: ("pod",)? + ("data", "model").
  * TP        — feature dims over "model" (XLA pads non-divisible dims).
  * FSDP      — train mode also shards the complementary feature dim (and
                the AdamW moments, which reuse the same specs) over "data".
  * EP        — MoE expert dim over "model" when divisible, else the expert
                ffn dim ("2D MoE sharding", needed to fit deepseek-v2-236b's
                226B expert bytes: E/16 x d_ff/16 -> ~1.8 GB/chip).
  * DP        — batch over ("pod","data") for activations and caches.

Rules are name-based over the param tree; stacked layer dims (scan) get
leading None automatically by right-aligning the spec against the rank.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# param-name -> spec over the LAST dims (right-aligned; rest None)
# "F" marks the fsdp-shardable dim (data axis in train mode, None in serve).
_COL = ("wq", "wk", "wv", "wg", "wr", "w_gate", "w_up", "in_proj", "cm_wk",
        "cm_wr", "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "lm_head",
        "embed_proj")
_ROW = ("wo", "w_down", "out_proj", "cm_wv")
_REPL = ("scale", "bias", "bq", "bk", "bv", "mu", "mu_x", "cm_mu_k",
         "cm_mu_r", "w0", "wa", "wb", "dd_w1", "dd_w2", "u", "A_log", "D",
         "dt_bias", "conv_b", "router", "lora_a", "lora_b", "tok_embed")


def _leaf_name(path) -> str:
    for entry in reversed(path):
        k = getattr(entry, "key", None)
        if isinstance(k, str):
            return k
    return ""


def param_spec(path, leaf, cfg: ModelConfig, fsdp: bool,
               expert_data: bool = False,
               fsdp_axes: tuple = ("data",)) -> P:
    """expert_data: serve-mode 2D MoE sharding — experts over "data",
    expert ffn over "model" (needed to fit deepseek-v2's 445 GB of expert
    bytes at inference, where fsdp=False leaves no data-axis sharding).
    fsdp_axes: mesh axes the FSDP dim shards over — ("pod", "data") on the
    multi-pod mesh halves per-chip moments/grads (§Perf B4)."""
    name = _leaf_name(path)
    path_str = "/".join(str(getattr(e, "key", e)) for e in path)
    F = (fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]) if fsdp else None
    nd = np.ndim(leaf)

    def right(spec_tail: tuple) -> P:
        pad = (None,) * (nd - len(spec_tail))
        return P(*(pad + spec_tail))

    if name == "embed":
        return right(("model", F))
    if "mlp" in path_str and "shared" not in path_str \
            and name in ("w_gate", "w_up", "w_down") \
            and nd >= 4 and cfg.is_moe:
        # MoE expert tensors (E, d_in, d_out)
        if expert_data:
            if name == "w_down":
                return right(("data", "model", None))
            return right(("data", None, "model"))
        if cfg.n_experts % 16 == 0:
            if name == "w_down":
                return right(("model", F, None))
            return right(("model", None, F))
        # small expert count: shard ffn dim over model, fsdp on the other
        if name == "w_down":
            return right((None, "model", F))
        return right((None, F, "model"))
    if name == "conv_w":
        return right((None, "model"))
    if name in _REPL or nd <= 1:
        return P(*([None] * nd))
    if name in _COL:
        return right((F, "model"))
    if name in _ROW:
        return right(("model", F))
    return P(*([None] * nd))


def param_specs(params, cfg: ModelConfig, fsdp: bool,
                expert_data: bool = False, fsdp_axes: tuple = ("data",)):
    return jax.tree.map_with_path(
        lambda path, leaf: param_spec(path, leaf, cfg, fsdp, expert_data,
                                      fsdp_axes),
        params)


def opt_state_specs(state, params_specs):
    """AdamW moments reuse the param specs; step is replicated."""
    from repro.training.optimizer import AdamWState
    return AdamWState(P(), params_specs, params_specs)


def _dp_axis(dp):
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def batch_specs(cfg: ModelConfig, kind: str, dp=("data",)) -> dict:
    dp_ax = _dp_axis(dp)
    spec: dict = {"tokens": P(dp_ax, None)}
    if kind == "train":
        spec["labels"] = P(dp_ax, None)
    if cfg.family == "vlm":
        spec["patch_embed"] = P(dp_ax, None, None)
    if cfg.is_encoder_decoder:
        spec["frames"] = P(dp_ax, None, None)
    return spec


def cache_specs(cfg: ModelConfig, dp=("data",), seq_shard: bool = False,
                seq_axes=None):
    """Decode cache specs. Default: batch over dp, heads over model.
    seq_shard=True: KV sequence over model (flash-decoding SP) — used when
    batch(or heads) can't absorb the mesh (long_500k) or as a perf knob.
    seq_axes: explicit axes tuple for the KV seq dim (overrides seq_shard),
    e.g. ("data", "model") for long_500k's batch-1 caches."""
    dp_ax = _dp_axis(dp)
    kind_specs = {}
    if seq_axes is not None:
        seq_ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
        head_ax = None
    else:
        seq_ax = "model" if seq_shard else None
        head_ax = None if seq_shard else "model"
    kind_specs["k"] = kind_specs["v"] = P(None, dp_ax, seq_ax, head_ax, None)
    kind_specs["k_scale"] = kind_specs["v_scale"] = P(None, dp_ax, seq_ax,
                                                      head_ax)
    # cross-attn memory: fixed enc_len (1500), not the decode seq — batch only
    kind_specs["xk"] = kind_specs["xv"] = P(None, dp_ax, None, None, None)
    kind_specs["ak"] = kind_specs["av"] = P(None, dp_ax, seq_ax, head_ax, None)
    kind_specs["latent"] = P(None, dp_ax, seq_ax, None)
    kind_specs["krope"] = P(None, dp_ax, seq_ax, None)
    # ssm states: heads over model
    kind_specs["s"] = P(None, dp_ax, "model", None, None)
    kind_specs["conv"] = P(None, dp_ax, None, "model")
    kind_specs["tm_x"] = P(None, dp_ax, None)
    kind_specs["cm_x"] = P(None, dp_ax, None)
    return kind_specs


def cache_spec_tree(cache, cfg: ModelConfig, dp=("data",),
                    seq_shard: bool = False, seq_axes=None):
    table = cache_specs(cfg, dp, seq_shard, seq_axes)
    return {k: table[k] for k in cache}


def named(mesh: Mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
