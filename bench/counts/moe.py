"""Operations and bytes a decode step needs at least, for a decoder with
multi-head latent attention and expert layers of which this chip holds a
share (DeepSeek-V2), from the configuration's sizes (Hugging Face key
names), the step's kv lengths and its routing counter.

The least a step reads: every weight matrix outside the routed experts
once (attention, the dense layers' MLPs, the routers, the shared experts,
the head), of the routed experts only those the counter shows received a
token, the latent and rope-key cache rows each token attends over, and
the embedding rows of its tokens. Its operations: twice the matmul
parameters each token passes through (its held experts' as the counter
gives them), and attention in latent space over its kv length (scores
over the latent and the rope key, values over the latent).
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def attn_params(m: dict) -> int:
    d, H = m["hidden_size"], m["num_attention_heads"]
    qd = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    R, vd = m["kv_lora_rank"], m["v_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * H * qd
            + d * R + d * m["qk_rope_head_dim"]
            + R * H * (m["qk_nope_head_dim"] + vd) + H * vd * d)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    """Every weight outside the routed experts that a token passes
    through: attention, dense MLPs, routers, shared experts, the head."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    n_dense = m["first_k_dense_replace"]
    n_moe = L - n_dense
    router = d * m["n_routed_experts"] * m["ep_size"]
    return (L * attn_params(m) + n_dense * 3 * d * m["intermediate_size"]
            + n_moe * (router + m["n_shared_experts"] * expert_params(m))
            + d * m["vocab_size"])


def decode_least(m: dict, kv_lens, routed) -> tuple:
    """(operations, bytes) of one decode step over the active slots, whose
    kv lengths (the new token included) are ``kv_lens``; ``routed``
    (n_moe_layers, held experts) counts the assignments each held expert
    received."""
    wb = DTYPE_BYTES[m["dtype"]]
    routed = np.asarray(routed)
    kv = int(np.sum(kv_lens))
    n = len(kv_lens)
    L, d, H = m["num_hidden_layers"], m["hidden_size"], \
        m["num_attention_heads"]
    R, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    fixed, expert = shared_params(m), expert_params(m)
    nbytes = (fixed + int(np.count_nonzero(routed)) * expert) * wb \
        + L * kv * (R + rope) * wb + n * d * wb
    ops = 2 * n * fixed + 2 * int(routed.sum()) * expert \
        + L * 2 * H * kv * (2 * R + rope)
    return float(ops), float(nbytes)
