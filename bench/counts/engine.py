"""Operations the engine's model needs per token, from the configuration's
sizes (Hugging Face key names), for a dense decoder with multi-head latent
attention: twice the matmul parameters of every layer, attention over the
token's kv length (scores over the nope and rope parts, then values),
and the vocabulary projection where logits are computed (every decode
token; only the last position of a prefill). Recomputed work does not
count.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    d, H = m["hidden_size"], m["num_attention_heads"]
    qd = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    R, vd = m["kv_lora_rank"], m["v_head_dim"]
    attn = (d * m["q_lora_rank"] + m["q_lora_rank"] * H * qd
            + d * R + d * m["qk_rope_head_dim"]
            + R * H * (m["qk_nope_head_dim"] + vd) + H * vd * d)
    return attn + 3 * d * m["intermediate_size"]


def token_ops(m: dict, kv_len: int, logits: bool) -> float:
    H = m["num_attention_heads"]
    att = 2 * H * kv_len * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                            + m["v_head_dim"])
    ops = m["num_hidden_layers"] * (2 * layer_matmul_params(m) + att)
    if logits:
        ops += 2 * m["hidden_size"] * m["vocab_size"]
    return float(ops)


def prefill_ops(m: dict, length: int) -> float:
    """A prompt of ``length`` tokens, causal: position t attends t+1."""
    H, L = m["num_attention_heads"], m["num_hidden_layers"]
    per = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    att = 2 * H * per * length * (length + 1) / 2
    return float(length * L * 2 * layer_matmul_params(m) + L * att
                 + 2 * m["hidden_size"] * m["vocab_size"])


def decode_ops(m: dict, kv_lens) -> float:
    return float(sum(token_ops(m, int(k), True) for k in kv_lens))
