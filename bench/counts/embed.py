"""Operations of one encoder call at its (batch, seq_len) bucket, from the
configuration's sizes: the factorized embedding projection, and per
application of the shared layer the q/k/v/o and MLP matmuls plus
attention over the whole bucket (the encoder attends every position)."""
from __future__ import annotations


def bucket_ops(e: dict) -> float:
    B, T = e["batch"], e["seq_len"]
    d, ff = e["hidden_size"], e["intermediate_size"]
    tok = B * T
    ops = 2 * tok * e["embedding_size"] * d
    per_layer = 2 * tok * (4 * d * d + 2 * d * ff) + 2 * 2 * B * T * T * d
    return float(ops + e["num_hidden_layers"] * per_layer)
