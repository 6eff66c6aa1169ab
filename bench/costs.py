"""Operations and bytes that the benchmark's cells need, from shapes alone.

A later change to the program cannot move these numbers: they count the
work the model and the rule require, not the work an implementation does.
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def param_count(cfg: dict) -> int:
    """Every parameter of the model: embedding, per layer two norm scales,
    attention and gated MLP, the final norm and the untied output head."""
    d, v, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return v * d + L * (2 * d + layer_matmul_params(cfg)) + d + d * v


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per
    parameter that enters a matrix multiplication (the embedding lookup
    does not), plus causal attention, 6 * layers * heads * head_dim *
    seq_len (half of the 12 * L * H * Q * T of the full score matrix).
    Recomputation is not counted."""
    n = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
         + cfg["hidden_size"] * cfg["vocab_size"])
    attn = (6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * seq_len)
    return 6.0 * n + attn


def aggregation_bytes(workers: int, dim: int, itemsize: int = 4) -> int:
    """Bytes a coordinate-wise rule must move: read the (m, D) matrix of
    worker gradients once and write the D outputs, float32 as the rule
    computes."""
    return itemsize * (workers * dim + dim)
