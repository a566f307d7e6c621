"""Operations a decoder needs per trained token: what ``mfu.train`` counts.

6 x (parameters in matrix multiplications) for forward and backward, plus
causal attention's two matrix products (QK^T and PV), forward and backward,
at half the square because of the mask. The embedding lookup is not a
multiplication and is left out; the head is in. Recomputed operations are
not counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * v


def total_params(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return matmul_params(cfg) + v * h + (2 * cfg["num_hidden_layers"] + 1) * h


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward+backward FLOPs of causal attention per token at length
    ``seq``: forward is 2 products x 2 FLOP x heads x head_dim x seq / 2
    (mask), backward twice that."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    fwd = 2 * 2 * cfg["num_attention_heads"] * hd * seq / 2
    return 3 * fwd * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)
