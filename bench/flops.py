"""Operations a step needs, computed from the configuration's shapes.

Model FLOPs of a decoder-only transformer's training step (PaLM,
arXiv:2204.02311, appendix B): each parameter of a matrix multiplication
costs 2 operations a token forward and 4 backward, and attention's two
products (scores and values) cost 2 * 2 * seq * heads * head_dim a token a
layer forward, counted over the full square as PaLM counts them.  The
embedding lookup is no multiplication; with tied embeddings the output
head multiplies by the same table.  Recomputation (rematerialisation) is
not counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix multiplication, per token."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward operations a token of a training step."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    attn = 4 * cfg["num_hidden_layers"] * seq * h * hd
    return 3.0 * (2 * matmul_params(cfg) + attn)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * batch * seq
