"""Operations of Qwen2 training, counted from the configuration.

Useful operations only: the forward and backward passes over the
job's tokens, 6 per matmul weight per token (2 forward, 4 backward),
plus attention's score and value products, 12 * layers * heads *
head_dim * seq per token (the PaLM appendix B count).  The embedding
lookup is no matmul; the tied head is.  Recomputation, the coded
step's redundant chunk passes and the optimizer do not count.
"""

from __future__ import annotations


def matmul_weights(cfg: dict) -> int:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, K, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * ff
    return L * per_layer + V * d


def train_flops_per_token(cfg: dict, seq: int) -> int:
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * seq
    return 6 * matmul_weights(cfg) + attn


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    """One job's useful training operations."""
    return batch * seq * train_flops_per_token(cfg, seq)
