"""Operations and bytes of DeepSeek-V2 training, counted from the
configuration (``bench/configs/deepseek-v2-lite.json``).

Useful operations (:func:`train_flops`): the forward and backward passes
over the job's tokens, 6 per matmul weight per token (2 forward, 4
backward), plus attention's score and value products at the full
sequence length, ``6 * heads * seq * (qk_head_dim + v_head_dim)`` per
layer and token (the PaLM appendix B count with two head sizes).  The
routed experts count per routed row, not per token: 6 per weight of one
expert for each (token, held expert) selection.  Recomputation, the
coded step's redundant chunk passes and the optimizer do not count.

The grouped expert matmuls (:func:`gmm_work`) count what the chip
executes for the routed rows: each of the three expert matrices is
multiplied forward, again in the backward pass's recomputation (the
layer is rematerialised), and twice backward (the rows' gradient and
the weights' gradient), each a product of ``2 * rows * d * eff``
operations that reads and writes ``rows * (d + eff)`` activations and
the held experts' weights once, in bfloat16.  Rows of the static buffer
past the routed ones count nothing.
"""

from __future__ import annotations

#: executions of each expert matrix per layer and step: forward,
#: recomputation, rows' gradient, weights' gradient
GMM_EXECUTIONS = 4
BF16 = 2


def _attn_weights(cfg: dict) -> int:
    d, H, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)


def token_weights(cfg: dict) -> int:
    """Matmul weights every token passes through: attention, the dense
    layers' MLP, each expert layer's router and shared experts, the
    head."""
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d, eff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_expert_layer = (d * cfg["router_experts"]
                        + 3 * d * cfg["n_shared_experts"] * eff)
    return (L * _attn_weights(cfg) + dense * 3 * d * cfg["intermediate_size"]
            + (L - dense) * per_expert_layer + d * cfg["vocab_size"])


def token_flops(cfg: dict, seq: int) -> int:
    """Useful training operations of one token outside the routed
    experts."""
    attn = 6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * seq \
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])
    return 6 * token_weights(cfg) + attn


def routed_row_flops(cfg: dict) -> int:
    """Useful training operations of one routed (token, expert) row."""
    return 6 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_rows(cfg: dict, tokens: int) -> float:
    """Routed rows a uniform router sends to the held experts: each token
    picks ``K`` of ``router_experts``, ``held`` of which live here, in
    each expert layer."""
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (tokens * layers * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_experts"])


def train_flops(cfg: dict, batch: int, seq: int, routed_rows: float) -> float:
    """Useful training operations of one job of ``batch`` sequences whose
    tokens sent ``routed_rows`` rows to the held experts over all expert
    layers."""
    return batch * seq * token_flops(cfg, seq) \
        + routed_rows * routed_row_flops(cfg)


def gmm_work(cfg: dict, rows: float, layer_steps: int) -> tuple[float, float]:
    """(operations, bytes) the grouped expert matmuls execute for
    ``rows`` routed rows, computed over ``layer_steps`` (expert layer,
    step) calls."""
    d, eff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = 3 * GMM_EXECUTIONS
    flops = n * 2 * rows * d * eff
    nbytes = n * BF16 * (rows * (d + eff)
                         + layer_steps * cfg["n_routed_experts"] * d * eff)
    return flops, nbytes
