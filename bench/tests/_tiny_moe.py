"""The expert-model training cell cut to CPU size for the tests: same
driver, reference and limits, float32 so that the program and the
reference agree to rounding."""

from bench import harness
from bench.tests._tiny import run  # noqa: F401

MOE_SMALL = dict(num_hidden_layers=3, hidden_size=32, num_attention_heads=2,
                 num_key_value_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
                 qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
                 moe_intermediate_size=16, router_experts=8,
                 n_routed_experts=2, held_expert_start=2,
                 num_experts_per_tok=3, vocab_size=256,
                 torch_dtype="float32")


def moe_cell(name="train-gc-dsv2lite"):
    cell = harness.resolve(name)
    cell.config = dict(cell.config, **MOE_SMALL)
    cell.traffic = dict(cell.traffic, seq_len=8, jobs_per_call=2)
    return cell
