"""deepseek-v2-lite [moe] — latent attention (MLA, no q_lora), YaRN,
one dense layer then 64 routed experts top-6 + 2 shared, per-sequence
balance term [hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=10944,
    moe_d_ff=1408,
    vocab_size=102400,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    norm_topk_prob=False,
    first_k_dense=1,
    balance="seq",
    # the checkpoint's config leaves the training-only weight out
    balance_weight=0.001,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    dtype="bfloat16",
    source="hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434",
)

SMOKE = CONFIG.replace(
    name="deepseek-v2-lite-smoke",
    num_layers=3,
    d_model=64,
    num_heads=2,
    num_kv_heads=2,
    head_dim=24,
    d_ff=128,
    moe_d_ff=32,
    vocab_size=256,
    num_experts=4,
    num_experts_per_tok=2,
    held_experts=2,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    dtype="float32",
)
