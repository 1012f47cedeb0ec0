"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes and finiteness (assignment item f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _arch import arch_params
from repro.configs import ARCHS, get_config, get_smoke
from repro.models import decode_step, forward, init_cache, init_params, loss_fn

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def make_batch(cfg, b=B, s=S):
    rng = np.random.default_rng(0)
    if cfg.frontend == "audio_stub":
        return {
            "frames": jnp.asarray(
                rng.standard_normal((b, s, cfg.d_model)), jnp.float32
            ),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32
            ),
        }
    if cfg.frontend == "vision_stub":
        text = s - cfg.num_prefix_tokens
        return {
            "prefix_embeds": jnp.asarray(
                rng.standard_normal((b, cfg.num_prefix_tokens, cfg.d_model)),
                jnp.float32,
            ),
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b, text)), jnp.int32
            ),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b, text)), jnp.int32
            ),
        }
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
    }


@pytest.mark.parametrize("arch", arch_params(ARCHS))
def test_smoke_forward_shapes(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, KEY)
    batch = make_batch(cfg)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape[-1] == cfg.vocab_size
    assert logits.shape[0] == B
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", arch_params(ARCHS))
def test_smoke_train_step(arch):
    """One SGD step decreases nothing NaN-wise and produces finite grads."""
    cfg = get_smoke(arch)
    params = init_params(cfg, KEY)
    batch = make_batch(cfg)
    loss, grads = jax.value_and_grad(lambda p: loss_fn(p, cfg, batch))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat)
    # apply the step; loss on the same batch must remain finite
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
    loss2 = loss_fn(new_params, cfg, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize(
    "arch",
    arch_params([a for a in ARCHS if get_smoke(a).has_decode]),
)
def test_smoke_decode_step(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, KEY)
    cache = init_cache(cfg, B, S)
    token = jnp.ones((B, 1), jnp.int32)
    logits, new_cache = decode_step(params, cfg, cache, token, jnp.int32(3))
    assert logits.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """Full configs carry the exact assigned hyperparameters."""
    spec = {
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[arch]
    cfg = get_config(arch)
    got = (
        cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
        cfg.d_ff, cfg.vocab_size,
    )
    assert got == spec
    assert cfg.source  # every config cites its source


def test_assignment_extras():
    assert get_config("mixtral-8x22b").sliding_window > 0
    assert get_config("mixtral-8x22b").num_experts == 8
    assert get_config("mixtral-8x22b").num_experts_per_tok == 2
    q = get_config("qwen2-moe-a2.7b")
    assert (q.num_experts, q.num_experts_per_tok, q.num_shared_experts) == (60, 4, 4)
    assert q.qkv_bias and get_config("qwen2-72b").qkv_bias
    assert get_config("zamba2-2.7b").ssm_state == 64
    assert get_config("mamba2-1.3b").ssm_state == 128
    assert not get_config("hubert-xlarge").causal
    ds = get_config("deepseek-v2-lite")
    assert (ds.num_experts, ds.num_experts_per_tok, ds.num_shared_experts,
            ds.moe_d_ff, ds.first_k_dense) == (64, 6, 2, 1408, 1)
    assert (ds.kv_lora_rank, ds.qk_nope_head_dim, ds.qk_rope_head_dim,
            ds.v_head_dim) == (512, 128, 64, 128)
    assert ds.held == 64 and not ds.norm_topk_prob


def test_smoke_configs_are_reduced():
    for arch in ARCHS:
        s = get_smoke(arch)
        assert s.num_layers <= 4
        assert s.d_model <= 512
        assert s.num_experts <= 4


def test_param_counts_plausible():
    """param_count approximates the advertised sizes (same order)."""
    approx = {
        "llama3.2-1b": 1.2e9,
        "qwen2-72b": 72e9,
        "deepseek-67b": 67e9,
        "mamba2-1.3b": 1.3e9,
        "qwen2-0.5b": 0.5e9,
        "deepseek-v2-lite": 15.7e9,
    }
    for arch, target in approx.items():
        n = get_config(arch).param_count()
        assert 0.4 * target < n < 2.2 * target, (arch, n, target)
