"""DeepSeek-V2 layers against the plain reference (``bench/refs/
deepseek_v2.py``) at small sizes on seeded random weights: latent
attention, YaRN, the held-expert shares of the expert layer, dropless
routing, and the GC decode identity with the balance term on."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.refs import deepseek_v2 as ref  # noqa: E402
from repro.configs import get_config, get_smoke  # noqa: E402
from repro.core.gc import make_gradient_code  # noqa: E402
from repro.data import gc_chunked_batch, token_batch  # noqa: E402
from repro.models import init_params, loss_fn, sequence_losses  # noqa: E402
from repro.models.layers import (  # noqa: E402
    mla_apply,
    mla_softmax_scale,
    moe_apply,
    yarn_correction_range,
    yarn_cos_sin_scale,
    yarn_frequencies,
)
from repro.train.coded import gc_round_weights, make_coded_loss  # noqa: E402

SMOKE = get_smoke("deepseek-v2-lite")


def ref_config(cfg) -> dict:
    """The reference's configuration dict for a program config."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "router_experts": cfg.num_experts,
        "n_routed_experts": cfg.held,
        "held_expert_start": cfg.held_expert_start,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.num_shared_experts,
        "vocab_size": cfg.vocab_size,
        "rms_norm_eps": 1e-6,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim},
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": 1.0,
        "aux_loss_alpha": cfg.balance_weight,
        "torch_dtype": cfg.dtype,
    }


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _hidden(key, b, s, d):
    return jax.random.normal(jax.random.PRNGKey(key), (b, s, d))


def test_yarn_matches_the_hand_count():
    cfg = get_config("deepseek-v2-lite")
    assert yarn_correction_range(64, cfg) == (10, 23)
    i = np.arange(32)
    f = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(np.asarray(yarn_frequencies(64, cfg)),
                               f / 40 * ramp + f * (1 - ramp), rtol=1e-6)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=5e-7)
    assert yarn_cos_sin_scale(cfg) == 1.0
    inv, cos_sin, scale = ref.yarn(ref_config(cfg))
    np.testing.assert_allclose(np.asarray(inv),
                               np.asarray(yarn_frequencies(64, cfg)),
                               rtol=1e-6)
    assert (cos_sin, scale) == (1.0, mla_softmax_scale(cfg))


def test_mla_layer_matches_the_reference():
    cfg = SMOKE
    a = jax.tree.map(lambda x: x[0],
                     init_params(cfg, jax.random.PRNGKey(3))["layers"]["attn"])
    x = _hidden(4, 2, 24, cfg.d_model)
    got = mla_apply(a, x, cfg)
    want = ref.mla(a, x, ref_config(cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_mla_takes_the_chunked_attention_path():
    """Past one 1,024-key block the attention core is the online-softmax
    scan, with a value head narrower than the query's."""
    cfg = SMOKE
    a = jax.tree.map(lambda x: x[0],
                     init_params(cfg, jax.random.PRNGKey(5))["layers"]["attn"])
    x = _hidden(6, 1, 1030, cfg.d_model)
    np.testing.assert_allclose(np.asarray(mla_apply(a, x, cfg)),
                               np.asarray(ref.mla(a, x, ref_config(cfg))),
                               rtol=1e-4, atol=1e-5)


SHARES = 8
UNCUT = SMOKE.replace(num_experts=16, num_experts_per_tok=6, held_experts=0)


def _uncut_moe():
    return jax.tree.map(lambda x: x[0],
                        init_params(UNCUT, jax.random.PRNGKey(7))
                        ["layers"]["moe"])


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each: their routed outputs, with the
    shared expert counted once, are the reference's whole layer."""
    p, held = _uncut_moe(), UNCUT.num_experts // SHARES
    x = _hidden(8, 2, 16, UNCUT.d_model)
    total, rows = 0.0, 0
    for c in range(SHARES):
        cfg = UNCUT.replace(held_expert_start=held * c, held_experts=held)
        share = dict(p, **{k: p[k][held * c: held * (c + 1)]
                           for k in ("w_gate", "w_up", "w_down")})
        y, aux, counts = moe_apply(share, x, cfg)
        total = total + y
        rows += int(counts.sum())
    shared = ref._swiglu(p["shared"], x, ref._dot(None))
    want, want_aux, idx = ref.experts(p, x, ref_config(UNCUT))
    np.testing.assert_allclose(np.asarray(total - (SHARES - 1) * shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(want_aux),
                               rtol=1e-5)
    assert rows == idx.size     # every selection computed once, somewhere
    y, _, counts = moe_apply(p, x, UNCUT)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(counts.sum()) == idx.size


def test_routing_is_dropless():
    """A sequence's loss and balance term do not change with the
    sequences routed beside it, however many pick the same experts."""
    cfg = SMOKE
    params = init_params(cfg, jax.random.PRNGKey(9))
    toks = jax.random.randint(jax.random.PRNGKey(10), (1, 16), 0,
                              cfg.vocab_size)
    crowd = jnp.concatenate([toks] * 5 + [toks[:, ::-1]] * 3)

    def per_seq(t):
        losses, stats = sequence_losses(params, cfg,
                                        {"tokens": t, "labels": t},
                                        aux_weight=cfg.balance_weight)
        return losses, stats

    alone, st1 = per_seq(toks)
    beside, st8 = per_seq(crowd)
    np.testing.assert_allclose(np.asarray(beside[:5]),
                               np.repeat(np.asarray(alone), 5), rtol=1e-5)
    assert int(st8["held_rows"]) > 5 * int(st1["held_rows"]) > 0


def test_coded_gradient_is_the_full_batch_gradient():
    """GC with held experts and the balance term on: the decoded
    gradient equals the full-batch gradient of the same loss."""
    cfg = SMOKE
    assert cfg.balance == "seq" and cfg.balance_weight > 0
    assert cfg.held < cfg.num_experts
    n, s = 4, 1
    code = make_gradient_code(n, s, prefer_rep=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = token_batch(0, 1, 8, 16, cfg.vocab_size)
    coded = gc_chunked_batch(batch, n, s)
    g_full = jax.grad(lambda p: loss_fn(
        p, cfg, batch, aux_weight=cfg.balance_weight))(params)
    coded_grad = jax.jit(jax.grad(make_coded_loss(cfg, n)))
    for survivors in ([0, 1, 2], [1, 2, 3], [0, 2, 3]):
        g = coded_grad(params, coded, gc_round_weights(code, survivors))
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


def test_program_loss_and_gradient_match_the_reference():
    cfg = SMOKE
    rc = ref_config(cfg)
    params = ref.init_weights(rc, 3)
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype),
                          init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == shapes
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 20), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def prog(p):
        return loss_fn(p, cfg, batch, aux_weight=cfg.balance_weight)

    def want(p):
        return ref.objective_sum(p, toks, rc) / (3 * 19)

    np.testing.assert_allclose(float(prog(params)), float(want(params)),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.grad(prog)(params)),
                    jax.tree.leaves(jax.grad(want)(params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_step_reports_routing_counts():
    """The coded step's metrics carry the expert layers' routing counts
    next to the loss; a model without experts reports the loss alone."""
    from repro.train.coded import init_train_state, make_coded_train_step

    n, s = 4, 1
    code = make_gradient_code(n, s, prefer_rep=False)
    w = gc_round_weights(code, [0, 1, 2])
    got = {}
    for cfg, keys in ((SMOKE, {"loss", "held_rows", "max_expert_rows"}),
                      (get_smoke("qwen2-0.5b"), {"loss"})):
        params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
        coded = gc_chunked_batch(token_batch(0, 1, 8, 16, cfg.vocab_size),
                                 n, s)
        _, _, metrics = jax.jit(make_coded_train_step(cfg, n, s))(
            params, opt, coded, w)
        assert set(metrics) == keys
        got[cfg.name] = metrics
    m = got[SMOKE.name]
    # every pass's 2 x 16 tokens, each layer: at most min(K, held) rows
    tokens = n * (s + 1) * 2 * 16
    layers = SMOKE.num_layers - SMOKE.first_k_dense
    assert 0 < int(m["max_expert_rows"]) <= tokens
    assert int(m["max_expert_rows"]) <= int(m["held_rows"]) \
        <= tokens * layers * min(SMOKE.num_experts_per_tok, SMOKE.held)


def test_unwritten_buffer_rows_are_never_read(monkeypatch):
    """The grouped matmul on the chip leaves the buffer's rows past the
    routed ones unwritten: filled with NaN here, the loss and every
    gradient are what they are with zeros there."""
    cfg = SMOKE.replace(held_experts=2, num_experts_per_tok=1)
    params = init_params(cfg, jax.random.PRNGKey(11))
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def loss_and_grad():
        return jax.value_and_grad(lambda p: loss_fn(
            p, cfg, batch, aux_weight=cfg.balance_weight))(params)

    want = loss_and_grad()
    real = jax.lax.ragged_dot

    def unwritten(lhs, rhs, group_sizes, *args, **kw):
        out = real(lhs, rhs, group_sizes, *args, **kw)
        used = jnp.arange(out.shape[0]) < group_sizes.sum()
        return jnp.where(used[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = loss_and_grad()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
