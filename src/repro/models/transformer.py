"""Model assembly for all six assigned families.

One parameter pytree + three entry points per model:

  * ``init_params(cfg, key)``      — stacked-layer pytree (scan-ready)
  * ``forward(params, cfg, ...)``  — full-sequence logits (train/prefill)
  * ``decode_step(params, cfg, cache, token, pos)`` — one-token serve
    step against a KV/state cache (``init_cache`` builds it)

Layer stacks are scanned (``lax.scan`` over stacked params) so the
lowered HLO stays O(1) in depth — essential for the 95-layer dry-runs.
The hybrid (zamba2-style) model nests the scan: outer scan over groups
of ``attn_every`` SSM layers, with one *shared* attention block (single
weight set) applied between groups.  A MoE stack with
``first_k_dense`` leading dense layers (DeepSeek-V2) scans those
(``dense_layers``) and then the expert layers (``layers``).

Families:
  dense  — GQA attention + SwiGLU, optional QKV bias / sliding window
  moe    — GQA or latent attention (MLA) + dropless top-k MoE FFN over
           the held experts (+ shared experts), optional leading dense
           layers
  ssm    — Mamba2/SSD blocks only (attention-free)
  hybrid — SSM stack + shared attention block every ``attn_every``
  vlm    — dense decoder consuming [patch-embeds | text tokens]
  audio  — non-causal encoder over precomputed frame embeddings
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import (
    attention_apply,
    attention_decode,
    attention_init,
    dense_init,
    mla_apply,
    mla_init,
    mlp_apply,
    mlp_init,
    moe_apply,
    moe_init,
    rmsnorm_apply,
    rmsnorm_init,
)

Params = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_block_init(key, cfg, dtype, *, experts=None):
    """One attention block; its FFN is the expert layer when
    ``experts`` (default: the family is moe), else the dense MLP."""
    if experts is None:
        experts = cfg.family == "moe"
    k1, k2, k3, k4 = jax.random.split(key, 4)
    attn_init = mla_init if cfg.is_mla else attention_init
    p = {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_init(k1, cfg, dtype),
        "norm2": rmsnorm_init(cfg.d_model, dtype),
    }
    if experts:
        p["moe"] = moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, dtype)
    return p


def _ssm_block_init(key, cfg, dtype):
    k1, _ = jax.random.split(key)
    return {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "ssm": ssm_mod.ssm_init(k1, cfg, dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = _dtype(cfg)
    k_embed, k_head, k_layers, k_shared = jax.random.split(key, 4)
    params: dict = {
        "embed": (
            jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)

    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.family in ("ssm", "hybrid"):
        params["layers"] = jax.vmap(
            lambda k: _ssm_block_init(k, cfg, dtype)
        )(layer_keys)
        if cfg.family == "hybrid":
            params["shared_attn"] = _attn_block_init(k_shared, cfg, dtype)
    else:
        dense = cfg.first_k_dense if cfg.family == "moe" else 0
        if dense:
            params["dense_layers"] = jax.vmap(
                lambda k: _attn_block_init(k, cfg, dtype, experts=False)
            )(layer_keys[:dense])
        params["layers"] = jax.vmap(
            lambda k: _attn_block_init(k, cfg, dtype)
        )(layer_keys[dense:])
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _remat(fn, cfg):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots
        )
    return jax.checkpoint(fn)


def _act_constraint(x, cfg):
    """FSDP / sequence-parallel pin: hidden states sharded on batch
    (and optionally sequence), feature dims replicated — forcing XLA to
    all-gather params per layer rather than psum activations.  No-op
    unless cfg.act_batch_axes / act_seq_axis is set."""
    if not cfg.act_batch_axes and not cfg.act_seq_axis:
        return x
    from jax.sharding import PartitionSpec as P

    batch = tuple(cfg.act_batch_axes) or None
    seq = cfg.act_seq_axis or None
    spec = P(batch, seq, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, spec)


def _attention(lp, x, cfg):
    h = rmsnorm_apply(lp["norm1"], x, use_pallas=cfg.use_pallas)
    if cfg.is_mla:
        return mla_apply(lp["attn"], h, cfg)
    return attention_apply(lp["attn"], h, cfg)[0]


def _attn_layer_body(x, lp, cfg):
    x = _act_constraint(x, cfg)
    x = x + _act_constraint(_attention(lp, x, cfg), cfg)
    hidden = rmsnorm_apply(lp["norm2"], x, use_pallas=cfg.use_pallas)
    with jax.named_scope("dense_mlp"):
        h = mlp_apply(lp["mlp"], hidden)
    return x + _act_constraint(h, cfg), jnp.zeros((), jnp.float32)


def _expert_layer_body(x, lp, cfg):
    """An expert layer: returns the balance term per sequence and the
    layer's routing counts (``held_rows``, ``max_expert_rows``)."""
    x = _act_constraint(x, cfg)
    x = x + _act_constraint(_attention(lp, x, cfg), cfg)
    hidden = rmsnorm_apply(lp["norm2"], x, use_pallas=cfg.use_pallas)
    h, aux, counts = moe_apply(lp["moe"], hidden, cfg)
    rows = counts.sum(0)
    stats = {"held_rows": rows.sum(), "max_expert_rows": rows.max()}
    return x + _act_constraint(h, cfg), (aux, stats)


def _ssm_layer_body(x, lp, cfg):
    x = _act_constraint(x, cfg)
    h = ssm_mod.ssm_apply(
        lp["ssm"], rmsnorm_apply(lp["norm1"], x, use_pallas=cfg.use_pallas),
        cfg,
    )
    return x + _act_constraint(h, cfg), jnp.zeros((), jnp.float32)


def _scan(cfg, body, init, xs):
    unroll = (
        jax.tree.leaves(xs)[0].shape[0] if cfg.scan_unroll else 1
    )
    return jax.lax.scan(body, init, xs, unroll=unroll)


def _stack_forward(params, cfg, x):
    """Run the layer stack; returns (hidden, aux, stats): the auxiliary
    loss summed over layers (per sequence, (b,), for a MoE stack) and
    the expert layers' routing counts (empty without experts)."""
    if cfg.family == "moe":
        return _moe_stack_forward(params, cfg, x)
    x, aux = _plain_stack_forward(params, cfg, x)
    return x, aux, {}


def _moe_stack_forward(params, cfg, x):
    if "dense_layers" in params:
        body = _remat(lambda h, lp: _attn_layer_body(h, lp, cfg), cfg)
        x, _ = _scan(cfg, body, x, params["dense_layers"])
    body = _remat(lambda h, lp: _expert_layer_body(h, lp, cfg), cfg)
    x, (aux, st) = _scan(cfg, body, x, params["layers"])
    stats = {"held_rows": st["held_rows"].sum(),
             "max_expert_rows": st["max_expert_rows"].max()}
    return x, aux.sum(0), stats


def _plain_stack_forward(params, cfg, x):
    if cfg.family in ("ssm", "hybrid"):
        body = _remat(lambda h, lp: _ssm_layer_body(h, lp, cfg), cfg)
        if cfg.family == "ssm" or not cfg.attn_every:
            x, aux = _scan(cfg, body, x, params["layers"])
            return x, aux.sum()
        # hybrid: groups of attn_every ssm layers + shared attn block
        k = cfg.attn_every
        G = cfg.num_layers // k
        grouped = jax.tree.map(
            lambda leaf: leaf.reshape(G, k, *leaf.shape[1:]), params["layers"]
        )
        shared = params["shared_attn"]
        attn_body = _remat(
            lambda h, lp: _attn_layer_body(h, lp, cfg), cfg
        )

        def group_body(h, gp):
            h, aux = _scan(cfg, body, h, gp)
            h, aux2 = attn_body(h, shared)
            return h, aux.sum() + aux2

        x, aux = _scan(cfg, group_body, x, grouped)
        return x, aux.sum()

    body = _remat(lambda h, lp: _attn_layer_body(h, lp, cfg), cfg)
    x, aux = _scan(cfg, body, x, params["layers"])
    return x, aux.sum()


def embed_inputs(params, cfg, batch) -> jax.Array:
    """Builds the (b, s, d) input sequence from the batch dict.

    dense/moe/ssm/hybrid: batch["tokens"] (b, s)
    vlm:   concat(batch["prefix_embeds"] (b, P, d), embed(tokens))
    audio: batch["frames"] (b, s, d) — stub frontend output
    """
    if cfg.frontend == "audio_stub":
        return batch["frames"].astype(_dtype(cfg))
    tok_embeds = params["embed"][batch["tokens"]]
    if cfg.frontend == "vision_stub":
        prefix = batch["prefix_embeds"].astype(tok_embeds.dtype)
        return jnp.concatenate([prefix, tok_embeds], axis=1)
    return tok_embeds


def _forward(params, cfg: ModelConfig, batch):
    """(logits, aux as :func:`_stack_forward` gives it, routing counts)."""
    x = embed_inputs(params, cfg, batch)
    with jax.named_scope("layers"):
        x, aux, stats = _stack_forward(params, cfg, x)
    with jax.named_scope("head"):
        x = rmsnorm_apply(params["final_norm"], x,
                          use_pallas=cfg.use_pallas)
        head = (
            params["embed"].T if cfg.tie_embeddings else params["head"]
        )
        logits = x @ head
    return logits, aux, stats


def _mean_aux(aux):
    """A MoE stack's per-sequence term averaged over the sequences."""
    return aux if aux.ndim == 0 else aux.mean()


def forward(params, cfg: ModelConfig, batch) -> tuple[jax.Array, jax.Array]:
    """Full-sequence logits. Returns (logits (b, s, vocab), aux_loss)."""
    logits, aux, _ = _forward(params, cfg, batch)
    return logits, _mean_aux(aux)


def _token_nll(logits, cfg: ModelConfig, batch):
    """Per-token negative log-likelihood (b, s')."""
    labels = batch["labels"]
    with jax.named_scope("head"):
        if cfg.causal:
            logits = logits[:, :-1]
            labels = labels[:, 1:]
        if cfg.frontend == "vision_stub":
            # labels cover only the text suffix
            logits = logits[:, -labels.shape[1]:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_fn(params, cfg: ModelConfig, batch, *, aux_weight: float = 0.01):
    """Mean CE (next-token for causal LMs, per-frame for encoders) plus
    ``aux_weight`` times the auxiliary loss."""
    logits, aux, _ = _forward(params, cfg, batch)
    nll = _token_nll(logits, cfg, batch)
    with jax.named_scope("head"):
        loss = nll.mean()
    return loss + aux_weight * _mean_aux(aux)


def sequence_losses(params, cfg: ModelConfig, batch, *, aux_weight: float):
    """Each sequence's mean CE plus ``aux_weight`` times its balance
    term, (b,), and the routing counts.  For a MoE stack every term is
    one sequence's own, so a batch of sequences gives what each would
    give alone."""
    logits, aux, stats = _forward(params, cfg, batch)
    nll = _token_nll(logits, cfg, batch)
    with jax.named_scope("head"):
        loss = nll.mean(-1)
    return loss + aux_weight * aux, stats


# ---------------------------------------------------------------------------
# serving: cache init / decode step
# ---------------------------------------------------------------------------


def _cached_decode(cfg: ModelConfig) -> None:
    if cfg.is_mla or cfg.first_k_dense:
        raise NotImplementedError(
            f"{cfg.name}: latent attention and leading dense layers have "
            "no cached decode path")


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int, dtype=None):
    """KV / SSM-state cache pytree (stacked on a leading layer axis)."""
    _cached_decode(cfg)
    dtype = dtype or _dtype(cfg)
    L, dh = cfg.num_layers, cfg.head_dim_
    hkv = cfg.num_kv_heads
    if cfg.family in ("ssm", "hybrid"):
        conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_state
        cache = {
            "state": jnp.zeros(
                (L, batch_size, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32,
            ),
            "conv": jnp.zeros((L, batch_size, 3, conv_dim), dtype),
        }
        if cfg.family == "hybrid" and cfg.attn_every:
            G = cfg.num_layers // cfg.attn_every
            cache["shared_k"] = jnp.zeros((G, batch_size, hkv, max_seq, dh), dtype)
            cache["shared_v"] = jnp.zeros((G, batch_size, hkv, max_seq, dh), dtype)
        return cache
    return {
        "k": jnp.zeros((L, batch_size, hkv, max_seq, dh), dtype),
        "v": jnp.zeros((L, batch_size, hkv, max_seq, dh), dtype),
    }


def _attn_decode_body(lp, cfg, x, k_cache, v_cache, pos):
    h = rmsnorm_apply(lp["norm1"], x, use_pallas=cfg.use_pallas)
    h, k_cache, v_cache = attention_decode(
        lp["attn"], h, k_cache, v_cache, pos, cfg
    )
    x = x + h
    hidden = rmsnorm_apply(lp["norm2"], x, use_pallas=cfg.use_pallas)
    if cfg.family == "moe":
        h = moe_apply(lp["moe"], hidden, cfg)[0]
    else:
        h = mlp_apply(lp["mlp"], hidden)
    return x + h, k_cache, v_cache


def decode_step(params, cfg: ModelConfig, cache, token, pos):
    """One serve step: token (b, 1) int32, pos scalar int32.

    Returns (logits (b, vocab), new_cache).
    """
    _cached_decode(cfg)
    x = params["embed"][token]
    if cfg.family in ("ssm", "hybrid"):
        x, cache = _decode_ssm_stack(params, cfg, cache, x, pos)
    else:
        def body(h, xs):
            lp, kc, vc = xs
            h, kc, vc = _attn_decode_body(lp, cfg, h, kc, vc, pos)
            return h, (kc, vc)

        x, (ks, vs) = _scan(
            cfg, body, x, (params["layers"], cache["k"], cache["v"])
        )
        cache = {"k": ks, "v": vs}
    x = rmsnorm_apply(params["final_norm"], x, use_pallas=cfg.use_pallas)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head)[:, 0]
    return logits, cache


def prefill(params, cfg: ModelConfig, batch, max_seq: int):
    """Process a prompt batch and build the decode cache (serving path).

    Returns (logits (b, s, vocab), cache) with the cache padded to
    ``max_seq`` positions, ready for ``decode_step`` at pos = s.
    """
    _cached_decode(cfg)
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    pad = max_seq - s

    if cfg.family in ("ssm", "hybrid"):
        x, cache = _prefill_ssm_stack(params, cfg, x, max_seq)
    else:
        def body(h, lp):
            h = _act_constraint(h, cfg)
            a_in = rmsnorm_apply(lp["norm1"], h, use_pallas=cfg.use_pallas)
            attn_out, (k, v) = attention_apply(lp["attn"], a_in, cfg)
            h = h + attn_out
            hidden = rmsnorm_apply(lp["norm2"], h, use_pallas=cfg.use_pallas)
            if cfg.family == "moe":
                m = moe_apply(lp["moe"], hidden, cfg)[0]
            else:
                m = mlp_apply(lp["mlp"], hidden)
            kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            return h + m, (kp, vp)

        x, (ks, vs) = _scan(cfg, _remat(body, cfg), x, params["layers"])
        cache = {"k": ks, "v": vs}

    x = rmsnorm_apply(params["final_norm"], x, use_pallas=cfg.use_pallas)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ head, cache


def _prefill_ssm_stack(params, cfg, x, max_seq):
    def ssm_body(h, lp):
        hin = rmsnorm_apply(lp["norm1"], h, use_pallas=cfg.use_pallas)
        y, state, conv = ssm_mod.ssm_apply(
            lp["ssm"], hin, cfg, return_cache=True
        )
        return h + y, (state, conv)

    if cfg.family == "ssm" or not cfg.attn_every:
        x, (states, convs) = _scan(
            cfg, _remat(ssm_body, cfg), x, params["layers"]
        )
        return x, {"state": states, "conv": convs}

    k_every = cfg.attn_every
    G = cfg.num_layers // k_every
    grouped = jax.tree.map(
        lambda leaf: leaf.reshape(G, k_every, *leaf.shape[1:]),
        params["layers"],
    )
    shared = params["shared_attn"]
    pad = max_seq - x.shape[1]

    def group_body(h, gp):
        h, (st, cv) = _scan(cfg, _remat(ssm_body, cfg), h, gp)
        a_in = rmsnorm_apply(shared["norm1"], h, use_pallas=cfg.use_pallas)
        attn_out, (k, v) = attention_apply(shared["attn"], a_in, cfg)
        h = h + attn_out
        hid = rmsnorm_apply(shared["norm2"], h, use_pallas=cfg.use_pallas)
        h = h + mlp_apply(shared["mlp"], hid)
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return h, (st, cv, kp, vp)

    x, (st, cv, kc, vc) = _scan(cfg, group_body, x, grouped)
    cache = {
        "state": st.reshape(cfg.num_layers, *st.shape[2:]),
        "conv": cv.reshape(cfg.num_layers, *cv.shape[2:]),
        "shared_k": kc,
        "shared_v": vc,
    }
    return x, cache


def generate(params, cfg: ModelConfig, batch, *, num_tokens: int,
             max_seq: int | None = None):
    """Greedy generation: prefill the prompt, then decode step-by-step.

    batch: {"tokens": (b, s)} prompt.  Returns (b, num_tokens) int32.
    """
    prompt = batch["tokens"]
    b, s = prompt.shape
    max_seq = max_seq or (s + num_tokens)
    logits, cache = prefill(params, cfg, batch, max_seq)
    token = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [token]
    for i in range(num_tokens - 1):
        logits, cache = decode_step(params, cfg, cache, token, jnp.int32(s + i))
        token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(token)
    return jnp.concatenate(out, axis=1)


def _decode_ssm_stack(params, cfg, cache, x, pos):
    def ssm_body(h, xs):
        lp, state, conv = xs
        hin = rmsnorm_apply(lp["norm1"], h, use_pallas=cfg.use_pallas)
        y, state, conv = ssm_mod.ssm_decode_step(lp["ssm"], hin, state, conv, cfg)
        return h + y, (state, conv)

    if cfg.family == "ssm" or not cfg.attn_every:
        x, (states, convs) = _scan(
            cfg, ssm_body, x, (params["layers"], cache["state"], cache["conv"])
        )
        return x, {"state": states, "conv": convs}

    k = cfg.attn_every
    G = cfg.num_layers // k
    grouped = jax.tree.map(
        lambda leaf: leaf.reshape(G, k, *leaf.shape[1:]), params["layers"]
    )
    g_state = cache["state"].reshape(G, k, *cache["state"].shape[1:])
    g_conv = cache["conv"].reshape(G, k, *cache["conv"].shape[1:])
    shared = params["shared_attn"]

    def group_body(h, xs):
        gp, st, cv, kc, vc = xs
        h, (st, cv) = _scan(cfg, ssm_body, h, (gp, st, cv))
        hin = rmsnorm_apply(shared["norm1"], h, use_pallas=cfg.use_pallas)
        y, kc, vc = attention_decode(shared["attn"], hin, kc, vc, pos, cfg)
        h = h + y
        hid = rmsnorm_apply(shared["norm2"], h, use_pallas=cfg.use_pallas)
        h = h + mlp_apply(shared["mlp"], hid)
        return h, (st, cv, kc, vc)

    x, (st, cv, kc, vc) = _scan(
        cfg, group_body, x,
        (grouped, g_state, g_conv, cache["shared_k"], cache["shared_v"]),
    )
    new_cache = {
        "state": st.reshape(cfg.num_layers, *st.shape[2:]),
        "conv": cv.reshape(cfg.num_layers, *cv.shape[2:]),
        "shared_k": kc,
        "shared_v": vc,
    }
    return x, new_cache
