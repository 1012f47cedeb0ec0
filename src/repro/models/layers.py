"""Shared transformer layers: RMSNorm, RoPE, GQA attention (train /
prefill / cached decode), SwiGLU MLP, MoE.

Every layer is a pair (init_fn, apply_fn) operating on plain pytrees —
no framework dependency, shard_map/pjit friendly.  ``use_pallas``
selects the Pallas TPU kernels; the default jnp path lowers on any
backend (CPU dry-run included) and is itself flash-style (chunked,
online softmax) so compile-time memory stays bounded at 32k+ sequence
lengths.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import native_on_tpu
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops
from repro.kernels.rmsnorm import ref as rn_ref

Params = Any
NEG_INF = -1e30


# -- init helpers -------------------------------------------------------------


def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# -- RMSNorm ------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"gamma": jnp.ones((d,), dtype)}

def rmsnorm_apply(p, x, *, use_pallas=False, eps=1e-6):
    if use_pallas:
        return rn_ops.rmsnorm(x, p["gamma"], eps=eps)
    return rn_ref.rmsnorm(x, p["gamma"], eps=eps)


# -- RoPE ---------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, h, s, dh); positions: (b, s) or (s,)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                     # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, dh/2)
    cos = jnp.cos(angles)[:, None, :, :]
    sin = jnp.sin(angles)[:, None, :, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- chunked (flash-style) jnp attention --------------------------------------


def _chunked_attention(q, k, v, *, causal, window, block_k=512):
    """Online-softmax attention via lax.scan over KV blocks.

    Pure-jnp twin of the Pallas kernel: O(seq) memory, lowers on every
    backend, differentiable.  q: (b,hq,sq,dh); k,v: (b,hkv,sk,dh).
    """
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = dh ** -0.5
    if sk <= block_k:
        return fa_ref.attention(q, k, v, causal=causal, window=window)
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = k.shape[2] // block_k
    kb = k.reshape(b, hkv, nk, block_k, dh).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nk, block_k, dh).transpose(2, 0, 1, 3, 4)

    q32 = q.astype(jnp.float32)
    q_pos = jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        ki, kblk, vblk = xs
        kx = jnp.repeat(kblk, group, axis=1).astype(jnp.float32)
        vx = jnp.repeat(vblk, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kx) * scale
        k_pos = ki * block_k + jnp.arange(block_k)
        mask = (k_pos[None, :] < sk)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vx)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, hq, sq, 1), NEG_INF, jnp.float32),
        jnp.zeros((b, hq, sq, 1), jnp.float32),
        jnp.zeros((b, hq, sq, dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(nk), kb, vb)
    )
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


def multihead_attention(
    q, k, v, *, causal: bool, window: int = 0, use_pallas: bool = False,
    interpret: bool | None = None,
):
    """``interpret=None`` runs the Pallas kernel natively when lowered
    for a TPU and interpreted elsewhere (``kernels.native_on_tpu``)."""
    if use_pallas:
        call = functools.partial(
            fa_ops.attention, causal=causal, window=window
        )
        if interpret is None:
            return native_on_tpu(call, q, k, v)
        return call(q, k, v, interpret=interpret)
    return _chunked_attention(q, k, v, causal=causal, window=window)


# -- GQA attention block -------------------------------------------------------


def attention_init(key, cfg, dtype):
    d, dh = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], hq * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def _project_qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    dh = cfg.head_dim_
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.num_kv_heads, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.num_kv_heads, dh).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(p, x, cfg, *, positions=None):
    """Training / prefill path. x: (b, s, d)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = multihead_attention(
        q, k, v, causal=cfg.causal, window=cfg.sliding_window,
        use_pallas=cfg.use_pallas,
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return out @ p["wo"], (k, v)


def attention_decode(p, x, cache_k, cache_v, pos, cfg):
    """Single-token decode against a KV cache.

    x: (b, 1, d); cache_k/v: (b, hkv, S, dh); pos: scalar int32 —
    current position (tokens < pos are valid).
    Returns (out, new_k, new_v).
    """
    b = x.shape[0]
    dh = cfg.head_dim_
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), pos, axis=2
    )
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), pos, axis=2
    )
    # GQA without materializing the repeat: fold the q heads into
    # (kv_head, group) and contract against the cache directly.  This
    # keeps the (sharded) cache untouched — materializing
    # repeat(cache, group) forces XLA to all-gather the whole cache per
    # layer (2 x 1 GiB/layer for mixtral decode; see §Perf).
    group = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, cfg.num_kv_heads, group, dh)
    # contract in the cache dtype with f32 accumulation — casting the
    # whole (huge) cache to f32 doubles its HBM read traffic (§Perf).
    s = jnp.einsum(
        "bkgd,bksd->bkgs", qg.astype(cache_k.dtype), cache_k,
        preferred_element_type=jnp.float32,
    ) * (dh ** -0.5)
    k_pos = jnp.arange(cache_k.shape[2])
    valid = k_pos <= pos
    if cfg.sliding_window > 0:
        valid &= (pos - k_pos) < cfg.sliding_window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pvals = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgs,bksd->bkgd", pvals.astype(cache_v.dtype), cache_v,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    out = out.reshape(b, 1, cfg.num_heads * dh)
    return out @ p["wo"], cache_k, cache_v


# -- SwiGLU MLP ---------------------------------------------------------------


def mlp_init(key, d, d_ff, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, d_ff, dtype),
        "w_up": dense_init(ks[1], d, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d, dtype),
    }


def mlp_apply(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# -- Mixture of Experts --------------------------------------------------------


def moe_init(key, cfg, dtype):
    d, e_ff, E = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, dtype),
        "w_gate": (jax.random.normal(ks[1], (E, d, e_ff)) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, e_ff)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, e_ff, d)) * e_ff ** -0.5).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(
            ks[4], d, cfg.num_shared_experts * e_ff, dtype
        )
    return p


def moe_apply(p, x, cfg, *, capacity_factor: float = 1.25,
              group_size: int = 1024):
    """Top-k token-choice MoE with grouped capacity dispatch.

    x: (b, s, d) -> ((b, s, d), aux load-balance loss).

    Tokens are split into groups of ``group_size`` and each group gets a
    private capacity ``Cg = cf * group_size * K / E`` (the flax/MaxText
    "dropping" formulation).  The largest intermediates are the
    (G, Tg, E, Cg) dispatch/combine one-hots; with Tg=1024 their FLOP
    and byte costs stay <10% of the expert FFN compute for all assigned
    MoE configs.  Sharding the expert axis of the weights over "model"
    and the group axis over "data" yields expert parallelism with XLA
    inserting the all-to-alls.
    """
    b, s, d = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    T = b * s
    Tg = min(group_size, T)
    while T % Tg:
        Tg //= 2
    G = T // Tg
    # small groups (decode steps, smoke configs) run dropless so the
    # cached-decode path reproduces the full forward exactly; large
    # training groups use the standard capacity-factor dropping.
    if Tg <= 256:
        Cg = Tg
    else:
        Cg = max(int(capacity_factor * Tg * K / E), 1)

    xt = x.reshape(G, Tg, d)
    logits = (xt @ p["router"]).astype(jnp.float32)           # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)             # (G, Tg, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # a token picks each expert at most once -> fold K into the E axis
    onehot_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(2)  # (G,Tg,E)
    gate_e = jnp.einsum(
        "gtk,gtke->gte",
        gate_vals,
        jax.nn.one_hot(gate_idx, E, dtype=jnp.float32),
    )
    pos_in_e = jnp.cumsum(onehot_e, axis=1) - 1.0             # (G, Tg, E)
    within = (pos_in_e < Cg) & (onehot_e > 0)
    dispatch = jax.nn.one_hot(
        pos_in_e.astype(jnp.int32), Cg, dtype=x.dtype
    ) * within[..., None].astype(x.dtype)                      # (G,Tg,E,Cg)
    combine = dispatch * gate_e[..., None].astype(x.dtype)

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xt)           # (G,E,Cg,d)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    out_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out = jnp.einsum("gtec,gecd->gtd", combine, out_e)

    if cfg.num_shared_experts:
        out = out + mlp_apply(p["shared"], xt)

    # Switch-style load-balance aux loss
    me = probs.mean(axis=(0, 1))
    ce = onehot_e.mean(axis=(0, 1))
    aux = E * jnp.sum(me * ce) / K
    return out.reshape(b, s, d), aux
