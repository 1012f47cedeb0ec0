"""Shared transformer layers: RMSNorm, RoPE (plain and YaRN), GQA
attention (train / prefill / cached decode), latent attention (MLA),
SwiGLU MLP, and a dropless expert layer that holds a share of the
experts.

Every layer is a pair (init_fn, apply_fn) operating on plain pytrees —
no framework dependency, shard_map/pjit friendly.  ``use_pallas``
selects the Pallas TPU kernels; the default jnp path lowers on any
backend (CPU dry-run included) and is itself flash-style (chunked,
online softmax) so compile-time memory stays bounded at 32k+ sequence
lengths.
"""

from __future__ import annotations

import functools
import math
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


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(dim: int, cfg) -> tuple[int, int]:
    """The rotary pairs between which YaRN blends interpolated and
    original frequencies (DeepSeek-V2's ``yarn_find_correction_range``):
    pairs below ``low`` keep their frequency, pairs above ``high`` are
    divided by the factor."""

    def pair(rotations):
        return (dim * math.log(cfg.rope_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(pair(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair(cfg.yarn_beta_slow)), dim - 1)
    return low, high


def yarn_frequencies(dim: int, cfg) -> jax.Array:
    """YaRN inverse frequencies: ``(f/factor) * ramp + f * (1 - ramp)``
    with ``ramp`` rising linearly from 0 at ``low`` to 1 at ``high``."""
    freqs = rope_frequencies(dim, cfg.rope_theta)
    low, high = yarn_correction_range(dim, cfg)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / cfg.rope_factor * ramp + freqs * (1.0 - ramp)


def yarn_cos_sin_scale(cfg) -> float:
    """The factor on YaRN's cos and sin (1 when both mscales agree)."""
    return (_yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
            / _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))


def mla_softmax_scale(cfg) -> float:
    """``qk_head_dim**-0.5``, times YaRN's ``mscale_all_dim`` squared."""
    scale = cfg.qk_head_dim ** -0.5
    if cfg.rope_factor and cfg.yarn_mscale_all_dim:
        scale *= _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               freqs: jax.Array | None = None,
               mscale: float = 1.0) -> jax.Array:
    """x: (b, h, s, dh); positions: (b, s) or (s,).  ``freqs`` replaces
    the plain inverse frequencies (YaRN); ``mscale`` scales cos and
    sin."""
    dh = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(dh, theta)                 # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, dh/2)
    cos = jnp.cos(angles)[:, None, :, :]
    sin = jnp.sin(angles)[:, None, :, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- chunked (flash-style) jnp attention --------------------------------------


def _chunked_attention(q, k, v, *, causal, window, block_k=1024,
                       scale=None):
    """Online-softmax attention via lax.scan over KV blocks.

    Pure-jnp twin of the Pallas kernel: O(seq) memory, lowers on every
    backend, differentiable.  q: (b,hq,sq,dh); k: (b,hkv,sk,dh); v:
    (b,hkv,sk,dv).  ``scale`` defaults to ``dh**-0.5``.  Up to one block
    of keys the dense core runs: a scan of two blocks would stack each
    block's float32 scores for the backward pass, which costs more than
    the dense scores it saves.
    """
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    dv = v.shape[-1]
    group = hq // hkv
    if sk <= block_k:
        return fa_ref.attention(q, k, v, causal=causal, window=window,
                                scale=scale)
    if scale is None:
        scale = dh ** -0.5
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = k.shape[2] // block_k
    kb = k.reshape(b, hkv, nk, block_k, dh).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nk, block_k, dv).transpose(2, 0, 1, 3, 4)

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
        jnp.zeros((b, hq, sq, dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(nk), kb, vb)
    )
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


def multihead_attention(
    q, k, v, *, causal: bool, window: int = 0, use_pallas: bool = False,
    interpret: bool | None = None, scale: float | None = None,
):
    """``interpret=None`` runs the Pallas kernel natively when lowered
    for a TPU and interpreted elsewhere (``kernels.native_on_tpu``).
    The Pallas kernel takes one head size and the default scale."""
    if use_pallas:
        if scale is not None or v.shape[-1] != q.shape[-1]:
            raise NotImplementedError(
                "the Pallas attention kernel takes one head size and the "
                "default scale")
        call = functools.partial(
            fa_ops.attention, causal=causal, window=window
        )
        if interpret is None:
            return native_on_tpu(call, q, k, v)
        return call(q, k, v, interpret=interpret)
    return _chunked_attention(q, k, v, causal=causal, window=window,
                              scale=scale)


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


# -- latent attention (MLA) ----------------------------------------------------


def mla_init(key, cfg, dtype):
    """DeepSeek-V2 attention without a query LoRA: ``wq`` (d, h*(dn+dr)),
    ``wkv_a`` (d, r+dr) to the latent and the shared rope key, its norm,
    ``wkv_b`` (r, h*(dn+dv)) to each head's nope key and value, ``wo``."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * (dn + dr), dtype),
        "wkv_a": dense_init(ks[1], d, r + dr, dtype),
        "kv_norm": rmsnorm_init(r, dtype),
        "wkv_b": dense_init(ks[2], r, h * (dn + dv), dtype),
        "wo": dense_init(ks[3], h * dv, d, dtype),
    }


def mla_apply(p, x, cfg, *, positions=None):
    """x: (b, s, d).  Each head's query is ``[q_nope | q_pe]`` and its key
    ``[k_nope | k_pe]``, with one rope key ``k_pe`` per token shared by
    all heads; rope (YaRN when ``cfg.rope_factor``) acts on the ``_pe``
    parts only."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(s)
    with jax.named_scope("mla"):
        q = (x @ p["wq"]).reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
        kv_a = x @ p["wkv_a"]
        c_kv = rmsnorm_apply(p["kv_norm"], kv_a[..., :r],
                             use_pallas=cfg.use_pallas)
        k_pe = kv_a[..., None, r:].transpose(0, 2, 1, 3)     # (b, 1, s, dr)
        kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, dn + dv)
        kv = kv.transpose(0, 2, 1, 3)
        rope = {}
        if cfg.rope_factor:
            rope = dict(freqs=yarn_frequencies(dr, cfg),
                        mscale=yarn_cos_sin_scale(cfg))
        q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta, **rope)
        k_pe = apply_rope(k_pe, positions, cfg.rope_theta, **rope)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, h, s, dr))], axis=-1)
        out = multihead_attention(
            q, k, kv[..., dn:], causal=cfg.causal,
            window=cfg.sliding_window, use_pallas=cfg.use_pallas,
            scale=mla_softmax_scale(cfg),
        )
        return out.transpose(0, 2, 1, 3).reshape(b, s, h * dv) @ p["wo"]


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
    """The router over all ``num_experts``; the weights of the held
    experts only (``cfg.held``); the shared experts as one SwiGLU."""
    d, e_ff, E = cfg.d_model, cfg.expert_d_ff, cfg.held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, cfg.num_experts, dtype),
        "w_gate": (jax.random.normal(ks[1], (E, d, e_ff)) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, e_ff)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, e_ff, d)) * e_ff ** -0.5).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(
            ks[4], d, cfg.num_shared_experts * e_ff, dtype
        )
    return p


# Moving rows between tokens and the expert-sorted buffer.  Both
# directions are masked gathers, forward and backward: the grouped matmul
# leaves the buffer's rows past the routed ones unwritten, so nothing may
# read them, and a gather's default transpose (a scatter-add) is slow on
# the chip.  ``take``: row -> (token, k) pair; ``slot``: pair -> row
# (clamped); ``held``: the pair's expert is held here; ``routed``: the row
# holds a pair.


def _masked_take(x, idx, ok):
    return jnp.where(ok[:, None], x[idx], jnp.zeros((), x.dtype))


@jax.custom_vjp
def _dispatch(xt, take, slot, held, routed):
    """Token rows (N, d) -> buffer rows (rows, d)."""
    return _masked_take(xt, take // (held.shape[0] // xt.shape[0]), routed)


def _dispatch_fwd(xt, take, slot, held, routed):
    return _dispatch(xt, take, slot, held, routed), (slot, held, xt.shape)


def _dispatch_bwd(res, g):
    slot, held, (n, d) = res
    dx = _masked_take(g, slot, held).reshape(n, -1, d).sum(1)
    return dx, None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, take, slot, held, routed):
    """Buffer rows (rows, d) -> pair rows (N*K, d), zero where the pair's
    expert is held elsewhere."""
    return _masked_take(ys, slot, held)


def _combine_fwd(ys, take, slot, held, routed):
    return _combine(ys, take, slot, held, routed), (take, routed)


def _combine_bwd(res, g):
    take, routed = res
    return _masked_take(g, take, routed), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_apply(p, x, cfg):
    """Dropless top-k token-choice MoE over the held experts.

    x: (b, s, d) -> (y (b, s, d), balance term per sequence (b,), rows
    per sequence and held expert (b, held) int32).

    The router scores all ``num_experts`` in float32 and each token
    takes its top ``K`` (renormalised when ``cfg.norm_topk_prob``).
    Every (token, held expert) selection is computed: the selections
    are sorted by held expert into a static buffer of ``tokens *
    min(K, held)`` rows (a token picks an expert at most once, so
    nothing is dropped) and the three expert matmuls are grouped
    matmuls over it (``lax.ragged_dot``), which compute the routed rows
    only.  Selections of experts held elsewhere add nothing here; the
    shared experts add for every token.  A token's output depends on
    that token alone.

    The balance term is ``"seq"`` (DeepSeek-V2: per sequence of length
    T, ``sum_i (E/(K T)) #{t: i in topk(t)} * mean_t p_{t,i}``) or
    ``"switch"`` (the call's ``E * sum_i mean p_i * mean f_i / K``,
    repeated per sequence).
    """
    b, s, d = x.shape
    E, K, H = cfg.num_experts, cfg.num_experts_per_tok, cfg.held
    N = b * s
    rows = N * min(K, H)
    xt = x.reshape(N, d)
    with jax.named_scope("experts"):
        with jax.named_scope("router"):
            logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)               # (N, E)
            gate, idx = jax.lax.top_k(probs, K)                   # (N, K)
            if cfg.norm_topk_prob:
                gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
            chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)
            if cfg.balance == "seq":
                f = chosen.reshape(b, s, E).sum(1) * (E / (K * s))
                aux = (f * probs.reshape(b, s, E).mean(1)).sum(-1)
            else:
                aux = E * jnp.sum(probs.mean(0) * chosen.mean(0)) / K
                aux = jnp.broadcast_to(aux, (b,))
        with jax.named_scope("dispatch"):
            local = idx - cfg.held_expert_start
            held = (local >= 0) & (local < H)
            group = jnp.where(held, local, H).reshape(-1)         # (N*K,)
            order = jnp.argsort(group, stable=True)
            take = order[:rows]                                   # row -> pair
            slot = jnp.zeros_like(order).at[order].set(
                jnp.arange(N * K, dtype=order.dtype))             # pair -> row
            slot = jnp.minimum(slot, rows - 1)
            counts = jax.nn.one_hot(group.reshape(b, s * K), H + 1,
                                    dtype=jnp.int32).sum(1)[:, :H]
            sizes = counts.sum(0)
            held = held.reshape(-1)
            routed = jnp.arange(rows) < sizes.sum()               # row is used
            xs = _dispatch(xt, take, slot, held, routed)
        with jax.named_scope("gmm"):
            h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes))
            h = h * jax.lax.ragged_dot(xs, p["w_up"], sizes)
            ys = jax.lax.ragged_dot(h, p["w_down"], sizes)
        with jax.named_scope("combine"):
            picked = _combine(ys, take, slot, held, routed).reshape(N, K, d)
            w = jnp.where(held.reshape(N, K), gate, 0.0).astype(ys.dtype)
            y = jnp.einsum("nkd,nk->nd", picked, w,
                           preferred_element_type=jnp.float32)
            y = y.astype(x.dtype)
    if cfg.num_shared_experts:
        with jax.named_scope("shared_expert"):
            y = y + mlp_apply(p["shared"], xt)
    return y.reshape(b, s, d), aux, counts
