"""Plain reference of DeepSeek-V2 training steps, in float32.

Follows DeepSeek-V2 (arXiv:2405.04434) and the DeepSeek-V2-Lite
``config.json``: token embedding; per layer RMSNorm, multi-head latent
attention, RMSNorm, then a SwiGLU MLP (the first ``first_k_dense_replace``
layers) or the expert layer, each with a residual; final RMSNorm and an
untied head; next-token cross entropy, mean over tokens, plus
``aux_loss_alpha`` times the per-sequence balance term (``seq_aux``),
averaged over the sequences and summed over the expert layers.  AdamW
updates in float32 and stores parameters in the configuration's
bfloat16.  Imports nothing of the program.

- Latent attention, no query LoRA: ``q = h Wq`` per head ``[q_nope |
  q_pe]``; ``[c_kv | k_pe] = h Wkv_a``, ``c_kv`` RMS-normalised;
  ``[k_nope | v]`` per head ``= c_kv Wkv_b``; rope on ``q_pe`` and on the
  one ``k_pe`` every head shares; causal softmax scaled by
  ``(dn+dr)**-0.5 * m**2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  output projection.  No biases.
- YaRN frequencies: ``f_i = theta**(-2i/dr)``, blended as ``(f_i/factor)
  ramp_i + f_i (1 - ramp_i)`` with ``ramp`` linear between the
  correction pairs found from ``beta_fast``, ``beta_slow`` and the
  original length; cos and sin scaled by ``m(mscale)/m(mscale_all_dim)``.
- Expert layer: router softmax over all ``router_experts`` in float32,
  greedy top ``num_experts_per_tok`` with the softmax values as weights
  (renormalised only if ``norm_topk_prob``, times
  ``routed_scaling_factor``); output ``sum over selected held experts of
  weight * SwiGLU_i(h)`` plus the shared SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``.  Here each held expert
  is applied densely to every token and masked, which is plain, not
  fast.

Departures from the published model, the same as the program's:

- rotary embedding by halves of the rope dims, not the checkpoint's
  interleaved pairs (with random weights, a permutation of columns);
- dropless routing: every selection of a held expert is computed, not
  the paper's device-level token dropping in training, because a coded
  chunk's gradient must not depend on the chunks routed beside it;
- one chip's share of an expert-parallel group: only the held experts
  ``held_expert_start .. + n_routed_experts`` add their part (the
  router still scores all ``router_experts``), and the vocabulary is a
  slice of ``vocab_size`` rows.

``quant`` as in :mod:`bench.refs.qwen2`: ``None`` is the reference,
``"fp8"`` rounds every matmul operand to float8 e4m3 (the control).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.refs.qwen2 import (  # noqa: F401  (read by bench/drivers/train.py)
    HIGHEST,
    _adamw,
    _quantizer,
    _rms,
    leaf_norms,
    tokens,
    worst_gap,
)


def dims(cfg: dict) -> dict:
    return dict(
        L=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], eff=cfg["moe_intermediate_size"],
        E=cfg["router_experts"], held=cfg["n_routed_experts"],
        K=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        V=cfg["vocab_size"])


def weight_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    d, H, r, dn, dr, dv = (g[k] for k in "d H r dn dr dv".split())

    def attn(n):
        return {"wq": (n, d, H * (dn + dr)), "wkv_a": (n, d, r + dr),
                "kv_norm": {"gamma": (n, r)},
                "wkv_b": (n, r, H * (dn + dv)), "wo": (n, H * dv, d)}

    def swiglu(n, ff):
        return {"w_gate": (n, d, ff), "w_up": (n, d, ff),
                "w_down": (n, ff, d)}

    dense, moe = g["dense"], g["L"] - g["dense"]
    eff, held = g["eff"], g["held"]
    return {
        "embed": (g["V"], d),
        "final_norm": {"gamma": (d,)},
        "head": (d, g["V"]),
        "dense_layers": {"norm1": {"gamma": (dense, d)}, "attn": attn(dense),
                         "norm2": {"gamma": (dense, d)},
                         "mlp": swiglu(dense, g["ff"])},
        "layers": {"norm1": {"gamma": (moe, d)}, "attn": attn(moe),
                   "norm2": {"gamma": (moe, d)},
                   "moe": {"router": (moe, d, g["E"]),
                           "w_gate": (moe, held, d, eff),
                           "w_up": (moe, held, d, eff),
                           "w_down": (moe, held, eff, d),
                           "shared": swiglu(moe, g["shared"] * eff)}},
    }


def init_weights(cfg: dict, seed: int):
    """Random weights from ``seed`` in the configuration's dtype, made
    on the device in one jitted call: std 0.02 for the embedding,
    ``fan_in**-0.5`` for matrices, ones for norm scales."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if name == "gamma":
                leaf = jnp.ones(shape, jnp.float32)
            elif name == "embed":
                leaf = jax.random.normal(k, shape) * 0.02
            else:
                leaf = jax.random.normal(k, shape) * shape[-2] ** -0.5
            out.append(leaf.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: dict):
    """(inverse frequencies (dr/2,), cos/sin factor, softmax scale)."""
    g = dims(cfg)
    dr, theta = g["dr"], cfg["rope_theta"]
    ys = cfg["rope_scaling"]
    factor, orig = ys["factor"], ys["original_max_position_embeddings"]

    def pair(rotations):
        return (dr * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(ys["beta_fast"])), 0)
    high = min(math.ceil(pair(ys["beta_slow"])), dr - 1)
    f = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = f / factor * ramp + f * (1.0 - ramp)
    cos_sin = _mscale(factor, ys["mscale"]) / _mscale(factor,
                                                     ys["mscale_all_dim"])
    scale = (g["dn"] + dr) ** -0.5 * _mscale(factor,
                                            ys["mscale_all_dim"]) ** 2
    return inv_freq, cos_sin, scale


def _rope(x, inv_freq, mscale):
    """x: (b, h, s, dr); rotate the two halves."""
    s = x.shape[-2]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dot(quant):
    q8 = _quantizer(quant)

    def dot(eq, a, b):
        return jnp.einsum(eq, q8(a), q8(b), precision=HIGHEST)

    return dot


def mla(a, h, cfg: dict, quant=None):
    """The latent attention of one layer, weights ``a``, input (b, s, d)."""
    g = dims(cfg)
    H, r, dn, dr, dv = (g[k] for k in "H r dn dr dv".split())
    dot = _dot(quant)
    inv_freq, cos_sin, scale = yarn(cfg)
    b, s, _ = h.shape
    q = dot("bsd,de->bse", h, a["wq"]).reshape(b, s, H, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    kv_a = dot("bsd,de->bse", h, a["wkv_a"])
    c_kv = _rms(kv_a[..., :r], a["kv_norm"]["gamma"], cfg["rms_norm_eps"])
    k_pe = _rope(kv_a[:, None, :, r:], inv_freq, cos_sin)     # (b, 1, s, dr)
    kv = dot("bsr,re->bse", c_kv, a["wkv_b"]).reshape(b, s, H, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq, cos_sin)],
                        -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, H, s, dr))], -1)
    sc = dot("bhqd,bhkd->bhqk", q, k) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), dtype=bool)), sc, -jnp.inf)
    o = dot("bhqk,bhkd->bhqd", jax.nn.softmax(sc, axis=-1), kv[..., dn:])
    o = o.transpose(0, 2, 1, 3).reshape(b, s, H * dv)
    return dot("bse,ed->bsd", o, a["wo"])


def _swiglu(m, h, dot):
    u = jax.nn.silu(dot("bsd,df->bsf", h, m["w_gate"])) * dot(
        "bsd,df->bsf", h, m["w_up"])
    return dot("bsf,fd->bsd", u, m["w_down"])


def route(m, h, cfg: dict):
    """Router probabilities (b, s, E) and the greedy top-k (weights,
    expert ids), in float32 at ``highest``."""
    logits = jnp.einsum("bsd,de->bse", h, m["router"], precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return probs, w * cfg["routed_scaling_factor"], idx


def experts(m, h, cfg: dict, quant=None):
    """One expert layer on (b, s, d): (output, balance term per sequence
    (b,), top-k ids (b, s, K))."""
    g = dims(cfg)
    E, K, s = g["E"], g["K"], h.shape[1]
    dot = _dot(quant)
    probs, w, idx = route(m, h, cfg)
    out = _swiglu(m["shared"], h, dot)
    for j in range(g["held"]):
        e = cfg["held_expert_start"] + j
        gate = jnp.where(idx == e, w, 0.0).sum(-1)[..., None]   # (b, s, 1)
        mine = {k: m[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + gate * _swiglu(mine, h, dot)
    count = jax.nn.one_hot(idx, E).sum((1, 2))                 # (b, E)
    f = count * E / (K * s)
    aux = (f * probs.mean(1)).sum(-1)
    return out, aux, idx


def _stack(p, toks, cfg: dict, quant=None):
    """The layers on ``toks`` (b, s): (final hidden state, balance terms
    (expert layers, b), top-k ids (expert layers, b, s, K))."""
    eps = cfg["rms_norm_eps"]
    dot = _dot(quant)
    x = p["embed"][toks]

    def attend(x, lp):
        return x + mla(lp["attn"], _rms(x, lp["norm1"]["gamma"], eps), cfg,
                       quant)

    def dense(x, lp):
        x = attend(x, lp)
        return x + _swiglu(lp["mlp"], _rms(x, lp["norm2"]["gamma"], eps),
                           dot), None

    def expert(x, lp):
        x = attend(x, lp)
        y, aux, idx = experts(lp["moe"], _rms(x, lp["norm2"]["gamma"], eps),
                              cfg, quant)
        return x + y, (aux, idx)

    x, _ = jax.lax.scan(dense, x, p["dense_layers"])
    x, (aux, idx) = jax.lax.scan(expert, x, p["layers"])
    return _rms(x, p["final_norm"]["gamma"], eps), aux, idx


def objective_sum(p, toks, cfg: dict, quant=None):
    """Summed next-token NLL of ``toks`` (b, s) plus ``aux_loss_alpha *
    (s - 1)`` times each sequence's balance term summed over the expert
    layers: divided by the token count it is the loss."""
    x, aux, _ = _stack(p, toks, cfg, quant)
    logits = _dot(quant)("bsd,dv->bsv", x, p["head"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1).sum()
    return nll + cfg["aux_loss_alpha"] * (toks.shape[1] - 1) * aux.sum()


def routing(p, toks, cfg: dict):
    """The reference's top-k expert ids of every expert layer, (expert
    layers, b, s, K), from the weights ``p`` taken to float32."""

    def ids(p, toks):
        pf = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return _stack(pf, toks, cfg)[2]

    return jax.jit(ids)(p, toks)


class Trainer:
    """Reference AdamW steps on bf16 parameters, from zero moments or
    from a given state (``m``, ``v`` and ``t`` steps taken).  The
    gradient is summed over ``blocks`` row blocks of the batch, so that
    the pass fits beside the optimizer state."""

    def __init__(self, cfg: dict, params, *, blocks: int, quant=None,
                 m=None, v=None, t: int = 0):
        self.blocks = blocks
        self.params = params
        zeros = functools.partial(jax.tree.map, lambda x: jnp.zeros(
            x.shape, jnp.float32))
        self.m = zeros(params) if m is None else m
        self.v = zeros(params) if v is None else v
        self.t = t

        def add_grad(acc, params, toks):
            pf = jax.tree.map(lambda x: x.astype(jnp.float32), params)
            loss, g = jax.value_and_grad(objective_sum)(pf, toks, cfg, quant)
            return loss, jax.tree.map(jnp.add, acc, g)

        self._add_grad = jax.jit(add_grad, donate_argnums=(0,))
        self._upd = jax.jit(functools.partial(_adamw, **cfg["optimizer"]),
                            donate_argnums=(0, 1, 2, 3))

    def loss_and_grad(self, toks):
        """Mean loss (with the balance term) and its float32 gradient."""
        grad = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                            self.params)
        total = 0.0
        for blk in jnp.split(toks, self.blocks):
            loss, grad = self._add_grad(grad, self.params, blk)
            total = total + float(loss)
        count = toks.shape[0] * (toks.shape[1] - 1)
        return total / count, jax.tree.map(lambda x: x / count, grad)

    def step(self, toks):
        """One AdamW step on ``toks``; returns the loss and the leaf
        norms of the gradient it took."""
        loss, grad = self.loss_and_grad(toks)
        norms = leaf_norms(grad)
        self.t += 1
        self.params, self.m, self.v = self._upd(
            self.params, self.m, self.v, grad, jnp.float32(self.t))
        return loss, norms
