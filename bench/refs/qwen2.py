"""Plain reference of Qwen2 training steps, in float32.

Follows the Qwen2 architecture as published (arXiv:2407.10671, the
Qwen/Qwen2-0.5B ``config.json``): token embedding; per layer RMSNorm,
grouped-query attention with biases on q, k and v, rotary embedding by
halves with base ``rope_theta``, causal softmax, output projection,
RMSNorm, SwiGLU MLP, each with a residual; final RMSNorm and the tied
embedding as the head; next-token cross entropy, mean over tokens.
AdamW updates in float32 and stores parameters in the configuration's
bfloat16.  Imports nothing of the program.

Weights are a dict in the layout the benchmark makes them
(:func:`init_weights`): ``embed``, ``final_norm/gamma`` and per layer,
stacked on a leading layer axis, ``norm1/gamma``, ``attn/{wq,wk,wv,wo,
bq,bk,bv}``, ``norm2/gamma``, ``mlp/{w_gate,w_up,w_down}``.

``quant`` rounds every matmul operand before the product: ``None`` is
the reference (float32 at ``highest``); ``"fp8"`` is the control,
operands rounded to float8 e4m3 with one scale per tensor, gradients
passed through the rounding in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
                dh=cfg["head_dim"], ff=cfg["intermediate_size"],
                V=cfg["vocab_size"])


def weight_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    L, d, H, K, dh, ff, V = (g[k] for k in "L d H K dh ff V".split())
    return {
        "embed": (V, d),
        "final_norm": {"gamma": (d,)},
        "layers": {
            "norm1": {"gamma": (L, d)},
            "attn": {"wq": (L, d, H * dh), "wk": (L, d, K * dh),
                     "wv": (L, d, K * dh), "wo": (L, H * dh, d),
                     "bq": (L, H * dh), "bk": (L, K * dh),
                     "bv": (L, K * dh)},
            "norm2": {"gamma": (L, d)},
            "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },
    }


def init_weights(cfg: dict, seed: int):
    """Random weights from ``seed`` in the configuration's dtype, made
    on the device in one jitted call: std 0.02 for the embedding,
    ``fan_in**-0.5`` for matrices, ones for norm scales, zeros for
    biases."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = weight_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if name == "gamma":
                leaf = jnp.ones(shape, jnp.float32)
            elif name.startswith("b"):
                leaf = jnp.zeros(shape, jnp.float32)
            elif name == "embed":
                leaf = jax.random.normal(k, shape) * 0.02
            else:
                leaf = jax.random.normal(k, shape) * shape[-2] ** -0.5
            out.append(leaf.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def tokens(seed: int, job: int, batch: int, seq: int, vocab: int):
    """The token ids of one job: uniform in [0, vocab), (batch, seq)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), job)
    toks = jax.random.randint(key, (batch, seq + 1), 0, vocab,
                              dtype=jnp.int32)
    return toks[:, :-1]


def _quantizer(quant):
    if quant is None:
        return lambda x: x
    if quant != "fp8":
        raise ValueError(quant)
    f8 = jnp.float8_e4m3fn
    top = float(jnp.finfo(f8).max)

    def q(x):
        scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / top)
        scale = jnp.where(scale > 0, scale, 1.0)
        low = (x / scale).astype(f8).astype(jnp.float32) * scale
        # the products see fp8 operands; gradients pass in float32
        return x + jax.lax.stop_gradient(low - x)

    return q


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _rope(x, theta):
    """x: (b, h, s, dh); rotate the two halves of each head."""
    dh, s = x.shape[-1], x.shape[-2]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def nll_sum(p, toks, cfg: dict, quant=None):
    """Summed next-token negative log-likelihood of ``toks`` (b, s)
    under float32 weights ``p``."""
    g = dims(cfg)
    H, K, dh = g["H"], g["K"], g["dh"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q8 = _quantizer(quant)

    def dot(eq, a, b):
        return jnp.einsum(eq, q8(a), q8(b), precision=HIGHEST)

    b, s = toks.shape
    x = p["embed"][toks]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["norm1"]["gamma"], eps)
        q = dot("bsd,de->bse", h, a["wq"]) + a["bq"]
        k = dot("bsd,de->bse", h, a["wk"]) + a["bk"]
        v = dot("bsd,de->bse", h, a["wv"]) + a["bv"]
        q = _rope(q.reshape(b, s, H, dh).transpose(0, 2, 1, 3), theta)
        k = _rope(k.reshape(b, s, K, dh).transpose(0, 2, 1, 3), theta)
        v = v.reshape(b, s, K, dh).transpose(0, 2, 1, 3)
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        sc = dot("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
        sc = jnp.where(causal, sc, -jnp.inf)
        o = dot("bhqk,bhkd->bhqd", jax.nn.softmax(sc, axis=-1), v)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, H * dh)
        x = x + dot("bse,ed->bsd", o, a["wo"])
        m = lp["mlp"]
        h = _rms(x, lp["norm2"]["gamma"], eps)
        u = jax.nn.silu(dot("bsd,df->bsf", h, m["w_gate"])) * dot(
            "bsd,df->bsf", h, m["w_up"])
        return x + dot("bsf,fd->bsd", u, m["w_down"]), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x, p["final_norm"]["gamma"], eps)
    logits = dot("bsd,vd->bsv", x, p["embed"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1).sum()


def _adamw(params, m, v, grad, t, *, lr, b1, b2, eps, weight_decay):
    b1t = 1.0 - b1 ** t
    b2t = 1.0 - b2 ** t

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / b1t) / (jnp.sqrt(v / b2t) + eps)
        if weight_decay:
            delta = delta + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tree = jax.tree.flatten(params)
    out = [one(*x) for x in zip(flat_p, tree.flatten_up_to(grad),
                                tree.flatten_up_to(m),
                                tree.flatten_up_to(v))]
    return tuple(tree.unflatten([o[i] for o in out]) for i in range(3))


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
            loss, g = jax.value_and_grad(nll_sum)(pf, toks, cfg, quant)
            return loss, jax.tree.map(jnp.add, acc, g)

        self._add_grad = jax.jit(add_grad, donate_argnums=(0,))
        self._upd = jax.jit(functools.partial(_adamw, **cfg["optimizer"]),
                            donate_argnums=(0, 1, 2, 3))

    def loss_and_grad(self, toks):
        """Mean next-token loss and its float32 gradient."""
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


def leaf_norms(tree) -> dict:
    """{'/'-joined path: float32 L2 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.linalg.norm(x.astype(jnp.float32)
                                                .ravel()) for x in xs])(
        [x for _, x in flat])
    return {"/".join(k.key for k in path): float(n)
            for (path, _), n in zip(flat, norms)}


def worst_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Largest gap between two leaves' norms, each measured against the
    larger of the reference leaf's norm and the median leaf's; leaves
    outside ``keep`` are left out."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    worst, at = 0.0, ""
    for k in names:
        gap = abs(prog.get(k, math.inf) - ref[k]) / max(ref[k], med)
        if gap > worst or not math.isfinite(gap):
            worst, at = gap, k
    return worst, at
