"""Pure-jnp oracle: dense GQA attention with causal / sliding-window masks.

The value head size may differ from the query/key head size (latent
attention: 192 against 128); ``scale`` defaults to ``dh ** -0.5``."""

import jax
import jax.numpy as jnp


def attention(
    q: jax.Array,   # (b, hq, sq, dh)
    k: jax.Array,   # (b, hkv, sk, dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) * (dh ** -0.5 if scale is None else scale)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32))
    return out.astype(q.dtype)
