"""AdamW in pure JAX (pytree-native, f32 moments regardless of param dtype)."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    m: object
    v: object


def adamw_init(params) -> AdamWState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros,
                      v=jax.tree.map(jnp.copy, zeros))


def adamw_update(
    params,
    grads,
    state: AdamWState,
    *,
    lr: float | jax.Array = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
):
    with jax.named_scope("adamw"):
        step = state.step + 1
        b1t = 1.0 - b1 ** step.astype(jnp.float32)
        b2t = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * g32 * g32
            mhat = m / b1t
            vhat = v / b2t
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, AdamWState(step=step, m=new_m, v=new_v)


def cosine_schedule(base_lr: float, warmup: int, total: int):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * base_lr * (1.0 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)

    return lr
