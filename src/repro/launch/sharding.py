"""Sharding rules: params / batches / caches -> PartitionSpecs.

Best-effort divisible sharding: every rule proposes a preferred axis
per dimension and falls back to replication when the dimension does not
divide the mesh axis — this is what lets all 10 assigned architectures
lower on the same mesh without per-arch hand tuning.  The §Perf pass
then iterates on the rules where the roofline says it matters.

Parameter layout (dense/moe blocks follow the Megatron pattern):
  embed (V, d)        -> (model, None)        vocab-sharded
  head  (d, V)        -> (None, model)
  attn wq/wk/wv       -> (None, model)        column parallel
  attn wo             -> (model, None)        row parallel
  mlp w_gate/w_up     -> (None, model)
  mlp w_down          -> (model, None)
  moe expert weights  -> (None, None, model)  tensor-parallel experts
                         (expert counts 8/60 don't divide 16; expert
                          parallelism is a §Perf variant)
  ssm in_proj         -> (None, model), out_proj -> (model, None)
  norms / scalars     -> replicated

Leading layer-stack axes (from scan stacking: ``layers``, and a MoE
stack's leading ``dense_layers``) are never sharded.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

from .mesh import data_axes, model_axis


#: top-level parameter groups stacked on a leading layer axis
STACKS = ("layers", "dense_layers")


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _maybe(mesh, dim: int, axes):
    """axes if dim divides the mesh axes product, else None."""
    return axes if axes and dim % _axis_size(mesh, axes) == 0 else None


def param_pspec(path: tuple[str, ...], leaf, cfg: ModelConfig, mesh) -> P:
    """PartitionSpec for one parameter leaf (path = key names)."""
    m = model_axis(mesh)
    name = path[-1]
    stacked = path[0] in STACKS  # leading scan axis
    lead = (None,) if stacked else ()
    shape = leaf.shape[1:] if stacked else leaf.shape

    def spec(*dims):
        dims = tuple(_maybe(mesh, shape[i], d) for i, d in enumerate(dims))
        return P(*lead, *dims)

    if name == "embed":
        return spec(m, None)
    if name == "head":
        return spec(None, m)
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "in_proj"):
        return spec(None, m)
    if name in ("wo", "w_down", "out_proj"):
        if len(shape) == 3:  # moe expert (E, f, d): shard f
            return spec(None, m, None)
        return spec(m, None)
    if name in ("bq", "bk", "bv"):
        return spec(m)
    if name == "router":
        return spec(None, None)
    if len(shape) == 3 and name in ("w_gate", "w_up"):
        return spec(None, None, m)
    # conv_w, conv_b, A_log, D, dt_bias, gamma, scalars
    return P(*lead, *(None,) * len(shape))


def _moe_fix(path, leaf, cfg, mesh, base: P) -> P:
    """Expert tensors are 3D; re-route w_gate/w_up to (None, None, model)."""
    name = path[-1]
    stacked = path[0] in STACKS
    shape = leaf.shape[1:] if stacked else leaf.shape
    if len(shape) == 3 and name in ("w_gate", "w_up"):
        m = model_axis(mesh)
        lead = (None,) if stacked else ()
        return P(*lead, None, None, _maybe(mesh, shape[2], m))
    return base


def params_shardings(cfg: ModelConfig, params_shape, mesh):
    """NamedSharding pytree matching ``params_shape`` (ShapeDtypeStructs)."""

    def one(path, leaf):
        keys = tuple(_key(p) for p in path)
        spec = param_pspec(keys, leaf, cfg, mesh)
        spec = _moe_fix(keys, leaf, cfg, mesh, spec)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def _key(p) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def batch_shardings(cfg: ModelConfig, batch_shape, mesh, *, profile: str = "tp"):
    """Batch pytree: leading dim over (pod, data) — or over ALL axes in
    the "fsdp" profile, where the model axis carries batch too and XLA
    all-gathers the (model-axis-sharded) params per layer instead of
    psumming activations (§Perf iteration)."""
    da = data_axes(mesh)
    if profile == "fsdp":
        m = model_axis(mesh)
        da = da + ((m,) if m else ())

    def one(leaf):
        b = leaf.shape[0]
        lead = _maybe(mesh, b, da)
        if lead is None and len(da) > 1:
            lead = _maybe(mesh, b, da[:-1])  # drop model axis if ragged
        rest = (None,) * (len(leaf.shape) - 1)
        return NamedSharding(mesh, P(lead, *rest))

    return jax.tree.map(one, batch_shape)


def cache_shardings(cfg: ModelConfig, cache_shape, mesh, *, mode: str = "auto"):
    """KV / SSM caches.

    kv cache (L, b, hkv, S, dh): batch over (pod,data); heads over model
    when divisible, else (mode="auto") sequence over model — or
    (mode="headdim") the head_dim over model, which keeps the
    dynamic-update-slice local at the cost of a psum after QK^T
    (§Perf iteration for the decode shapes).
    ssm state (L, b, nh, hd, st): batch over (pod,data), heads over model.
    When b == 1 (long_500k) the data axes move to the sequence / heads
    dims instead so the cache still spreads across the pod.
    """
    da = data_axes(mesh)
    m = model_axis(mesh)

    def one(path, leaf):
        name = _key(path[-1])
        s = leaf.shape
        if name in ("k", "v", "shared_k", "shared_v"):
            b, hkv, S = s[1], s[2], s[3]
            dh = s[4]
            if _maybe(mesh, b, da):
                heads = _maybe(mesh, hkv, m)
                if heads:
                    return NamedSharding(mesh, P(None, da, heads, None, None))
                if mode == "headdim" and _maybe(mesh, dh, m):
                    return NamedSharding(mesh, P(None, da, None, None, m))
                seq = _maybe(mesh, S, m)
                return NamedSharding(mesh, P(None, da, None, seq, None))
            # b == 1: spread sequence across everything
            seq = _maybe(mesh, S, da + ((m,) if m else ()))
            if seq:
                return NamedSharding(mesh, P(None, None, None, da + (m,), None))
            return NamedSharding(mesh, P(None, None, None, None, None))
        if name == "state":
            b, nh = s[1], s[2]
            bd = _maybe(mesh, b, da)
            heads = _maybe(mesh, nh, m)
            return NamedSharding(mesh, P(None, bd, heads, None, None))
        if name == "conv":
            bd = _maybe(mesh, s[1], da)
            return NamedSharding(mesh, P(None, bd, None, None))
        return NamedSharding(mesh, P(*(None,) * len(s)))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def opt_shardings(cfg: ModelConfig, opt_shape, mesh, params_sharding):
    """Adam moments mirror the parameter shardings; step is replicated."""
    import numpy as np  # noqa: F401

    return type(opt_shape)(
        step=NamedSharding(mesh, P()),
        m=jax.tree.map(
            lambda _, s: s, opt_shape.m, params_sharding
        ),
        v=jax.tree.map(lambda _, s: s, opt_shape.v, params_sharding),
    )


def replicated(mesh):
    return NamedSharding(mesh, P())
