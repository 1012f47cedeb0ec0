"""Array-backend shim for the lockstep scheme kernels.

Mirrors the ``kernels/*/ref.py`` vs ``ops.py`` split at the library
level: every array op in the lockstep hot loop (``core.kernel``) goes
through the active :class:`Backend` — the array namespace lives in
``Backend.xp`` and all state updates go through the functional
``at_set`` / ``at_or`` helpers — and the control-flow hooks (``jit``,
``scan``, ``vmap``, ``where``, ``segment_sum``) have a plain-Python
fallback, so
the same kernel code runs eagerly on numpy or staged through
``jax.jit`` + ``lax.scan`` with no scheme-logic changes.

The **numpy** backend is the default and is what every bit-for-bit
guarantee in ``tests/test_lockstep.py`` / ``tests/test_batch_engine.py``
is stated against (its ``at_*`` helpers mutate in place and return the
same array, which is safe because kernel states own their arrays).  The
**jax** backend is always registered; its ``at_*``
helpers are non-mutating (``arr.at[idx].set``) and its ``concrete``
flag is False, which tells the kernels that data-dependent Python
branching (early exits, ``nonzero`` fancy-indexing) is unavailable —
they switch to mask-select math with static shapes, the form
``lax.scan`` can carry over the rounds axis.  jax numerics are an
"allclose" contract, not a bit-identical one (exact for bool/int
bookkeeping, allclose for float loads/runtimes — see
docs/scheme_kernels.md).

Set the environment variable ``REPRO_BACKEND=jax`` to select the jax
backend process-wide (the CI matrix job uses this to run the lockstep
differential suite on both backends); an unknown name raises at import.
"""

from __future__ import annotations

import contextlib
import os

import jax as _jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Backend",
    "available_backends",
    "get_backend",
    "set_backend",
    "use_backend",
    "xp_of",
]


class Backend:
    """One array namespace + functional-update and staging helpers."""

    name: str = "abstract"
    xp = None
    #: True when arrays hold concrete values the kernels may branch on
    #: (``if mask.any(): ...``).  False under jax, where ``step`` may be
    #: traced inside ``jit``/``scan`` and every branch must be
    #: mask-select with static shapes.
    concrete: bool = True

    def at_set(self, arr, idx, val):
        """Functional ``arr[idx] = val``; returns the updated array."""
        raise NotImplementedError

    def at_or(self, arr, idx, val):
        """Functional ``arr[idx] |= val``; returns the updated array."""
        raise NotImplementedError

    def where(self, cond, x, y):
        """Elementwise select (``lax.select``-style; broadcasts)."""
        return self.xp.where(cond, x, y)

    def jit(self, fn, **kwargs):
        """Stage ``fn`` for compiled execution (identity on numpy)."""
        return fn

    def scan(self, f, init, xs, length: int | None = None):
        """``lax.scan`` contract: ``f(carry, x) -> (carry, y)`` over the
        leading axis of the ``xs`` pytree; returns ``(carry, ys)`` with
        the per-step ``y`` outputs stacked on a new leading axis.  The
        numpy fallback is a plain Python loop, so kernels written
        against ``scan`` run identically on both backends.
        """
        raise NotImplementedError

    def vmap(self, fn, in_axes=0, out_axes=0):
        """``jax.vmap`` contract: map ``fn`` over a leading batch axis
        of its (pytree) arguments; ``in_axes`` is an int applied to all
        arguments or a per-argument tuple with ``None`` meaning
        "broadcast, don't map".  The grid-fused batch engine wraps one
        spec's staged lockstep sweep with this to run a whole shape
        bucket of stacked specs under a single compilation.  The numpy
        fallback is a plain Python loop over the mapped axis with
        leaf-wise stacking, so vmapped code runs identically (just
        eagerly) on both backends.
        """
        raise NotImplementedError

    def argsort_stable(self, arr, axis: int = -1):
        """Stable ascending argsort (ties keep first-index order)."""
        raise NotImplementedError

    def segment_sum(self, data, segment_ids, num_segments: int):
        """Sum ``data`` rows into ``num_segments`` buckets by id."""
        raise NotImplementedError

    @property
    def lax(self):
        """The backend's lax-like namespace (None on numpy)."""
        return None

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"<Backend {self.name}>"


def _tree_map(fn, tree):
    """Minimal pytree map over nested tuples/lists/dicts (None passes
    through) — enough for the numpy ``scan`` fallback to mirror
    ``lax.scan``'s pytree handling."""
    if tree is None:
        return None
    if isinstance(tree, (tuple, list)):
        return type(tree)(_tree_map(fn, x) for x in tree)
    if isinstance(tree, dict):
        return {k: _tree_map(fn, v) for k, v in tree.items()}
    return fn(tree)


def _tree_leaves(tree):
    if tree is None:
        return []
    if isinstance(tree, (tuple, list)):
        return [leaf for x in tree for leaf in _tree_leaves(x)]
    if isinstance(tree, dict):
        return [leaf for v in tree.values() for leaf in _tree_leaves(v)]
    return [tree]


def _zip_stack(trees):
    """Stack a list of structurally identical pytrees leaf-wise on a new
    leading axis — how the numpy ``scan``/``vmap`` fallbacks assemble
    their per-step / per-lane outputs into ``lax``-shaped results."""
    first = trees[0]
    if first is None:
        return None
    if isinstance(first, (tuple, list)):
        return type(first)(
            _zip_stack([t[i] for t in trees]) for i in range(len(first))
        )
    if isinstance(first, dict):
        return {k: _zip_stack([t[k] for t in trees]) for k in first}
    return np.stack(trees, axis=0)


class _NumpyBackend(Backend):
    name = "numpy"
    xp = np
    concrete = True

    def at_set(self, arr, idx, val):
        arr[idx] = val
        return arr

    def at_or(self, arr, idx, val):
        arr[idx] |= val
        return arr

    def scan(self, f, init, xs, length: int | None = None):
        leaves = _tree_leaves(xs)
        if length is None and not leaves:
            raise ValueError("scan needs xs leaves or an explicit length")
        n = length if length is not None else len(leaves[0])
        carry = init
        ys = []
        for i in range(n):
            x = _tree_map(lambda a: a[i], xs)
            carry, y = f(carry, x)
            ys.append(y)
        if not ys:
            return carry, None
        return carry, _zip_stack(ys)

    def vmap(self, fn, in_axes=0, out_axes=0):
        if out_axes != 0:
            raise NotImplementedError("numpy vmap fallback maps to axis 0")

        def mapped(*args):
            axes = (
                tuple(in_axes)
                if isinstance(in_axes, (tuple, list))
                else (in_axes,) * len(args)
            )
            if len(axes) != len(args):
                raise ValueError(
                    f"vmap got {len(args)} args but in_axes has "
                    f"{len(axes)} entries"
                )
            size = None
            for a, ax in zip(args, axes):
                if ax is None:
                    continue
                leaves = _tree_leaves(a)
                if leaves:
                    size = np.shape(leaves[0])[ax]
                    break
            if size is None:
                raise ValueError("vmap needs at least one mapped input")
            ys = []
            for i in range(size):
                call = [
                    a if ax is None
                    else _tree_map(lambda x: np.take(x, i, axis=ax), a)
                    for a, ax in zip(args, axes)
                ]
                ys.append(fn(*call))
            return _zip_stack(ys)

        return mapped

    def argsort_stable(self, arr, axis: int = -1):
        return np.argsort(arr, axis=axis, kind="stable")

    def segment_sum(self, data, segment_ids, num_segments: int):
        data = np.asarray(data)
        out = np.zeros((num_segments,) + data.shape[1:], dtype=data.dtype)
        np.add.at(out, np.asarray(segment_ids), data)
        return out


class _JaxBackend(Backend):
    name = "jax"
    xp = jnp
    concrete = False

    def at_set(self, arr, idx, val):
        return arr.at[idx].set(val)

    def at_or(self, arr, idx, val):
        # single scatter, no gather: max == or for bools; for int
        # flag-words apply the OR to the selected elements in place
        if arr.dtype == jnp.bool_:
            return arr.at[idx].max(val)
        return arr.at[idx].apply(lambda x: x | val)

    def jit(self, fn, **kwargs):
        return _jax.jit(fn, **kwargs)

    def scan(self, f, init, xs, length: int | None = None):
        return _jax.lax.scan(f, init, xs, length=length)

    def vmap(self, fn, in_axes=0, out_axes=0):
        if isinstance(in_axes, list):
            in_axes = tuple(in_axes)
        return _jax.vmap(fn, in_axes=in_axes, out_axes=out_axes)

    def argsort_stable(self, arr, axis: int = -1):
        return jnp.argsort(arr, axis=axis, stable=True)

    def segment_sum(self, data, segment_ids, num_segments: int):
        return _jax.ops.segment_sum(
            data, segment_ids, num_segments=num_segments
        )

    @property
    def lax(self):
        return _jax.lax


_REGISTRY: dict[str, Backend] = {
    "numpy": _NumpyBackend(),
    "jax": _JaxBackend(),
}

_ACTIVE = "numpy"


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def get_backend(name: str | None = None) -> Backend:
    """The active backend (or a specific one by name)."""
    return _REGISTRY[name or _ACTIVE]


def set_backend(name: str) -> Backend:
    """Select the process-wide default backend for the scheme kernels."""
    global _ACTIVE
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        )
    _ACTIVE = name
    return _REGISTRY[name]


@contextlib.contextmanager
def use_backend(name: str):
    """Temporarily switch the default backend."""
    global _ACTIVE
    prev = _ACTIVE
    set_backend(name)
    try:
        yield _REGISTRY[name]
    finally:
        _ACTIVE = prev


def xp_of(arr):
    """The array namespace ``arr`` belongs to: numpy for ndarrays (and
    scalars), ``jax.numpy`` for jax arrays/tracers.  Lets the batched
    straggler-model hooks run unchanged under ``jit``/``scan``."""
    if isinstance(arr, np.ndarray) or np.isscalar(arr):
        return np
    return jnp


_env_backend = os.environ.get("REPRO_BACKEND", "").strip().lower()
if _env_backend:
    set_backend(_env_backend)
