"""Public wrapper around the gate-window Pallas kernel.

Handles ragged shapes (pad cells to the block multiple, lane-pad n to
128 — all-False padding never changes any of the four statistics),
bool -> int32 plumbing, and backend selection: lowered for a TPU the
kernel compiles natively, on any other platform it runs in interpret
mode (still jit-staged, so it composes with the lockstep ``lax.scan``).

Both entry points accept a leading **spec axis** — ``(specs, cells,
rounds, n)`` inputs fold into the cells axis (one fused launch over
``specs * cells`` rows, then unfold) — and register that fold as a
``custom_vmap`` rule, so the grid-fused batch engine's ``jax.vmap``
over stacked specs (``core.batch``) lowers to the same single launch
instead of jax's generic pallas batching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from .. import native_on_tpu
from .gate_window import buffer_stats as _buf_kernel
from .gate_window import window_stats as _win_kernel

_LANE = 128
_BLOCK_C = 512


def _pad_plan(cells: int, n: int):
    n_pad = -(-n // _LANE) * _LANE
    block_c = min(_BLOCK_C, max(8, -(-cells // 8) * 8))
    c_pad = -(-cells // block_c) * block_c
    return n_pad, block_c, c_pad


def _padded_i32(win, c_pad: int, n_pad: int):
    cells, _, n = win.shape
    w32 = win.astype(jnp.int32)
    return jnp.pad(w32, ((0, c_pad - cells), (0, 0), (0, n_pad - n)))


def _window_call(win, B: int, *, interpret: bool):
    cells, _, n = win.shape
    n_pad, block_c, c_pad = _pad_plan(cells, n)
    distinct, worker_max, round_max, pair = _win_kernel(
        _padded_i32(win, c_pad, n_pad), B,
        block_c=block_c, interpret=interpret,
    )
    return (
        distinct[:cells],
        worker_max[:cells],
        round_max[:cells],
        pair[:cells] > 0,
    )


def _buffer_call(buf, B: int, *, interpret: bool):
    cells, _, n = buf.shape
    n_pad, block_c, c_pad = _pad_plan(cells, n)
    act, cnt, md, pair = _buf_kernel(
        _padded_i32(buf, c_pad, n_pad), B,
        block_c=block_c, interpret=interpret,
    )
    return (
        act[:cells, :n] > 0,
        cnt[:cells, :n],
        md[:cells, :n] > 0,
        pair[:cells, 0] > 0,
    )


def _fold_specs(call, x):
    """Fold a leading spec axis into the cells axis, run the fused
    kernel ONCE over (specs * cells) rows, and unfold the outputs —
    all-reshape, so verdicts are identical to per-spec calls."""
    S, C = x.shape[0], x.shape[1]
    outs = call(x.reshape((S * C,) + x.shape[2:]))
    return tuple(o.reshape((S, C) + o.shape[1:]) for o in outs)


@functools.lru_cache(maxsize=None)
def _vmappable(which: str, B: int, interpret: bool | None):
    """The stats call with a reshape-to-cells ``custom_vmap`` rule, one
    cached instance per (kernel, B, interpret) so jit tracing stays
    stable.  ``jax.vmap`` over it (the grid-fused engine's spec axis)
    becomes one launch over the folded rows.  ``interpret=None`` picks
    native or interpreted by the lowering platform
    (:func:`repro.kernels.native_on_tpu`)."""
    call = {"window": _window_call, "buffer": _buffer_call}[which]

    @custom_vmap
    def f(x):
        if interpret is None:
            return native_on_tpu(functools.partial(call, B=B), x)
        return call(x, B, interpret=interpret)

    @f.def_vmap
    def _rule(axis_size, in_batched, x):
        del axis_size, in_batched
        outs = _fold_specs(f, x)
        return outs, tuple(True for _ in outs)

    return f


@functools.partial(jax.jit, static_argnames=("B", "interpret"))
def window_stats(win: jax.Array, B: int, *, interpret: bool | None = None):
    """Fused per-cell suffix-window reductions, any (cells, W, n) bool
    — or (specs, cells, W, n) with the spec axis folded into cells.

    Returns ``(distinct, worker_max, round_max, pair_bad)`` — int32
    counts of shape ``(cells,)`` (``(specs, cells)`` for 4-D input)
    plus the bool pair-violation flag — exactly the
    ``core.straggler._window_stats`` contract.
    """
    fn = _vmappable("window", B, interpret)
    if win.ndim == 4:
        return _fold_specs(fn, win)
    return fn(win)


@functools.partial(jax.jit, static_argnames=("B", "interpret"))
def buffer_stats(buf: jax.Array, B: int, *, interpret: bool | None = None):
    """Fused fixed-buffer statistics, any (cells, kh >= 1, n) bool —
    or (specs, cells, kh, n) with the spec axis folded into cells.

    Returns ``(bufact, bufcnt, mdmap, pair_bad)`` — bool/int32 worker
    maps of shape ``(cells, n)`` plus the bool buffer-internal pair
    flag (a leading specs axis on every output for 4-D input) —
    exactly the ``core.straggler._buffer_stats`` contract.
    """
    fn = _vmappable("buffer", B, interpret)
    if buf.ndim == 4:
        return _fold_specs(fn, buf)
    return fn(buf)
