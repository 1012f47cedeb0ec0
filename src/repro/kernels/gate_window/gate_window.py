"""Pallas TPU kernel: fused suffix-window reductions for the wait-out gate.

Each round, the batched conformance gate (``core.kernel.GateKernel``)
asks every straggler model whether each grid cell's trailing window
``(cells, W, n)`` is admissible.  All the windowed models' verdicts
reduce to four per-cell statistics of that boolean buffer:

  * ``distinct``   — workers straggling anywhere in the window
                     (spatial constraint of Bursty/Arbitrary);
  * ``worker_max`` — max per-worker straggling-round count
                     (Arbitrary's ``N``);
  * ``round_max``  — max per-round straggler count (PerRound's ``s``);
  * ``pair``       — count of same-worker straggle pairs >= ``B``
                     rounds apart (Bursty's temporal constraint; pass
                     ``B >= W`` to skip the pair loop entirely).

XLA would compute each verdict as separate reductions re-reading the
window buffer; this kernel streams each cell block through VMEM once
and emits all four statistics together.  ``W`` is tiny (<= a few
rounds) and ``n`` is lane-padded by the wrapper, so one grid step
reduces a ``(block_c, W, n)`` int32 tile with plain VPU ops.

``ops.window_stats`` is the public wrapper (padding, dtype plumbing,
CPU interpret-mode selection); ``ref.window_stats`` is the pure-jnp
oracle the differential test runs against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _row_any(win):
    """max over the (tiny, static) round axis, unrolled — XLA lowers a
    strided middle-axis reduction ~10x slower than these elementwise
    ops on CPU, and the same unrolling is TPU-friendly."""
    out = win[:, 0]
    for r in range(1, win.shape[1]):
        out = jnp.maximum(out, win[:, r])
    return out


def _row_sum(win):
    out = win[:, 0]
    for r in range(1, win.shape[1]):
        out = out + win[:, r]
    return out


def _pair_count(win, d: int):
    """Same-worker straggle pairs exactly ``d`` rounds apart, per cell,
    as a ``(block_c, 1)`` column.  Rounds fold elementwise first, then
    lanes reduce with ``keepdims``: Mosaic rejects a reduction to a 1-D
    value (``.sum(axis=(1, 2))``), so no step may drop to rank 1."""
    W = win.shape[1]
    return _row_sum(win[:, : W - d] * win[:, d:]).sum(
        axis=1, keepdims=True
    ).astype(jnp.int32)


def _stats_kernel(win_ref, distinct_ref, wmax_ref, rmax_ref, pair_ref, *,
                  B: int):
    win = win_ref[...]                       # (block_c, W, n) int32 0/1
    W = win.shape[1]
    anyt = _row_any(win)                     # (block_c, n) worker active?
    per_worker = _row_sum(win)               # (block_c, n)
    per_round = win.sum(axis=2)              # (block_c, W)
    distinct_ref[...] = anyt.sum(axis=1, keepdims=True).astype(jnp.int32)
    wmax_ref[...] = per_worker.max(axis=1, keepdims=True).astype(jnp.int32)
    rmax_ref[...] = per_round.max(axis=1, keepdims=True).astype(jnp.int32)
    pair = jnp.zeros((win.shape[0], 1), jnp.int32)
    for d in range(B, W):                    # static: W is tiny
        pair = pair + _pair_count(win, d)
    pair_ref[...] = pair


def _buffer_kernel(buf_ref, act_ref, cnt_ref, md_ref, pair_ref, *, B: int):
    buf = buf_ref[...]                       # (block_c, kh, n) int32 0/1
    kh = buf.shape[1]
    act_ref[...] = _row_any(buf).astype(jnp.int32)
    cnt_ref[...] = _row_sum(buf).astype(jnp.int32)
    if kh >= B:
        # rows that pair-violate (>= B apart) with the candidate row
        # the gate is about to append at offset kh
        md_ref[...] = _row_any(buf[:, : kh - B + 1]).astype(jnp.int32)
    else:
        md_ref[...] = jnp.zeros(act_ref.shape, jnp.int32)
    pair = jnp.zeros((buf.shape[0], 1), jnp.int32)
    for d in range(B, kh):
        pair = pair + _pair_count(buf, d)
    pair_ref[...] = pair


@functools.partial(jax.jit, static_argnames=("B", "block_c", "interpret"))
def window_stats(win: jax.Array, B: int, *, block_c: int,
                 interpret: bool = False):
    """Fused window statistics for lane-padded int32 windows.

    ``win``: (cells, W, n) int32 with 0/1 entries; ``cells`` must be a
    multiple of ``block_c`` and ``n`` a multiple of 128 (the
    ``ops.window_stats`` wrapper handles ragged shapes).  Returns
    ``(distinct, worker_max, round_max, pair)`` int32 ``(cells,)``
    arrays (``pair`` is a violation count, > 0 means inadmissible).
    """
    cells, W, n = win.shape
    if cells % block_c != 0:
        raise ValueError(f"cells={cells} not divisible by block_c={block_c}")
    grid = (cells // block_c,)
    # the staged simulator traces under x64, but Mosaic takes only
    # 32-bit block indices: trace the kernel with 32-bit defaults
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            functools.partial(_stats_kernel, B=B),
            grid=grid,
            in_specs=[pl.BlockSpec((block_c, W, n), lambda i: (i, 0, 0))],
            out_specs=[pl.BlockSpec((block_c, 1), lambda i: (i, 0))] * 4,
            out_shape=[jax.ShapeDtypeStruct((cells, 1), jnp.int32)] * 4,
            interpret=interpret,
            name="gate_window_stats",
        )(win)
    return tuple(o[:, 0] for o in outs)


@functools.partial(jax.jit, static_argnames=("B", "block_c", "interpret"))
def buffer_stats(buf: jax.Array, B: int, *, block_c: int,
                 interpret: bool = False):
    """Fixed-buffer statistics for the staged gate's per-round
    admission closures: one fused pass over the committed rows emits
    the worker maps (``bufact``/``bufcnt``/``mdmap`` — (cells, n)
    int32) plus the per-cell buffer-internal pair-violation count.
    Same layout contract as :func:`window_stats`.
    """
    cells, kh, n = buf.shape
    if cells % block_c != 0:
        raise ValueError(f"cells={cells} not divisible by block_c={block_c}")
    grid = (cells // block_c,)
    with jax.enable_x64(False):    # 32-bit block indices, as above
        act, cnt, md, pair = pl.pallas_call(
            functools.partial(_buffer_kernel, B=B),
            grid=grid,
            in_specs=[pl.BlockSpec((block_c, kh, n), lambda i: (i, 0, 0))],
            out_specs=[
                pl.BlockSpec((block_c, n), lambda i: (i, 0)),
                pl.BlockSpec((block_c, n), lambda i: (i, 0)),
                pl.BlockSpec((block_c, n), lambda i: (i, 0)),
                pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((cells, n), jnp.int32),
                jax.ShapeDtypeStruct((cells, n), jnp.int32),
                jax.ShapeDtypeStruct((cells, n), jnp.int32),
                jax.ShapeDtypeStruct((cells, 1), jnp.int32),
            ],
            interpret=interpret,
            name="gate_buffer_stats",
        )(buf)
    return act, cnt, md, pair
