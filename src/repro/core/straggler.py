"""Straggler models (paper §2.1) and sources.

Deterministic sliding-window models used for code design:

* ``BurstyModel(B, W, lam)`` — in every window of W consecutive rounds
  there are at most ``lam`` *distinct* stragglers (spatial correlation),
  and per worker the first/last straggling rounds inside the window are
  < B apart (temporal correlation: bursts of length <= B, one burst per
  window).
* ``ArbitraryModel(N, W, lam)`` — at most ``lam`` distinct stragglers
  per window and at most ``N`` straggling rounds per worker per window.
* ``PerRoundModel(s)`` — at most ``s`` stragglers in every round.

Stochastic ground truth:

* ``GilbertElliotSource`` — the 2-state chain of App. C, used both to
  sample straggler indicator matrices and to synthesize worker delay
  profiles for the runtime simulator.

Patterns are ``bool`` arrays of shape ``(rounds, n)`` with ``True`` =
straggler (``S_i(t)`` in the paper, transposed to time-major).

All models here are *closed under contiguous sub-patterns*: a pattern
that conforms keeps conforming when rows are removed from either end.
That closure is what makes single-suffix-window incremental admission
(``suffix_ok`` / ``ConformanceGate``) equivalent to re-validating every
window touching the new round, and it lets every check be a handful of
NumPy reductions instead of nested Python loops.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .backend import xp_of

__all__ = [
    "BurstyModel",
    "ArbitraryModel",
    "PerRoundModel",
    "MixtureModel",
    "WindowwiseOr",
    "RepCoverageModel",
    "DynamicClusterModel",
    "StochasticBlockModel",
    "ConformanceGate",
    "GilbertElliotSource",
    "TraceSource",
    "TraceModel",
    "LambdaTraceGenerator",
    "Scenario",
    "trace_library",
    "load_recorded_harness",
    "fit_gilbert_elliot",
    "suggest_parameters",
]


def _window_any(pat: np.ndarray, W: int) -> np.ndarray:
    """Per full length-W window: does worker i straggle at all in it?

    Returns bool of shape ``(max(rounds - W + 1, 1), n)``.  Trailing
    partial windows are row-subsets of the last full window, so (by
    sub-pattern closure) they never need separate checking.
    """
    rounds = pat.shape[0]
    if rounds <= W:
        return pat.any(axis=0, keepdims=True)
    cs = np.zeros((rounds + 1, pat.shape[1]), dtype=np.int64)
    np.cumsum(pat, axis=0, out=cs[1:])
    return (cs[W:] - cs[:-W]) > 0


def _window_sum(pat: np.ndarray, W: int) -> np.ndarray:
    """Per full length-W window: straggling-round count per worker."""
    rounds = pat.shape[0]
    if rounds <= W:
        return pat.sum(axis=0, keepdims=True)
    cs = np.zeros((rounds + 1, pat.shape[1]), dtype=np.int64)
    np.cumsum(pat, axis=0, out=cs[1:])
    return cs[W:] - cs[:-W]


def _spatial_min_drops(
    buf: np.ndarray, cand: np.ndarray, order: np.ndarray, lam: int
) -> np.ndarray:
    """Minimal k (dropping the k first candidates in ``order``) that
    brings the window's distinct-straggler count to <= ``lam``.

    Dropping a candidate removes a distinct straggler iff the worker is
    inactive in the committed ``buf`` rows, so the k-th prefix of the
    drop order fixes the count exactly when it contains enough
    buffer-inactive candidates — a cumulative count over the drop
    order.  Returns ``n + 1`` (sentinel) when no k can help (more
    buffer-active workers than ``lam``; impossible for a member that
    admitted those rows).
    """
    xp = xp_of(cand)
    n = cand.shape[1]
    if buf.shape[1]:
        bufact = buf.any(axis=1)
        newc = cand & ~bufact
        m0 = bufact.sum(axis=1)
    else:
        newc = cand
        m0 = 0
    S = newc.sum(axis=1)
    dn = S + m0 - lam                      # drops needed among newc
    cum = xp.cumsum(xp.take_along_axis(newc, order, axis=1), axis=1)
    ks = (cum >= xp.maximum(dn, 1)[:, None]).argmax(axis=1) + 1
    out = xp.where(dn <= 0, 0, ks)
    return xp.where(dn > S, n + 1, out)


def _must_drop_min(md: np.ndarray, rank: np.ndarray) -> np.ndarray:
    """Minimal k whose drop prefix covers every must-drop worker."""
    return xp_of(md).where(md, rank, -1).max(axis=1, initial=-1) + 1


def _prefix_upto_costliest(md, cand, cost):
    """Candidates at-or-before the costliest must-drop worker in the
    stable ascending-cost greedy order (cost ties break on the smaller
    index, so the costliest must-drop is (max cost, then max index)
    over ``md``).  Empty where ``md`` is empty."""
    xp = xp_of(cand)
    idx = xp.arange(cand.shape[1])[None, :]
    cstar = xp.where(md, cost, -xp.inf).max(axis=1)
    at_star = cost == cstar[:, None]
    istar = xp.where(md & at_star, idx, -1).max(axis=1)
    return cand & (
        (cost < cstar[:, None]) | (at_star & (idx <= istar[:, None]))
    )


#: Worker count above which the jax suffix checks route through the
#: Pallas ``gate_window`` kernel (one fused pass over the window buffer
#: instead of several XLA reductions).  Below it the plain jnp
#: reduction wins on launch overhead.
PALLAS_WINDOW_MIN_N = 128


def _any_rows(win):
    """``win.any(axis=1)`` unrolled over the (tiny, static) round axis.

    XLA CPU lowers middle-axis reductions of (cells, W, n) buffers to a
    strided loop an order of magnitude slower than the equivalent
    unrolled elementwise ops; W is a model window (<= a few rounds), so
    unrolling is free.  Matches numpy semantics exactly.
    """
    if win.shape[1] == 0:
        return xp_of(win).zeros(
            (win.shape[0], win.shape[2]), dtype=bool
        )
    out = win[:, 0]
    for r in range(1, win.shape[1]):
        out = out | win[:, r]
    return out


def _sum_rows(win):
    """``win.sum(axis=1)`` unrolled over the static round axis (see
    :func:`_any_rows`); bool input sums to integer counts (the
    backend's default int width)."""
    xp = xp_of(win)
    if win.shape[1] == 0:
        return xp.zeros((win.shape[0], win.shape[2]), dtype=int)
    out = win[:, 0] * 1
    for r in range(1, win.shape[1]):
        out = out + win[:, r]
    return out


def _window_stats(win, B: int):
    """Fused per-cell suffix-window reductions for the batched gate.

    ``win``: (cells, T, n) bool trailing windows.  Returns
    ``(distinct, worker_max, round_max, pair_bad)`` where ``distinct``
    counts workers active anywhere in the window, ``worker_max`` is the
    max per-worker straggling-round count, ``round_max`` the max
    per-round straggler count, and ``pair_bad`` flags a same-worker
    straggle pair >= ``B`` rounds apart (pass ``B >= T`` to skip).

    These four statistics are exactly what the windowed models'
    ``suffix_ok_batch`` verdicts reduce to; on the jax path with
    ``n >= PALLAS_WINDOW_MIN_N`` they come from the Pallas
    ``gate_window`` kernel (``src/repro/kernels/gate_window``).
    """
    xp = xp_of(win)
    if xp is not np and win.shape[-1] >= PALLAS_WINDOW_MIN_N:
        from repro.kernels.gate_window.ops import window_stats

        return window_stats(win, B)
    distinct = _any_rows(win).sum(axis=1)
    worker_max = _sum_rows(win).max(axis=1, initial=0)
    round_max = win.sum(axis=2).max(axis=1, initial=0)
    pair_bad = xp.zeros(win.shape[0], dtype=bool)
    for d in range(B, win.shape[1]):
        pair_bad = pair_bad | (win[:, :-d] & win[:, d:]).any(axis=(1, 2))
    return distinct, worker_max, round_max, pair_bad


def _buffer_stats(buf, B: int):
    """Fixed per-round statistics of a committed window buffer
    ``(cells, kh, n)``, computed once per round by the staged gate's
    specialized admission closures (``admit_fn_batch``):

    ``bufact[c, w]`` — worker straggles somewhere in the buffer;
    ``bufcnt[c, w]`` — its straggling-round count; ``mdmap[c, w]`` —
    a straggle in rows ``0..kh-B`` (would pair-violate, >= ``B``
    apart, with the incoming candidate row at offset ``kh``);
    ``pair_bad[c]`` — a >= ``B``-apart pair already inside the buffer.

    jax buffers at ``n >= PALLAS_WINDOW_MIN_N`` come from the Pallas
    ``gate_window.buffer_stats`` kernel in one fused pass.
    """
    xp = xp_of(buf)
    kh = buf.shape[1]
    if xp is not np and kh and buf.shape[-1] >= PALLAS_WINDOW_MIN_N:
        from repro.kernels.gate_window.ops import buffer_stats

        return buffer_stats(buf, B)
    bufact = _any_rows(buf)
    bufcnt = _sum_rows(buf)
    if kh >= B:
        mdmap = _any_rows(buf[:, : kh - B + 1])
    else:
        mdmap = xp.zeros_like(bufact)
    pair_bad = xp.zeros(buf.shape[0], dtype=bool)
    for d in range(B, kh):
        pair_bad = pair_bad | (buf[:, :-d] & buf[:, d:]).any(axis=(1, 2))
    return bufact, bufcnt, mdmap, pair_bad


class StragglerModel:
    """Interface: validate a full pattern or check incremental conformance."""

    #: True when the model's verdict is unchanged by dropping all-clear
    #: worker COLUMNS from the pattern (anything counting only straggler
    #: occurrences).  Lets the batched gate check only the active
    #: columns.  False for models tied to worker identity/layout
    #: (e.g. replication-group coverage).
    column_reducible: bool = False

    #: Closed-form minimal-drop solver for the batched wait-out gate,
    #: or None.  When every gate member defines it, the gate computes
    #: each cell's greedy wait-out in O(1) array passes instead of
    #: re-checking candidate variants.  Signature:
    #: ``min_drops_batch(buf, cand, rank, order) -> (rows,) int``
    #: where ``buf`` is this model's trailing committed window rows
    #: ``(rows, kh, n)``, ``cand``/``rank``/``order`` describe the
    #: candidate row and its fixed drop order, and the result is the
    #: smallest k such that dropping the k cheapest candidates makes
    #: the window admissible (``n + 1`` when impossible).  Soundness
    #: requires admissibility to be MONOTONE in the drop prefix, which
    #: holds for any model closed under removing stragglers.
    min_drops_batch = None

    def conforms(self, pattern: np.ndarray) -> bool:
        raise NotImplementedError

    def drops_lower_bound_fn_batch(self, buf, cost):
        """Rank-free lower bound on this member's minimal wait-out
        drops, specialized (like :meth:`admit_fn_batch`) to the round's
        fixed buffer and cost row: returns ``f(cand) -> (cells,) int``
        (``n + 1``-style sentinels where the member can never admit).
        The staged gate takes the min over alive members and retires
        that many cheapest candidates per ``while_loop`` iteration
        without re-checking after each one — sound because no member
        can admit before its own bound is dropped, and drops always
        proceed in cost order.  The default (0) is always valid, just
        slow when wait-outs run deep.
        """
        xp = xp_of(cost)
        return lambda cand: xp.zeros(cand.shape[0], dtype=xp.int64)

    def admit_fn_batch(self, buf):
        """Admission specialized to a FIXED committed buffer: returns
        ``f(cand) -> (cells,) bool`` verdicts for the window
        ``buf + cand``.  The staged gate builds one closure per member
        per round and calls it once per greedy iteration, so overrides
        precompute every buffer-only quantity up front; this default
        re-runs the full suffix check per call.
        """
        if buf.shape[1] == 0:
            return lambda cand: self.suffix_ok_batch(cand[:, None])
        xp = xp_of(buf)

        def f(cand):
            return self.suffix_ok_batch(
                xp.concatenate([buf, cand[:, None]], axis=1)
            )

        return f

    def suffix_ok(self, win: np.ndarray) -> bool:
        """Is the trailing window ``win`` (bool[<=W, n], last row = the
        candidate round) admissible, assuming every earlier window was
        validated when its own last row was committed?

        By sub-pattern closure this is just ``conforms`` on the suffix;
        windowed models override it with a single-window array check.
        """
        return self.conforms(win)

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        """Lockstep variant of ``suffix_ok``: ``win`` is ``(cells, T, n)``
        (one trailing window per grid cell, last row = each cell's
        candidate round); returns a ``(cells,)`` bool array.

        The fallback loops over cells; every model in this module
        overrides it with a single vectorized pass so the batched
        ``ConformanceGate`` (``core.kernel.GateKernel``) costs one array
        check per member per round regardless of the grid size.
        """
        return np.array([self.suffix_ok(w) for w in win], dtype=bool)

    def admits_round(self, history: np.ndarray, candidate: np.ndarray) -> bool:
        """Would appending ``candidate`` (bool[n]) keep the pattern valid?

        Only windows touching the new round need rechecking; models here
        are windowed, so validating the length-W suffix suffices.
        """
        w = self.window
        rounds = history.shape[0] if history.size else 0
        tail = history[max(0, rounds - (w - 1)) :] if rounds else None
        win = (
            np.concatenate([tail, candidate[None]], axis=0)
            if tail is not None and tail.shape[0]
            else candidate[None]
        )
        return self.suffix_ok(win)

    @property
    def window(self) -> int:
        raise NotImplementedError


@dataclass(frozen=True)
class PerRoundModel(StragglerModel):
    column_reducible = True

    s: int

    def conforms(self, pattern: np.ndarray) -> bool:
        return bool((pattern.sum(axis=1) <= self.s).all())

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        if not isinstance(win, np.ndarray):
            # jax path: the fused window reduction (Pallas at large n)
            _, _, round_max, _ = _window_stats(win, win.shape[1])
            return round_max <= self.s
        return (win.sum(axis=2) <= self.s).all(axis=1)

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        xp = xp_of(cand)
        k = xp.maximum(cand.sum(axis=1) - self.s, 0)
        if buf.shape[1]:
            # inside a multi-round window (WindowwiseOr member): the
            # committed rows must conform too — drops cannot fix them
            hist_ok = (buf.sum(axis=2) <= self.s).all(axis=1)
            k = xp.where(hist_ok, k, cand.shape[1] + 1)
        return k

    def admit_fn_batch(self, buf):
        if buf.shape[1] == 0:
            return lambda cand: cand.sum(axis=1) <= self.s
        hist_ok = (buf.sum(axis=2) <= self.s).all(axis=1)
        return lambda cand: hist_ok & (cand.sum(axis=1) <= self.s)

    def drops_lower_bound_fn_batch(self, buf, cost):
        xp = xp_of(cost)
        s, sent = self.s, cost.shape[1] + 1
        if buf.shape[1] == 0:
            return lambda cand: xp.maximum(cand.sum(axis=1) - s, 0)
        hist_ok = (buf.sum(axis=2) <= s).all(axis=1)
        return lambda cand: xp.where(
            hist_ok, xp.maximum(cand.sum(axis=1) - s, 0), sent
        )

    @property
    def window(self) -> int:
        return 1


@dataclass(frozen=True)
class BurstyModel(StragglerModel):
    column_reducible = True

    B: int
    W: int
    lam: int

    def __post_init__(self) -> None:
        if not (1 <= self.B <= self.W):
            raise ValueError(f"need 1 <= B <= W, got B={self.B}, W={self.W}")
        if self.lam < 0:
            raise ValueError("lam must be >= 0")

    def conforms(self, pattern: np.ndarray) -> bool:
        pat = np.asarray(pattern, dtype=bool)
        if pat.shape[0] == 0:
            return True
        # spatial: <= lam distinct stragglers in every window
        if int(_window_any(pat, self.W).sum(axis=1).max()) > self.lam:
            return False
        # temporal: per worker, straggling rounds in a common window span
        # < B.  Two rounds share a window iff they are <= W-1 apart, so a
        # violation is exactly a pair of straggles d in [B, W-1] apart.
        for d in range(self.B, min(self.W, pat.shape[0])):
            if (pat[:-d] & pat[d:]).any():
                return False
        return True

    def suffix_ok(self, win: np.ndarray) -> bool:
        if int(win.any(axis=0).sum()) > self.lam:
            return False
        T = win.shape[0]
        idx = np.arange(T)[:, None]
        first = np.where(win, idx, T).min(axis=0)
        last = np.where(win, idx, -1).max(axis=0)
        # inactive workers give last - first = -1 - T < B automatically
        return bool((last - first < self.B).all())

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        if not isinstance(win, np.ndarray):
            distinct, _, _, pair_bad = _window_stats(win, self.B)
            return (distinct <= self.lam) & ~pair_bad
        ok = win.any(axis=1).sum(axis=1) <= self.lam
        # temporal: a violation is exactly a same-worker straggle pair
        # >= B rounds apart (cheap bool ops; mirrors ``conforms``)
        for d in range(self.B, win.shape[1]):
            ok &= ~(win[:, :-d, :] & win[:, d:, :]).any(axis=(1, 2))
        return ok

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        xp = xp_of(cand)
        k = _spatial_min_drops(buf, cand, order, self.lam)
        kh = buf.shape[1]
        if kh >= self.B:
            # candidates straggling >= B rounds before the new row can
            # only be fixed by dropping them (window rows 0..kh-B)
            md = cand & buf[:, : kh - self.B + 1].any(axis=1)
            k = xp.maximum(k, _must_drop_min(md, rank))
            # a straggle pair >= B apart WITHIN the committed rows can
            # never be fixed by dropping candidates.  Inside a
            # WindowwiseOr the window may have been admitted through
            # another arm, so this does happen (top-level members are
            # alive-tracked and never see it).
            bad = xp.zeros(cand.shape[0], dtype=bool)
            for d in range(self.B, kh):
                bad = bad | (buf[:, :-d] & buf[:, d:]).any(axis=(1, 2))
            k = xp.where(bad, cand.shape[1] + 1, k)
        return k

    def admit_fn_batch(self, buf):
        if buf.shape[1] == 0:
            return lambda cand: cand.sum(axis=1) <= self.lam
        bufact, _, mdmap, pair_bad = _buffer_stats(buf, self.B)
        base = bufact.sum(axis=1)
        ok_fixed = ~pair_bad

        def f(cand):
            distinct = base + (cand & ~bufact).sum(axis=1)
            return (
                (distinct <= self.lam)
                & ok_fixed
                & ~(cand & mdmap).any(axis=1)
            )

        return f

    def drops_lower_bound_fn_batch(self, buf, cost):
        xp = xp_of(cost)
        lam, sent = self.lam, cost.shape[1] + 1
        if buf.shape[1] == 0:
            return lambda cand: xp.maximum(cand.sum(axis=1) - lam, 0)
        bufact, _, mdmap, pair_bad = _buffer_stats(buf, self.B)
        base = bufact.sum(axis=1)

        def f(cand):
            # spatial shortfall: each drop removes at most one distinct
            # straggler from the window
            distinct = base + (cand & ~bufact).sum(axis=1)
            k = xp.maximum(distinct - lam, 0)
            # every candidate at-or-before the costliest must-drop
            # worker is dropped before this member can admit
            md = cand & mdmap
            k = xp.maximum(
                k,
                (cand & _prefix_upto_costliest(md, cand, cost)).sum(axis=1),
            )
            return xp.where(pair_bad, sent, k)

        return f

    @property
    def window(self) -> int:
        return self.W


@dataclass(frozen=True)
class ArbitraryModel(StragglerModel):
    column_reducible = True

    N: int
    W: int
    lam: int

    def conforms(self, pattern: np.ndarray) -> bool:
        pat = np.asarray(pattern, dtype=bool)
        if pat.shape[0] == 0:
            return True
        if int(_window_any(pat, self.W).sum(axis=1).max()) > self.lam:
            return False
        return int(_window_sum(pat, self.W).max()) <= self.N

    def suffix_ok(self, win: np.ndarray) -> bool:
        if int(win.any(axis=0).sum()) > self.lam:
            return False
        return int(win.sum(axis=0).max(initial=0)) <= self.N

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        if not isinstance(win, np.ndarray):
            distinct, worker_max, _, _ = _window_stats(win, win.shape[1])
            return (distinct <= self.lam) & (worker_max <= self.N)
        spatial = win.any(axis=1).sum(axis=1) <= self.lam
        return spatial & (win.sum(axis=1).max(axis=1, initial=0) <= self.N)

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        xp = xp_of(cand)
        k = _spatial_min_drops(buf, cand, order, self.lam)
        # candidates already at N straggling rounds in the window must
        # be dropped (with an empty buffer this still catches N == 0)
        bufcnt = buf.sum(axis=1) if buf.shape[1] else 0
        md = cand & (bufcnt >= self.N)
        k = xp.maximum(k, _must_drop_min(md, rank))
        if buf.shape[1]:
            # a worker already PAST N in the committed rows cannot be
            # fixed by dropping candidates (reachable only inside a
            # WindowwiseOr; top-level members are alive-tracked)
            bad = (bufcnt > self.N).any(axis=1)
            k = xp.where(bad, cand.shape[1] + 1, k)
        return k

    def admit_fn_batch(self, buf):
        if buf.shape[1] == 0:
            if self.N >= 1:
                return lambda cand: cand.sum(axis=1) <= self.lam
            return lambda cand: (
                (cand.sum(axis=1) <= self.lam) & ~cand.any(axis=1)
            )
        bufact, bufcnt, _, _ = _buffer_stats(buf, buf.shape[1] + 1)
        base = bufact.sum(axis=1)
        md = bufcnt >= self.N
        ok_fixed = bufcnt.max(axis=1, initial=0) <= self.N

        def f(cand):
            distinct = base + (cand & ~bufact).sum(axis=1)
            return (
                (distinct <= self.lam)
                & ok_fixed
                & ~(cand & md).any(axis=1)
            )

        return f

    def drops_lower_bound_fn_batch(self, buf, cost):
        xp = xp_of(cost)
        lam, N, sent = self.lam, self.N, cost.shape[1] + 1
        if buf.shape[1] == 0:
            if N == 0:
                # every candidate must go
                return lambda cand: cand.sum(axis=1)
            return lambda cand: xp.maximum(cand.sum(axis=1) - lam, 0)
        bufact, bufcnt, _, _ = _buffer_stats(buf, buf.shape[1] + 1)
        base = bufact.sum(axis=1)
        mdmap = bufcnt >= N
        bad = (bufcnt > N).any(axis=1)

        def f(cand):
            distinct = base + (cand & ~bufact).sum(axis=1)
            k = xp.maximum(distinct - lam, 0)
            md = cand & mdmap
            k = xp.maximum(
                k,
                (cand & _prefix_upto_costliest(md, cand, cost)).sum(axis=1),
            )
            return xp.where(bad, sent, k)

        return f

    @property
    def window(self) -> int:
        return self.W


@dataclass(frozen=True)
class MixtureModel(StragglerModel):
    """Pattern is admissible if it conforms to ANY member model GLOBALLY.

    Used for M-SGC (bursty OR arbitrary, Prop 3.2).  NOTE: a naive
    per-round OR of ``admits_round`` is WRONG — it can weave rounds that
    alternate between members so the final pattern satisfies neither
    model.  Incremental admission must track which members are still
    globally valid; use ``ConformanceGate`` for that.
    """

    members: tuple

    def conforms(self, pattern: np.ndarray) -> bool:
        return any(m.conforms(pattern) for m in self.members)

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        raise TypeError(
            "MixtureModel admission is stateful; use ConformanceGate "
            "(or the batched GateKernel, which tracks members separately)"
        )

    def admits_round(self, history: np.ndarray, candidate: np.ndarray) -> bool:
        raise TypeError(
            "MixtureModel admission is stateful; use ConformanceGate"
        )

    @property
    def window(self) -> int:
        return max(m.window for m in self.members)


@dataclass(frozen=True)
class RepCoverageModel(StragglerModel):
    """App. G: with the GC-Rep code, a round is tolerable iff every
    replication group of size (s+1) keeps at least one non-straggler —
    a strict superset of the <= s-per-round patterns."""

    n: int
    s: int

    def conforms(self, pattern: np.ndarray) -> bool:
        g = self.s + 1
        groups = pattern.reshape(pattern.shape[0], self.n // g, g)
        return bool((~groups.all(axis=2)).all())

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        g = self.s + 1
        groups = win.reshape(win.shape[0], win.shape[1], self.n // g, g)
        return (~groups.all(axis=3)).all(axis=(1, 2))

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        # a fully-straggling replication group is fixed by dropping its
        # cheapest member, i.e. once the drop prefix reaches the
        # group's minimum rank
        xp = xp_of(cand)
        g = self.s + 1
        rows = cand.shape[0]
        candg = cand.reshape(rows, self.n // g, g)
        full = candg.all(axis=2)
        minr = xp.where(candg, rank.reshape(rows, self.n // g, g), self.n).min(
            axis=2
        )
        return xp.where(full, minr + 1, 0).max(axis=1, initial=0)

    def admit_fn_batch(self, buf):
        g = self.s + 1

        def f(cand):
            groups = cand.reshape(cand.shape[0], self.n // g, g)
            return ~groups.all(axis=2).any(axis=1)

        return f

    def drops_lower_bound_fn_batch(self, buf, cost):
        # every fully-straggling group needs one (disjoint) drop
        g = self.s + 1

        def f(cand):
            groups = cand.reshape(cand.shape[0], self.n // g, g)
            return groups.all(axis=2).sum(axis=1)

        return f

    @property
    def window(self) -> int:
        return 1


@dataclass(frozen=True)
class WindowwiseOr(StragglerModel):
    """Every length-W window must satisfy at least ONE member predicate
    (members restricted to that window) — Prop 3.1's tolerance class for
    SR-SGC: each window is bursty-conforming OR has <= s stragglers per
    round.  Window predicates are local, so suffix-based incremental
    admission is sound.  Members must be closed under contiguous
    sub-patterns (all models in this module are), which lets both
    ``conforms`` and ``suffix_ok`` check only full windows.
    """

    members: tuple
    W: int

    @property
    def column_reducible(self) -> bool:
        return all(m.column_reducible for m in self.members)

    def conforms(self, pattern: np.ndarray) -> bool:
        pat = np.asarray(pattern, dtype=bool)
        rounds = pat.shape[0]
        if rounds == 0:
            return True
        for j in range(max(rounds - self.W, 0) + 1):
            win = pat[j : j + self.W]
            if not any(m.conforms(win) for m in self.members):
                return False
        return True

    def suffix_ok(self, win: np.ndarray) -> bool:
        return any(m.conforms(win) for m in self.members)

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        # member suffix_ok == conforms on a single (<= W)-round window
        # for every model in this module, so the OR vectorizes directly
        out = xp_of(win).zeros(win.shape[0], dtype=bool)
        for m in self.members:
            out = out | m.suffix_ok_batch(win)
        return out

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        # the window admits when ANY member does: minimum over members
        # (each sees the full Or-window rows)
        xp = xp_of(cand)
        out = None
        for m in self.members:
            km = m.min_drops_batch(buf, cand, rank, order)
            out = km if out is None else xp.minimum(out, km)
        return out

    def drops_lower_bound_fn_batch(self, buf, cost):
        # admits via ANY member: the true minimum is the min over
        # member minima, so the bound is the min over member bounds
        xp = xp_of(cost)
        fns = [m.drops_lower_bound_fn_batch(buf, cost) for m in self.members]

        def f(cand):
            out = None
            for g in fns:
                km = g(cand)
                out = km if out is None else xp.minimum(out, km)
            return out

        return f

    def admit_fn_batch(self, buf):
        fns = [m.admit_fn_batch(buf) for m in self.members]

        def f(cand):
            out = None
            for g in fns:
                r = g(cand)
                out = r if out is None else out | r
            return out

        return f

    @property
    def window(self) -> int:
        return self.W


# ---------------------------------------------------------------------------
# cluster-capacity models (scenario-sweep baselines)
# ---------------------------------------------------------------------------


def _round_robin_clusters(prev, C: int):
    """Cluster id per worker from the previous round's straggler row:
    previous stragglers are dealt round-robin across the ``C`` clusters
    first (in worker order), then the remaining workers fill in worker
    order — so bursty stragglers land at most ``ceil(S/C)`` per
    cluster.  ``prev``: (..., n) bool; returns ints of the same shape.
    Pure cumulative sums, no sort (XLA-CPU sort/scatter is a known
    cliff inside the scanned round loop)."""
    xp = xp_of(prev)
    strag = xp.cumsum(prev, axis=-1)
    total = strag[..., -1:]
    other = xp.cumsum(~prev, axis=-1)
    rank = xp.where(prev, strag - 1, total + other - 1)
    return rank % C


def _cluster_counts_ok(strag, cid, C: int, s):
    """Does every cluster keep <= ``s`` stragglers?  ``strag`` is
    (..., n) bool, ``cid`` broadcasts against it; reduces the worker
    axis.  The loop over ``C`` is static (a per-spec cost), so the
    check stays a handful of elementwise ops under jit/vmap."""
    xp = xp_of(strag)
    ok = None
    for c in range(C):
        ok_c = (strag & (cid == c)).sum(axis=-1) <= s
        ok = ok_c if ok is None else ok & ok_c
    return ok


def _cluster_min_drops(cand, cid, C: int, s, order):
    """Minimal k such that dropping the k first candidates in ``order``
    brings every cluster's straggler count to <= ``s``: per cluster,
    the position in the global drop order where its dropped count
    reaches its shortfall (max over clusters; 0 when none is over).
    Every over-count is fixable by dropping that cluster's own
    candidates, so no sentinel is needed."""
    xp = xp_of(cand)
    cid = xp.broadcast_to(cid, cand.shape)
    cid_o = xp.take_along_axis(cid, order, axis=1)
    cand_o = xp.take_along_axis(cand, order, axis=1)
    out = None
    for c in range(C):
        inc = cand_o & (cid_o == c)
        need = xp.maximum(inc.sum(axis=1) - s, 0)
        cum = xp.cumsum(inc, axis=1)
        kc = (cum >= xp.maximum(need, 1)[:, None]).argmax(axis=1) + 1
        kc = xp.where(need > 0, kc, 0)
        out = kc if out is None else xp.maximum(out, kc)
    return out


def _cluster_drops_lower_bound(cand, cid, C: int, s):
    """Sum of per-cluster shortfalls — a valid lower bound on the drops
    any order needs (each drop decrements exactly one cluster)."""
    xp = xp_of(cand)
    out = None
    for c in range(C):
        kc = xp.maximum((cand & (cid == c)).sum(axis=1) - s, 0)
        out = kc if out is None else out + kc
    return out


@dataclass(frozen=True)
class DynamicClusterModel(StragglerModel):
    """Per-round tolerability of dynamic-clustering GC (Buyukates et
    al., arXiv:2011.01922): every round the ``n`` workers are
    re-partitioned into ``C`` clusters from the PREVIOUS round's
    straggler row (:func:`_round_robin_clusters` — past stragglers are
    spread evenly), and the round conforms iff every cluster keeps
    <= ``s`` stragglers.  With no history (round 1 / an all-clear
    previous row) the assignment degenerates to the identity layout
    ``worker i -> cluster i mod C``.

    ``window == 2``: a suffix window's first row fixes the assignment,
    its last row is the candidate — which makes the history dependence
    expressible through the gate's standard rolling-buffer protocol.
    Committed rows need no rechecking (they were admitted under their
    own assignment), so every hook below validates the LAST row only.
    Tied to worker layout, hence not ``column_reducible``.
    """

    n: int
    C: int
    s: int

    def __post_init__(self) -> None:
        if not 1 <= self.C <= self.n:
            raise ValueError(f"need 1 <= C <= n, got C={self.C}")
        if self.n % self.C:
            raise ValueError("DynamicClusterModel requires C | n")
        if not 0 <= self.s < self.n // self.C:
            raise ValueError(
                f"need 0 <= s < n/C = {self.n // self.C}, got s={self.s}"
            )

    def _cid(self, prev):
        return _round_robin_clusters(prev, self.C)

    def conforms(self, pattern: np.ndarray) -> bool:
        pat = np.asarray(pattern, dtype=bool)
        if pat.shape[0] == 0:
            return True
        prev = np.zeros_like(pat)
        prev[1:] = pat[:-1]
        return bool(
            _cluster_counts_ok(pat, self._cid(prev), self.C, self.s).all()
        )

    def suffix_ok(self, win: np.ndarray) -> bool:
        prev = win[-2] if win.shape[0] >= 2 else np.zeros_like(win[-1])
        return bool(
            _cluster_counts_ok(win[-1], self._cid(prev), self.C, self.s)
        )

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        xp = xp_of(win)
        prev = (
            win[:, -2] if win.shape[1] >= 2 else xp.zeros_like(win[:, -1])
        )
        return _cluster_counts_ok(win[:, -1], self._cid(prev), self.C,
                                  self.s)

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        xp = xp_of(cand)
        prev = buf[:, -1] if buf.shape[1] else xp.zeros_like(cand)
        return _cluster_min_drops(cand, self._cid(prev), self.C, self.s,
                                  order)

    def admit_fn_batch(self, buf):
        xp = xp_of(buf)
        if buf.shape[1]:
            cid = self._cid(buf[:, -1])
        else:
            cid = xp.arange(self.n) % self.C  # zero history: identity
        return lambda cand: _cluster_counts_ok(cand, cid, self.C, self.s)

    def drops_lower_bound_fn_batch(self, buf, cost):
        xp = xp_of(cost)
        if buf.shape[1]:
            cid = self._cid(buf[:, -1])
        else:
            cid = xp.arange(self.n) % self.C
        return lambda cand: _cluster_drops_lower_bound(cand, cid, self.C,
                                                       self.s)

    @property
    def window(self) -> int:
        return 2


@dataclass(frozen=True)
class StochasticBlockModel(StragglerModel):
    """Per-round tolerability of stochastic-block GC (Charles &
    Papailiopoulos, arXiv:1805.10378): a FIXED random partition of the
    ``n`` workers into ``C`` equal blocks (drawn from the
    gradient-code seed by the scheme), and a round conforms iff every
    block keeps <= ``s`` stragglers.  ``blocks`` is the length-n tuple
    of block ids — a tuple so the frozen dataclass stays hashable;
    the array view is cached at construction.  Worker-layout-bound,
    hence not ``column_reducible``; window 1 (memoryless)."""

    n: int
    C: int
    s: int
    blocks: tuple

    def __post_init__(self) -> None:
        if len(self.blocks) != self.n:
            raise ValueError("blocks must assign every worker")
        if not 0 <= self.s < self.n // self.C:
            raise ValueError(
                f"need 0 <= s < n/C = {self.n // self.C}, got s={self.s}"
            )
        object.__setattr__(
            self, "_bl", np.asarray(self.blocks, dtype=np.int64)
        )

    def conforms(self, pattern: np.ndarray) -> bool:
        pat = np.asarray(pattern, dtype=bool)
        if pat.shape[0] == 0:
            return True
        return bool(
            _cluster_counts_ok(pat, self._bl, self.C, self.s).all()
        )

    def suffix_ok(self, win: np.ndarray) -> bool:
        return self.conforms(win)

    def suffix_ok_batch(self, win: np.ndarray) -> np.ndarray:
        return _cluster_counts_ok(win, self._bl, self.C, self.s).all(axis=1)

    def min_drops_batch(self, buf, cand, rank, order) -> np.ndarray:
        return _cluster_min_drops(cand, self._bl, self.C, self.s, order)

    def admit_fn_batch(self, buf):
        return lambda cand: _cluster_counts_ok(cand, self._bl, self.C,
                                               self.s)

    def drops_lower_bound_fn_batch(self, buf, cost):
        return lambda cand: _cluster_drops_lower_bound(cand, self._bl,
                                                       self.C, self.s)

    @property
    def window(self) -> int:
        return 1


class _ModelTracker:
    """O(1)-per-round rolling conformance state for one windowed model.

    Keeps only the last ``window - 1`` committed rounds in a fixed
    ring-shifted buffer; ``admits`` is a single vectorized suffix-window
    check instead of re-scanning (and re-concatenating) the whole
    history every round.
    """

    def __init__(self, model: StragglerModel, n: int):
        self.model = model
        self.w = model.window
        self.buf = np.zeros((self.w - 1, n), dtype=bool)
        self.filled = 0  # committed rounds, saturating at w - 1

    def admits(self, candidate: np.ndarray) -> bool:
        k = min(self.filled, self.w - 1)
        if k:
            win = np.concatenate(
                [self.buf[self.w - 1 - k :], candidate[None]], axis=0
            )
        else:
            win = candidate[None]
        return self.model.suffix_ok(win)

    def commit(self, candidate: np.ndarray) -> None:
        if self.w > 1:
            self.buf[:-1] = self.buf[1:]
            self.buf[-1] = candidate
        if self.filled < self.w - 1:
            self.filled += 1


class ConformanceGate:
    """Stateful Remark-2.3 wait-out gate.

    Maintains the effective straggler history and, for mixture models,
    which members are still globally satisfiable (a member that fails
    once is dead forever — conformance violations are permanent).
    ``admit(candidate)`` returns True and commits the round if the
    pattern stays admissible; the caller waits out all stragglers (and
    calls ``admit(zeros)``, which always succeeds) otherwise.

    Per-member state is a rolling ``_ModelTracker``, so each round costs
    O(window * n) array ops regardless of how long the run is.
    """

    def __init__(self, model: StragglerModel, n: int):
        if isinstance(model, MixtureModel):
            self.members = list(model.members)
        else:
            self.members = [model]
        self.alive = [True] * len(self.members)
        self.n = n
        self._trackers = [_ModelTracker(m, n) for m in self.members]
        self._rows: list[np.ndarray] = []
        self._history_cache: np.ndarray | None = None

    @property
    def history(self) -> np.ndarray:
        """Effective pattern committed so far, (rounds, n) bool."""
        if self._history_cache is None:
            if self._rows:
                self._history_cache = np.array(self._rows, dtype=bool)
            else:
                self._history_cache = np.zeros((0, self.n), dtype=bool)
        return self._history_cache

    def _commit(self, row: np.ndarray) -> None:
        row = row.copy()
        self._rows.append(row)
        self._history_cache = None
        for tr in self._trackers:
            tr.commit(row)

    def admit(self, candidate: np.ndarray) -> bool:
        ok = [
            i
            for i, tr in enumerate(self._trackers)
            if self.alive[i] and tr.admits(candidate)
        ]
        if not ok:
            return False
        self.alive = [i in ok for i in range(len(self.members))]
        self._commit(candidate)
        return True

    def force(self, candidate: np.ndarray) -> None:
        """Commit a round unconditionally (used for the all-clear row
        after a wait-out; zeros can never violate any model)."""
        assert not candidate.any()
        self._commit(candidate)

    def admit_partial(
        self, candidate: np.ndarray, cost: np.ndarray
    ) -> tuple[np.ndarray, list[int]]:
        """Selective wait-out (Remark 2.3, refined).

        Greedily waits out (drops from the straggler set) the cheapest
        violating workers until the remaining set is admissible.  The
        master pays ``max(cost[waited])`` extra round time but keeps the
        effective pattern inside the design envelope with minimal
        waiting — strictly better than the App-J "wait out all the
        workers" fallback, which is the degenerate end of this loop.

        Returns (effective straggler set, waited worker ids); commits.
        """
        cand = candidate.copy()
        waited: list[int] = []
        while cand.any():
            ok = [
                i
                for i, tr in enumerate(self._trackers)
                if self.alive[i] and tr.admits(cand)
            ]
            if ok:
                self.alive = [i in ok for i in range(len(self.members))]
                self._commit(cand)
                return cand, waited
            on = np.flatnonzero(cand)
            drop = on[np.argmin(cost[on])]
            cand[drop] = False
            waited.append(int(drop))
        self._commit(cand)
        return cand, waited


# ---------------------------------------------------------------------------
# sources of ground-truth straggling / delays
# ---------------------------------------------------------------------------


@dataclass
class GilbertElliotSource:
    """2-state GE chain per worker (App. C).

    ``p_ns``: P(non-straggler -> straggler); ``p_sn``: P(straggler ->
    non-straggler).  Stationary straggler fraction = p_ns/(p_ns+p_sn).
    Delays: non-straggler times ~ base * (1 + jitter), straggler times
    ~ base * slow_factor * (1 + jitter) — a long right tail mirroring
    Fig. 1(c).
    """

    n: int
    p_ns: float = 0.05
    p_sn: float = 0.6
    base_time: float = 1.0
    slow_factor: float = 4.0
    jitter: float = 0.08
    # Fig. 16 slope: extra seconds per unit of normalized load.  In the
    # paper's Lambda cluster the per-round time is dominated by a fixed
    # overhead (~base_time); full-load compute adds ~8x base on top.
    compute_scale: float = 8.0
    seed: int = 0

    @property
    def alpha(self) -> float:
        return self.base_time * self.compute_scale

    def sample_pattern(self, rounds: int) -> np.ndarray:
        # NB: the RNG draw ORDER (one init draw, then one (rounds, n)
        # block in C order) is a compatibility contract — see
        # tests/test_determinism.py before reordering anything here.
        rng = np.random.default_rng(self.seed)
        state = rng.random(self.n) < self.p_ns / (self.p_ns + self.p_sn)
        flips = rng.random((rounds, self.n))
        out = np.zeros((rounds, self.n), dtype=bool)
        for t in range(rounds):
            out[t] = state
            state = np.where(state, flips[t] >= self.p_sn, flips[t] < self.p_ns)
        return out

    def sample_delays(self, rounds: int) -> np.ndarray:
        """(rounds, n) seconds at the reference load 1/n."""
        rng = np.random.default_rng(self.seed + 1)
        pat = self.sample_pattern(rounds)
        base = self.base_time * (1.0 + self.jitter * rng.standard_normal((rounds, self.n)) ** 2)
        slow = 1.0 + (self.slow_factor - 1.0) * rng.random((rounds, self.n))
        return np.where(pat, base * np.maximum(slow, 1.0), base)


@dataclass
class TraceSource:
    """Replays a recorded (rounds, n) delay matrix (App. J reference profile)."""

    delays: np.ndarray

    def sample_delays(self, rounds: int) -> np.ndarray:
        if rounds > self.delays.shape[0]:
            reps = -(-rounds // self.delays.shape[0])
            return np.tile(self.delays, (reps, 1))[:rounds]
        return self.delays[:rounds]


@dataclass
class TraceModel:
    """Replays a RECORDED per-round straggler pattern as a delay
    source: the bool ``pattern`` (rounds, n) tiles cyclically to any
    horizon (like :class:`TraceSource` does for raw delays), straggler
    slots draw a heavy-tailed slow multiplier, everything else sits at
    ``base_time`` plus jitter.  This is how captured cluster logs (or
    the synthetic recordings shipped in :func:`trace_library`) feed the
    runtime simulator while keeping their exact straggler structure.
    """

    pattern: np.ndarray
    base_time: float = 1.0
    slow_factor: float = 4.0
    jitter: float = 0.05
    compute_scale: float = 8.0
    seed: int = 0
    #: optional measured per-(round, worker) wall-clock seconds from a
    #: real harness run (NaN where no result arrived); carried for
    #: provenance/validation, never consulted by ``sample_delays``
    timings: np.ndarray | None = None
    #: optional supervision event log from an ELASTIC harness run —
    #: ``{"round", "worker", "kind"}`` dicts (kinds: death / respawn /
    #: rejoin / lost / degrade); presence upgrades the recording to
    #: schema v2.  Carried for provenance, never consulted by
    #: ``sample_delays``.
    events: list | None = None

    @property
    def n(self) -> int:
        return self.pattern.shape[1]

    @property
    def alpha(self) -> float:
        return self.base_time * self.compute_scale

    def sample_pattern(self, rounds: int) -> np.ndarray:
        pat = np.asarray(self.pattern, dtype=bool)
        if rounds > pat.shape[0]:
            reps = -(-rounds // pat.shape[0])
            return np.tile(pat, (reps, 1))[:rounds]
        return pat[:rounds]

    def sample_delays(self, rounds: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed)
        pat = self.sample_pattern(rounds)
        base = self.base_time * (
            1.0 + self.jitter * rng.standard_normal((rounds, self.n)) ** 2
        )
        slow = 1.0 + (self.slow_factor - 1.0) * rng.random((rounds, self.n))
        return np.where(pat, base * np.maximum(slow, 1.0), base)

    # -- stable JSON recording schema (versions 1 and 2) -----------------
    #
    #   {"kind": "trace-model", "version": 1, "n", "rounds",
    #    "stragglers": [[worker ids straggling in round t], ...],
    #    "base_time", "slow_factor", "jitter", "compute_scale", "seed",
    #    "timings": null | [[seconds-or-null per worker], ...]}
    #
    # Straggler rows are id lists (patterns are sparse); timings use
    # null for NaN (JSON has no NaN).  Version 2 adds one key to v1:
    # "events" — the elastic harness's supervision log
    # ([{"round", "worker", "kind"}, ...]); recordings without events
    # keep serializing as v1 so checked-in v1 files stay byte-stable.
    # ``from_json(to_json())`` is exact for both versions.

    _REQUIRED_FIELDS = ("n", "rounds", "stragglers", "base_time",
                        "slow_factor", "jitter", "compute_scale", "seed")

    def to_json(self, *, indent: int | None = None) -> str:
        """Serialize the recording (see the schema comment above)."""
        pat = np.asarray(self.pattern, dtype=bool)
        timings = None
        if self.timings is not None:
            tim = np.asarray(self.timings, dtype=np.float64)
            timings = [
                [None if np.isnan(v) else float(v) for v in row]
                for row in tim
            ]
        obj = {
            "kind": "trace-model",
            "version": 2 if self.events is not None else 1,
            "n": int(pat.shape[1]),
            "rounds": int(pat.shape[0]),
            "stragglers": [np.flatnonzero(row).tolist() for row in pat],
            "base_time": float(self.base_time),
            "slow_factor": float(self.slow_factor),
            "jitter": float(self.jitter),
            "compute_scale": float(self.compute_scale),
            "seed": int(self.seed),
            "timings": timings,
        }
        if self.events is not None:
            obj["events"] = self.events
        return json.dumps(obj, indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "TraceModel":
        """Inverse of :meth:`to_json` (exact round-trip).

        Validates the payload up front and raises ``ValueError`` with a
        descriptive message — not a ``KeyError``/``IndexError`` — on a
        foreign payload, an unknown schema version, missing fields, or
        malformed straggler/timing rows."""
        obj = json.loads(text)
        if not isinstance(obj, dict) or obj.get("kind") != "trace-model":
            raise ValueError(
                f"not a trace-model recording: kind={obj.get('kind')!r}"
                if isinstance(obj, dict)
                else f"not a trace-model recording: {type(obj).__name__}"
            )
        version = obj.get("version")
        if version not in (1, 2):
            raise ValueError(
                f"unsupported trace-model schema version {version!r} "
                "(this reader supports versions 1 and 2)"
            )
        missing = [k for k in cls._REQUIRED_FIELDS if k not in obj]
        if missing:
            raise ValueError(
                f"trace-model v{version} recording is missing "
                f"field(s): {missing}"
            )
        rounds, n = int(obj["rounds"]), int(obj["n"])
        stragglers = obj["stragglers"]
        if not isinstance(stragglers, list) or len(stragglers) != rounds:
            raise ValueError(
                f"malformed stragglers: expected {rounds} rows, got "
                f"{len(stragglers) if isinstance(stragglers, list) else type(stragglers).__name__}"
            )
        pat = np.zeros((rounds, n), dtype=bool)
        for t, ids in enumerate(stragglers):
            if not isinstance(ids, list) or not all(
                isinstance(i, int) and 0 <= i < n for i in ids
            ):
                raise ValueError(
                    f"malformed straggler row {t + 1}: want worker ids in "
                    f"[0, {n}), got {ids!r}"
                )
            pat[t, ids] = True
        timings = obj.get("timings")
        if timings is not None:
            if not isinstance(timings, list) or len(timings) != rounds:
                raise ValueError(
                    f"malformed timings: expected {rounds} rows, got "
                    f"{len(timings) if isinstance(timings, list) else type(timings).__name__}"
                )
            for t, row in enumerate(timings):
                if (not isinstance(row, list) or len(row) != n
                        or not all(v is None
                                   or isinstance(v, (int, float))
                                   for v in row)):
                    raise ValueError(
                        f"malformed timing row {t + 1}: want {n} "
                        f"seconds-or-null entries, got {row!r}"
                    )
            timings = np.asarray([
                [np.nan if v is None else float(v) for v in row]
                for row in timings
            ], dtype=np.float64)
        events = obj.get("events") if version >= 2 else None
        if events is not None:
            if not isinstance(events, list) or not all(
                isinstance(ev, dict) and "kind" in ev for ev in events
            ):
                raise ValueError(
                    "malformed events: want a list of dicts with a "
                    "'kind' key"
                )
        return cls(
            pattern=pat,
            base_time=float(obj["base_time"]),
            slow_factor=float(obj["slow_factor"]),
            jitter=float(obj["jitter"]),
            compute_scale=float(obj["compute_scale"]),
            seed=int(obj["seed"]),
            timings=timings,
            events=events,
        )


@dataclass
class LambdaTraceGenerator:
    """AWS-Lambda-like delay synthesizer for the scenario sweeps.

    Captures the serverless-cluster features the GE chain alone does
    not: **cold starts** (a fraction of workers pays a one-off penalty
    on their first round), **platform events** (whole-fleet slowdown
    rounds), and **heterogeneous workers** — per-worker speed factors
    drawn lognormal with sigma ``hetero``, which scale both the base
    latency and the load slope.  :meth:`worker_alpha` exposes that
    slope as a per-worker ``(n,)`` alpha vector; the simulation engines
    accept it anywhere a scalar alpha is accepted (``time = ref +
    (L - 1/n) * alpha_i``), so slow workers get slower *faster* as the
    normalized load grows.  Transient straggling follows the same
    2-state chain as :class:`GilbertElliotSource`.
    """

    n: int
    seed: int = 0
    base_time: float = 1.0
    jitter: float = 0.06
    cold_start: float = 2.5
    cold_fraction: float = 0.7
    p_ns: float = 0.05
    p_sn: float = 0.65
    slow_factor: float = 5.0
    hetero: float = 0.0
    p_event: float = 0.02
    event_factor: float = 2.0
    compute_scale: float = 8.0
    #: fix this to share ONE fleet (one speed draw) across several
    #: generators with different trace seeds; defaults to ``seed + 2``
    speed_seed: int | None = None

    def speed_factors(self) -> np.ndarray:
        """(n,) per-worker speed multipliers (1.0 when homogeneous)."""
        if self.hetero <= 0:
            return np.ones(self.n)
        sseed = self.speed_seed if self.speed_seed is not None else self.seed + 2
        rng = np.random.default_rng(sseed)
        return np.clip(rng.lognormal(0.0, self.hetero, self.n), 0.25, 4.0)

    def worker_alpha(self) -> np.ndarray:
        """(n,) load slope: seconds of extra compute per unit of
        normalized load, per worker (slow workers pay more per chunk)."""
        return self.base_time * self.compute_scale * self.speed_factors()

    @property
    def alpha(self) -> float:
        """Scalar slope (fleet mean) for ``estimate_alpha`` callers."""
        return float(self.worker_alpha().mean())

    def sample_pattern(self, rounds: int) -> np.ndarray:
        # delegate the transient-straggler chain (and its pinned RNG
        # draw-order contract, see GilbertElliotSource) rather than
        # duplicating it
        return GilbertElliotSource(
            n=self.n, seed=self.seed, p_ns=self.p_ns, p_sn=self.p_sn
        ).sample_pattern(rounds)

    def sample_delays(self, rounds: int) -> np.ndarray:
        """(rounds, n) seconds at the reference load 1/n."""
        rng = np.random.default_rng(self.seed + 1)
        pat = self.sample_pattern(rounds)
        speed = self.speed_factors()
        base = self.base_time * speed[None, :] * (
            1.0 + self.jitter * rng.standard_normal((rounds, self.n)) ** 2
        )
        slow = 1.0 + (self.slow_factor - 1.0) * rng.random((rounds, self.n))
        out = np.where(pat, base * np.maximum(slow, 1.0), base)
        cold = rng.random(self.n) < self.cold_fraction
        out[0] = out[0] + np.where(cold, self.cold_start * speed, 0.0)
        events = rng.random(rounds) < self.p_event
        out[events] *= self.event_factor
        return out


_RECORDINGS_DIR = Path(__file__).resolve().parent / "recordings"


def load_recorded_harness(
    name: str = "harness-ge-bursty",
    *,
    n: int | None = None,
    rounds: int | None = None,
) -> TraceModel:
    """Load a checked-in harness recording (JSON written by
    ``repro.dist``'s ``RunLedger.to_trace_model().to_json()``) from
    ``src/repro/core/recordings/``.

    With ``n``/``rounds`` given, the recorded pattern tiles cyclically
    (rows like :meth:`TraceModel.sample_pattern`, columns likewise) to
    the requested fleet shape; the measured ``timings`` are kept only at
    the recording's native shape (they describe specific workers)."""
    path = _RECORDINGS_DIR / f"{name}.json"
    model = TraceModel.from_json(path.read_text())
    pat = np.asarray(model.pattern, dtype=bool)
    reshaped = False
    if rounds is not None and rounds != pat.shape[0]:
        pat = model.sample_pattern(rounds)
        reshaped = True
    if n is not None and n != pat.shape[1]:
        reps = -(-n // pat.shape[1])
        pat = np.tile(pat, (1, reps))[:, :n]
        reshaped = True
    if not reshaped:
        return model
    return TraceModel(
        pattern=pat,
        base_time=model.base_time,
        slow_factor=model.slow_factor,
        jitter=model.jitter,
        compute_scale=model.compute_scale,
        seed=model.seed,
    )


@dataclass(frozen=True)
class Scenario:
    """One named entry of the straggler trace library: a stack of
    reference delay profiles plus the load slope the profiles were
    recorded at (a scalar, or a per-worker ``(n,)`` vector for
    heterogeneous fleets)."""

    name: str
    delays: np.ndarray            # (num_traces, rounds, n)
    alpha: object                 # float | (n,) float array
    note: str = ""


def trace_library(
    n: int = 64,
    rounds: int = 40,
    num_traces: int = 4,
    seed: int = 0,
) -> list[Scenario]:
    """The in-repo straggler trace library the scenario sweeps run on.

    Five qualitatively different worker profiles, all deterministic in
    ``seed`` (``num_traces`` independent traces each):

    * ``ge-bursty`` — the paper's Fig.-1-calibrated GE chain (short
      bursts, ~5% stragglers);
    * ``ge-heavy`` — slower recovery (long bursts, more overlap);
    * ``lambda-cold`` — :class:`LambdaTraceGenerator` with cold starts
      and platform events, homogeneous workers;
    * ``lambda-hetero`` — the same with lognormal worker speeds and the
      matching **per-worker alpha vector** (heterogeneous load slope);
    * ``replayed-waves`` — :class:`TraceModel` replaying a recorded
      diagonal-wave pattern (two adjacent stragglers sweeping the
      fleet), the adversarial-but-structured case cluster logs show;
    * ``recorded-harness`` — :class:`TraceModel` replaying the
      checked-in pattern a real ``repro.dist`` master/worker run
      recorded (see :func:`load_recorded_harness`), tiled cyclically to
      the requested fleet;
    * ``recorded-netfault`` — the same replay machinery over the
      checked-in TCP-transport recording (``harness-tcp-netfault``):
      a real socket run through a mid-run network partition that healed
      (the v2 ``events`` carry the partition/heal transitions), so the
      sweep sees the straggler texture a partitioned-then-healed fleet
      actually produced.
    """

    def _stack(mk):
        return np.stack([mk(k).sample_delays(rounds)
                         for k in range(num_traces)])

    ge_bursty = _stack(lambda k: GilbertElliotSource(
        n=n, seed=seed + 10 * k, p_ns=0.035, p_sn=0.85, slow_factor=6.0,
        jitter=0.05,
    ))
    ge_heavy = _stack(lambda k: GilbertElliotSource(
        n=n, seed=seed + 10 * k + 1, p_ns=0.05, p_sn=0.35, slow_factor=6.0,
        jitter=0.05,
    ))
    cold0 = LambdaTraceGenerator(n=n, seed=seed + 2)
    lam_cold = _stack(lambda k: LambdaTraceGenerator(
        n=n, seed=seed + 10 * k + 2,
    ))
    # ONE fleet (shared speed draw) across the hetero traces, so the
    # scenario's per-worker alpha vector describes every trace
    hetero0 = LambdaTraceGenerator(n=n, seed=seed + 3, hetero=0.35,
                                   speed_seed=seed + 1009)
    lam_het = _stack(lambda k: LambdaTraceGenerator(
        n=n, seed=seed + 10 * k + 3, hetero=0.35,
        speed_seed=seed + 1009,
    ))
    wave = np.zeros((rounds, n), dtype=bool)
    for t in range(rounds):
        wave[t, (2 * t) % n] = wave[t, (2 * t + 1) % n] = True
    wave0 = TraceModel(wave, seed=seed + 4)
    waves = _stack(lambda k: TraceModel(wave, seed=seed + 10 * k + 4))
    rec0 = load_recorded_harness(n=n, rounds=rounds)
    recorded = _stack(lambda k: TraceModel(
        rec0.pattern, base_time=rec0.base_time,
        slow_factor=rec0.slow_factor, jitter=rec0.jitter,
        compute_scale=rec0.compute_scale, seed=seed + 10 * k + 5,
    ))
    net0 = load_recorded_harness("harness-tcp-netfault", n=n,
                                 rounds=rounds)
    netfault = _stack(lambda k: TraceModel(
        net0.pattern, base_time=net0.base_time,
        slow_factor=net0.slow_factor, jitter=net0.jitter,
        compute_scale=net0.compute_scale, seed=seed + 10 * k + 6,
    ))
    # the GE source's calibrated slope; the Lambda/replay scenarios
    # read their own generators' .alpha so a retuned compute scale can
    # never drift from the delays it synthesized
    ge_alpha = GilbertElliotSource(n=n).alpha
    return [
        Scenario("ge-bursty", ge_bursty, ge_alpha,
                 "Fig.-1 calibrated short bursts"),
        Scenario("ge-heavy", ge_heavy, ge_alpha,
                 "long straggler bursts"),
        Scenario("lambda-cold", lam_cold, cold0.alpha,
                 "cold starts + platform events"),
        Scenario("lambda-hetero", lam_het, hetero0.worker_alpha(),
                 "lognormal worker speeds, per-worker alpha"),
        Scenario("replayed-waves", waves, wave0.alpha,
                 "recorded diagonal-wave pattern replay"),
        Scenario("recorded-harness", recorded, rec0.alpha,
                 "real master/worker harness recording replay"),
        Scenario("recorded-netfault", netfault, net0.alpha,
                 "TCP harness recording: partition healed mid-run"),
    ]


def fit_gilbert_elliot(pattern: np.ndarray) -> dict:
    """MLE fit of the 2-state GE chain to an observed straggler pattern
    (App. C: the GE model tracks worker state transitions).

    pattern: bool (rounds, n).  Returns {p_ns, p_sn, stationary,
    mean_burst} — transition MLEs are simple count ratios.
    """
    pat = np.asarray(pattern, dtype=bool)
    prev, nxt = pat[:-1], pat[1:]
    n_to_s = int((~prev & nxt).sum())
    n_stay = int((~prev & ~nxt).sum())
    s_to_n = int((prev & ~nxt).sum())
    s_stay = int((prev & nxt).sum())
    p_ns = n_to_s / max(n_to_s + n_stay, 1)
    p_sn = s_to_n / max(s_to_n + s_stay, 1)
    stationary = p_ns / max(p_ns + p_sn, 1e-12)
    return {
        "p_ns": p_ns,
        "p_sn": p_sn,
        "stationary": stationary,
        "mean_burst": 1.0 / max(p_sn, 1e-12),
    }


def burst_lengths(pattern: np.ndarray) -> np.ndarray:
    """All straggling-run lengths in ``pattern``, worker-major then
    time-ordered (vectorized run-length extraction)."""
    pat = np.asarray(pattern, dtype=bool)
    padded = np.zeros((pat.shape[0] + 2, pat.shape[1]), dtype=bool)
    padded[1:-1] = pat
    starts = ~padded[:-1] & padded[1:]
    ends = padded[:-1] & ~padded[1:]
    _, s_pos = np.nonzero(starts.T)
    _, e_pos = np.nonzero(ends.T)
    return e_pos - s_pos


def suggest_parameters(pattern: np.ndarray, *, quantile: float = 0.95) -> dict:
    """Design-model parameters implied by an observed pattern: smallest
    B covering the burst-length quantile, and per-window distinct
    straggler counts for candidate W (how the paper's Remark-J.1 rule of
    thumb is grounded in data)."""
    pat = np.asarray(pattern, dtype=bool)
    bursts = burst_lengths(pat)
    if bursts.size == 0:
        bursts = np.asarray([0])
    B = int(np.quantile(bursts, quantile)) or 1
    lam_by_W = {}
    for W in (B + 1, 2 * B + 1, 3 * B + 1):
        counts = _window_any(pat, W).sum(axis=1)
        lam_by_W[W] = int(np.quantile(counts, quantile))
    return {"B": B, "lam_by_W": lam_by_W, "burst_q": float(np.quantile(bursts, quantile))}
