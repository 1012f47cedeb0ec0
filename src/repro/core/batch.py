"""Vectorized batch simulation engine (the App.-J / Table-1 hot path).

The legacy ``simulator.simulate`` walks one scheme through one trace a
round at a time with descriptor materialization and decode solves; grid
sweeps (parameter selection, Monte-Carlo scheme comparisons) replay it
once per candidate and spend almost all their time in Python loops.

This module batches that work at two levels:

* ``simulate_fast`` — a drop-in replacement for ``simulate`` on the
  schemes' load-only fast path (``step``/``collect_jobs``: single-cell
  kernel wrappers, no ``MiniTask`` objects, no decode-weight solves)
  and the O(window * n) rolling ``ConformanceGate``.  Bit-for-bit
  identical ``SimResult``s — the legacy descriptor path stays as the
  differential-testing oracle (``tests/test_batch_engine.py``).
* ``simulate_lockstep`` — the **lockstep engine**: every grid cell of
  one spec (one cell per trace) advances through the same round
  together, on the functional scheme kernels and batched wait-out gate
  of ``core.kernel`` (struct-of-arrays state with a leading cells
  axis).  The per-round Python overhead is paid once per *grid*
  instead of once per *cell*, and the results stay bit-identical to
  per-cell ``simulate_fast`` runs (``tests/test_lockstep.py``;
  speedup gate in ``benchmarks/run.py lockstep``).
* ``simulate_batch`` — runs a (specs x seeds x traces) grid.  On the
  jax backend the grid is **grid-fused**: specs are bucketed by static
  shape key (:func:`grid_plan`), scalar parameters are stacked into
  spec-axis arrays, and each bucket runs as ONE ``vmap``-wrapped
  jitted ``lax.scan`` — a whole parameter sweep pays one compilation
  per shape bucket.  Elsewhere (and for unstageable specs) it runs one
  lockstep batch per spec.  Schemes whose load-only stepping ignores
  the coefficient seed (``seed_sensitive = False``, all paper schemes)
  run the trace axis ONCE and broadcast the results across the seed
  axis.
* ``select_parameters_fast`` — the App.-J probe sweep on top of
  ``simulate_batch``; ``simulator.select_parameters`` delegates here.

Every floating-point expression mirrors the legacy code exactly (same
ops, same order), so results are reproducible to the bit, not just to a
tolerance.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field

import numpy as np

from .backend import available_backends, get_backend
from .kernel import (
    GateKernel,
    GateState,
    SchemeKernel,
    has_kernel,
    kernel_seed_sensitive,
    make_kernel,
    state_flatten,
    state_unflatten,
)
from .schemes import Scheme, make_scheme
from .simulator import (
    Candidate,
    SimResult,
    default_grid,
    estimate_alpha,
)
from .straggler import ConformanceGate

__all__ = [
    "RoundPrecompute",
    "precompute_rounds",
    "simulate_fast",
    "simulate_lockstep",
    "simulate_batch",
    "select_parameters_fast",
    "grid_plan",
    "cache_stats",
    "clear_runner_cache",
]


@dataclass(frozen=True)
class RoundPrecompute:
    """Per-round timing quantities for one (trace, load) pair.

    ``times[t]`` are the load-adjusted worker seconds of round t+1;
    ``cand[t]`` is the mu-rule candidate straggler mask *before* the
    wait-out gate.  Rows beyond a scheme's horizon are simply unused, so
    one precompute serves schemes with different T.
    """

    times: np.ndarray    # (rounds, n) float
    kappa: np.ndarray    # (rounds,)  fastest worker per round
    cutoff: np.ndarray   # (rounds,)  (1 + mu) * kappa
    tmax: np.ndarray     # (rounds,)  slowest worker per round
    cand: np.ndarray     # (rounds, n) bool
    any_cand: np.ndarray  # (rounds,) bool


def precompute_rounds(
    ref_delays: np.ndarray, extra: float, mu: float
) -> RoundPrecompute:
    """Vectorize the per-round timing math of ``simulate`` over rounds."""
    times = ref_delays + extra
    kappa = times.min(axis=1)
    cutoff = (1.0 + mu) * kappa
    cand = times > cutoff[:, None]
    return RoundPrecompute(
        times=times,
        kappa=kappa,
        cutoff=cutoff,
        tmax=times.max(axis=1),
        cand=cand,
        any_cand=cand.any(axis=1),
    )


def simulate_fast(
    scheme: Scheme,
    ref_delays: np.ndarray,
    *,
    mu: float = 1.0,
    alpha: float = 1.0,
    J: int | None = None,
    waitout: str = "selective",
    pre: RoundPrecompute | None = None,
) -> SimResult:
    """Load-only fast simulation: bit-for-bit the same ``SimResult`` as
    the legacy ``simulate`` without MiniTask materialization or decode
    solves.  ``pre`` lets grid sweeps share the vectorized per-round
    precompute across candidates with the same (trace, load).
    """
    n = scheme.n
    J = J if J is not None else scheme.J
    rounds = J + scheme.T
    if ref_delays.shape[0] < rounds or ref_delays.shape[1] != n:
        raise ValueError(
            f"need delays of shape (>={rounds}, {n}), got {ref_delays.shape}"
        )
    extra = (scheme.normalized_load - 1.0 / n) * alpha
    if pre is None:
        pre = precompute_rounds(ref_delays[:rounds], extra, mu)

    gate = ConformanceGate(scheme.design_model, n)
    round_times = np.zeros(rounds)
    job_done_round: dict[int, int] = {}
    job_done_time: dict[int, float] = {}
    waitouts = 0

    for t in range(1, rounds + 1):
        k = t - 1
        times = pre.times[k]
        cutoff = pre.cutoff[k]
        tmax = pre.tmax[k]
        if not pre.any_cand[k]:
            candidate = pre.cand[k]
            gate.force(candidate)
            duration = float(min(cutoff, tmax))
        elif waitout == "selective":
            candidate, waited = gate.admit_partial(pre.cand[k], times)
            if waited:
                waitouts += 1
                duration = float(max(times[waited].max(), min(cutoff, tmax) if candidate.any() else cutoff))
            else:
                duration = float(min(cutoff, tmax))
        else:  # App-J fallback: wait out all workers on violation
            if gate.admit(pre.cand[k]):
                candidate = pre.cand[k]
                duration = float(min(cutoff, tmax))
            else:
                waitouts += 1
                candidate = np.zeros(n, dtype=bool)
                gate.force(candidate)
                duration = float(tmax)
        scheme.step(t, candidate)
        round_times[k] = duration
        done = scheme.collect_jobs(t)
        if done:
            elapsed = float(round_times[:t].sum())
            for job, round_done in done:
                job_done_round[job] = round_done
                job_done_time[job] = elapsed

    missing = [j for j in range(1, J + 1) if j not in job_done_round]
    if missing:
        raise AssertionError(f"jobs never finished: {missing[:5]}...")
    late = [j for j, r in job_done_round.items() if r > j + scheme.T]
    if late:
        raise AssertionError(f"jobs past deadline: {late[:5]}")

    return SimResult(
        scheme=scheme.name,
        total_time=float(round_times.sum()),
        round_times=round_times,
        job_done_round=job_done_round,
        job_done_time=job_done_time,
        waitouts=waitouts,
        effective_pattern=gate.history,
        normalized_load=scheme.normalized_load,
    )


def simulate_lockstep(
    name: str,
    params: dict,
    traces: np.ndarray,
    *,
    mu: float = 1.0,
    alpha: float = 1.0,
    J: int | None = None,
    waitout: str = "selective",
    seed: int = 0,
    strict: bool = True,
    backend: str | None = None,
) -> list[SimResult | None]:
    """Advance one spec through MANY traces in lockstep.

    One grid cell per trace: the functional kernel state
    (``core.kernel``) and the batched wait-out gate carry a leading
    cells axis, so each round of the whole grid is a handful of array
    ops.  On the default **numpy** backend every per-cell ``SimResult``
    is bit-identical to the scalar ``simulate_fast`` run on that trace
    (and hence to the legacy ``simulate``): the timing math, gate
    decisions, and elapsed-time accounting replicate the scalar
    expressions exactly.

    With ``backend="jax"`` (or when jax is the process default, e.g.
    ``REPRO_BACKEND=jax``) the whole (cells x rounds) sweep is staged
    as ONE jitted ``lax.scan`` per spec: the per-round transition —
    gate admission plus ``kernel.step`` — is a pure
    ``(state, (t, stragglers)) -> (state, outputs)`` function carried
    over the rounds axis, and results transfer to the host once.  The
    jax path is an "allclose" contract against the numpy oracle: exact
    on the bool/int bookkeeping (done rounds, dead flags, gate
    patterns, waitouts), allclose on float loads/runtimes.  Specs the
    staged path cannot express (load-adaptive ``round_loads``
    overrides, gate members without analytic wait-out solvers) fall
    back to this numpy engine transparently.

    ``traces``: (cells, rounds, n).  ``J = None`` fits ``J + T`` inside
    the trace (the App-J rule).  With ``strict=False``, cells whose
    wait-out contract is violated yield ``None`` instead of raising.

    ``alpha`` may be a scalar or a per-worker ``(n,)`` vector
    (heterogeneous fleets, e.g. ``LambdaTraceGenerator.worker_alpha``):
    worker i's round time is ``trace + (load_i - 1/n) * alpha[i]``,
    with the per-cell loads still coming from the kernel's
    ``round_loads`` protocol.  Identical broadcasting on every path
    (scalar, numpy lockstep, jax scan, fused grid).
    """
    traces = np.asarray(traces, dtype=np.float64)
    if traces.ndim == 2:
        traces = traces[None]
    cells, rounds_avail, n = traces.shape

    if J is None:
        # probe at the trace length (an upper bound on any fitted J, so
        # constructors that validate J accept it) just to learn T
        probe = make_scheme(name, n, rounds_avail, seed=seed, **dict(params))
        J = _grid_J(rounds_avail, probe.T, None, f"{name} {params}")
    scheme = make_scheme(name, n, J, seed=seed, **dict(params))
    if J + scheme.T > rounds_avail:
        # clamp an explicit J to the trace (the App-J rule, same as
        # _grid_J); callers like simulate_batch pass J pre-clamped
        J = _grid_J(rounds_avail, scheme.T, J, f"{name} {params}")
        scheme = make_scheme(name, n, J, seed=seed, **dict(params))

    if backend is not None and backend not in available_backends():
        raise ValueError(
            f"unknown backend {backend!r}; available: "
            f"{available_backends()}"
        )
    bk_name = backend if backend is not None else get_backend().name
    if bk_name == "jax":
        res = _simulate_lockstep_jax(
            name, params, scheme, traces, mu=mu, alpha=alpha, J=J,
            waitout=waitout, seed=seed, strict=strict,
        )
        if res is not None:
            return res

    # numpy engine — the bit-for-bit oracle (and the fallback for specs
    # the staged path cannot express); kernels pinned to the numpy
    # backend regardless of the process default
    nbk = get_backend("numpy")
    kernel = make_kernel(scheme, nbk)
    gate = GateKernel(scheme.design_model, n, nbk)
    state = kernel.init_state(cells)
    gs = gate.init_state(cells)
    rounds = J + kernel.T

    inv_n = 1.0 / n
    rt = np.zeros((cells, rounds))
    waitouts = np.zeros(cells, dtype=np.int64)
    job_done_time: list[dict[int, float]] = [{} for _ in range(cells)]

    # constant-load kernels (every paper scheme: round_loads not
    # overridden) get the whole timing grid in one broadcast pass;
    # load-adaptive kernels fall back to per-round math
    const_load = type(kernel).round_loads is SchemeKernel.round_loads
    if const_load:
        extra_s = (kernel.normalized_load - inv_n) * alpha
        times_all = traces[:, :rounds, :] + extra_s
        kappa_all = times_all.min(axis=2)
        cutoff_all = (1.0 + mu) * kappa_all
        tmax_all = times_all.max(axis=2)
        cand_all = times_all > cutoff_all[..., None]
        any_all = cand_all.any(axis=2)

    for t in range(1, rounds + 1):
        k = t - 1
        # per-round timing math (identical expressions to simulate_fast,
        # broadcast over cells; loads come from the kernel so
        # load-adaptive schemes can vary them per cell / per round)
        if const_load:
            times, kappa, cutoff = times_all[:, k], kappa_all[:, k], cutoff_all[:, k]
            tmax, cand, any_cand = tmax_all[:, k], cand_all[:, k], any_all[:, k]
        else:
            # (cells, 1) loads x scalar-or-(n,) alpha: heterogeneous
            # per-worker load slopes broadcast into a (cells, n) extra
            extra = (kernel.round_loads(state, t) - inv_n)[:, None] * alpha
            times = traces[:, k, :] + extra
            kappa = times.min(axis=1)
            cutoff = (1.0 + mu) * kappa
            tmax = times.max(axis=1)
            cand = times > cutoff[:, None]
            any_cand = cand.any(axis=1)
        base = np.minimum(cutoff, tmax)
        if waitout == "selective":
            gs, eff, waited = gate.admit_partial(gs, cand, times, any_cand)
            waited_any = waited.any(axis=1)
            wmax = np.where(waited, times, -np.inf).max(axis=1)
            dur_w = np.maximum(
                wmax, np.where(eff.any(axis=1), base, cutoff)
            )
            duration = np.where(waited_any, dur_w, base)
            waitouts += waited_any
        else:  # App-J fallback: wait out all workers on violation
            gs, eff, ok_any = gate.admit_all(gs, cand, any_cand)
            wo = any_cand & ~ok_any
            duration = np.where(wo, tmax, base)
            waitouts += wo
        state = kernel.step(state, t, eff)
        rt[:, k] = duration
        # elapsed time for jobs that completed this round; the row-wise
        # prefix sum replicates the scalar engine's float accounting
        # (numpy's pairwise summation per contiguous row) to the bit
        lo, hi = max(1, t - kernel.T), min(t, kernel.J)
        if hi >= lo:
            newly = state.done_round[:, lo : hi + 1] == t
            if newly.any():
                elapsed = rt[:, :t].sum(axis=1)
                cs, js = np.nonzero(newly)
                for c, j in zip(cs.tolist(), js.tolist()):
                    job_done_time[c][lo + j] = float(elapsed[c])
        if strict and bool(state.dead.any()):
            bad = np.flatnonzero(state.dead).tolist()
            raise AssertionError(
                f"{kernel.name}: wait-out contract violated at round {t} "
                f"in cell(s) {bad[:5]}"
            )

    history = np.stack(gs.history, axis=0) if gs.history else np.zeros(
        (0, cells, n), dtype=bool
    )
    return _assemble_results(
        kernel.name, scheme.normalized_load, J, rt,
        np.asarray(state.done_round), np.asarray(state.dead),
        np.asarray(waitouts), history, strict, job_done_time,
    )


def _assemble_results(
    scheme_name: str,
    normalized_load: float,
    J: int,
    rt: np.ndarray,
    done_round: np.ndarray,
    dead: np.ndarray,
    waitouts: np.ndarray,
    history: np.ndarray,
    strict: bool,
    job_done_time: list[dict[int, float]] | None = None,
) -> list[SimResult | None]:
    """Build per-cell ``SimResult``s from lockstep outputs (host side,
    shared by the numpy loop and the jax scan path).

    ``job_done_time=None`` (the jax path) recomputes each job's elapsed
    time as ``rt[c, :done_round].sum()`` — the same contiguous-row
    numpy reduction the incremental accounting performs, so both paths
    agree bitwise given identical ``rt``.
    """
    cells = rt.shape[0]
    if strict and bool(dead.any()):
        bad = np.flatnonzero(dead).tolist()
        raise AssertionError(
            f"{scheme_name}: wait-out contract violated in cell(s) "
            f"{bad[:5]}"
        )
    if job_done_time is None:
        job_done_time = []
        for c in range(cells):
            done = done_round[c]
            job_done_time.append({
                j: float(rt[c, : int(done[j])].sum())
                for j in range(1, J + 1)
                if int(done[j])
            })
    results: list[SimResult | None] = []
    for c in range(cells):
        done = done_round[c]
        if bool(dead[c]) or not bool((done[1:] != 0).all()):
            if strict:
                missing = np.flatnonzero(done[1:] == 0) + 1
                raise AssertionError(
                    f"jobs never finished: {missing.tolist()[:5]}..."
                )
            results.append(None)
            continue
        results.append(
            SimResult(
                scheme=scheme_name,
                total_time=float(rt[c].sum()),
                round_times=rt[c].copy(),
                job_done_round={j: int(done[j]) for j in range(1, J + 1)},
                job_done_time=job_done_time[c],
                waitouts=int(waitouts[c]),
                effective_pattern=np.ascontiguousarray(history[:, c]),
                normalized_load=normalized_load,
            )
        )
    return results


# staged-scan runners: per-SPEC runners (one jitted scan per
# (scheme, params, n, J, waitout[, seed]) spec, ``simulate_lockstep``)
# and per-BUCKET grid runners (one vmapped scan per shape bucket of a
# fused ``simulate_batch`` sweep) share one FIFO cache, so
# recompilation is paid once per spec / bucket, not once per call (the
# ``lockstep-jax`` and ``grid-jax`` benches gate this).  The seed
# enters keys only for seed-sensitive schemes — load-only stepping
# never reads the code coefficients otherwise.  The registered
# factory/kernel OBJECTS are part of every key (hashed by identity,
# and the key reference keeps them alive so a freed address can never
# be recycled into a colliding id), so re-registering a scheme or
# kernel — the extension API's register/unregister pattern — never
# hits a stale compiled runner or a stale "unsupported" verdict; the
# FIFO cap (``REPRO_RUNNER_CACHE_CAP``, default 256) keeps long
# parameter sweeps from holding every compiled executable for the
# process lifetime.
_JAX_RUNNERS: dict[tuple, object] = {}
_RUNNER_CACHE_CAP_DEFAULT = 256
_JAX_UNSUPPORTED = object()
#: "unsupported spec" verdicts live in a SIDE table: they are cheap
#: host-side markers, so they must neither count toward the FIFO cap
#: nor push hot *compiled* runners out of ``_JAX_RUNNERS`` (long mixed
#: sweeps interleave many unstageable specs with a few compiled ones).
#: Still FIFO-bounded (generously — re-deriving an evicted verdict is
#: cheap, no compile) so unbounded spec churn in a long-lived process
#: cannot grow memory without limit.
_JAX_UNSUPPORTED_VERDICTS: dict[tuple, object] = {}
_VERDICT_CACHE_CAP = 4096
_CACHE_COUNTERS = {"hits": 0, "misses": 0, "evictions": 0, "compiles": 0}


def _runner_cache_cap() -> int:
    """FIFO cap on cached compiled runners; configurable per process
    via the ``REPRO_RUNNER_CACHE_CAP`` environment variable (read at
    lookup time, so tests and long-lived services can retune it)."""
    raw = os.environ.get("REPRO_RUNNER_CACHE_CAP", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            warnings.warn(
                f"REPRO_RUNNER_CACHE_CAP={raw!r} is not an int; using "
                f"{_RUNNER_CACHE_CAP_DEFAULT}",
                stacklevel=2,
            )
    return _RUNNER_CACHE_CAP_DEFAULT


def cache_stats() -> dict:
    """Counters for the compiled-runner cache: ``hits`` / ``misses`` /
    ``evictions`` plus ``compiles`` (cache misses that actually built
    and staged a runner — "unsupported spec" verdicts are misses but
    not compiles), the current ``size`` / ``cap`` of the compiled-
    runner FIFO, and ``unsupported`` — the cached verdict count, held
    in a side table exempt from the cap.  The ``grid-jax`` bench
    asserts one compile per shape bucket off these."""
    return dict(_CACHE_COUNTERS, size=len(_JAX_RUNNERS),
                cap=_runner_cache_cap(),
                unsupported=len(_JAX_UNSUPPORTED_VERDICTS))


def clear_runner_cache() -> None:
    """Drop every cached runner and verdict and zero the
    :func:`cache_stats` counters (benchmarks use this to measure
    cold-start compiles)."""
    _JAX_RUNNERS.clear()
    _JAX_UNSUPPORTED_VERDICTS.clear()
    for k in _CACHE_COUNTERS:
        _CACHE_COUNTERS[k] = 0


def _runner_cache_lookup(key: tuple, build):
    """FIFO-cached runner lookup; ``build()`` runs on a miss and may
    return ``_JAX_UNSUPPORTED`` (cached too — in the cap-exempt side
    table, so the verdict is neither re-derived every call nor able to
    evict a hot compiled runner)."""
    if key in _JAX_UNSUPPORTED_VERDICTS:
        _CACHE_COUNTERS["hits"] += 1
        return _JAX_UNSUPPORTED
    entry = _JAX_RUNNERS.get(key)
    if entry is not None:
        _CACHE_COUNTERS["hits"] += 1
        return entry
    _CACHE_COUNTERS["misses"] += 1
    entry = build()
    if entry is _JAX_UNSUPPORTED:
        while len(_JAX_UNSUPPORTED_VERDICTS) >= _VERDICT_CACHE_CAP:
            _JAX_UNSUPPORTED_VERDICTS.pop(
                next(iter(_JAX_UNSUPPORTED_VERDICTS))
            )
        _JAX_UNSUPPORTED_VERDICTS[key] = entry
        return entry
    _CACHE_COUNTERS["compiles"] += 1
    cap = _runner_cache_cap()
    while len(_JAX_RUNNERS) >= cap:
        _JAX_RUNNERS.pop(next(iter(_JAX_RUNNERS)))
        _CACHE_COUNTERS["evictions"] += 1
    _JAX_RUNNERS[key] = entry
    return entry


def _jax_runner_key(scheme, params: dict, J: int, waitout: str, seed: int):
    from .kernel import _KERNELS
    from .schemes import _SCHEME_FACTORIES

    sensitive = (
        getattr(scheme, "seed_sensitive", False)
        or kernel_seed_sensitive(scheme.name)
    )
    return (
        "spec",
        scheme.name,
        _SCHEME_FACTORIES.get(scheme.name),
        _KERNELS.get(scheme.name),
        tuple(sorted((str(k), v) for k, v in params.items())),
        scheme.n,
        J,
        waitout,
        seed if sensitive else None,
    )


def _stageable(kernel_or_none, gate_or_none, waitout: str) -> bool:
    """Can the static-shape scan path express this spec?  Shared by the
    per-spec runner builder and the grid-fusion planner (which must
    route unstageable specs to the per-spec fallback BEFORE bucketing).
    False when: no registered kernel, load-adaptive ``round_loads``
    overrides (the timing precompute assumes one constant load), or —
    in selective wait-out — gate members without the analytic
    ``min_drops_batch`` solver.  Callers pass the gate they already
    built for the spec (None only alongside a None kernel)."""
    if kernel_or_none is None:
        return False
    if type(kernel_or_none).round_loads is not SchemeKernel.round_loads:
        return False
    if waitout == "selective":
        return gate_or_none.analytic
    return True


def _staged_lockstep_run(kernel, gate, rounds: int, selective: bool,
                         traces_dev, mu, alpha, load):
    """One spec's whole (cells x rounds) lockstep sweep as a ``scan``
    over the rounds axis — the pure traced core shared by the per-spec
    jitted runner and the grid-fused (vmapped) bucket runner.  ``mu``,
    ``alpha`` and ``load`` are traced scalars (per-spec lanes of the
    stacked arrays under ``vmap``)."""
    import jax.numpy as jnp

    bkj = kernel.bk
    inv_n = 1.0 / kernel.n
    cells = traces_dev.shape[0]
    extra = (load - inv_n) * alpha
    times_all = traces_dev + extra                  # (cells, rounds, n)
    cls, flat0 = state_flatten(kernel.init_state(cells))
    gs0 = gate.init_state(cells)

    def body(carry, xs):
        flat, bufs, alive = carry
        t, times = xs
        state = state_unflatten(cls, list(flat))
        # identical expressions to the numpy engine, one round at
        # a time under the scan
        kappa = times.min(axis=1)
        cutoff = (1.0 + mu) * kappa
        tmax = times.max(axis=1)
        cand = times > cutoff[:, None]
        any_cand = cand.any(axis=1)
        base = jnp.minimum(cutoff, tmax)
        gs = GateState(bufs=list(bufs), alive=alive,
                       filled=gate.full, history=None)
        if selective:
            gs, eff, waited = gate.admit_partial(
                gs, cand, times, any_cand
            )
            waited_any = waited.any(axis=1)
            wmax = jnp.where(waited, times, -jnp.inf).max(axis=1)
            dur_w = jnp.maximum(
                wmax, jnp.where(eff.any(axis=1), base, cutoff)
            )
            duration = jnp.where(waited_any, dur_w, base)
            wflag = waited_any
        else:
            gs, eff, ok_any = gate.admit_all(gs, cand, any_cand)
            wflag = any_cand & ~ok_any
            duration = jnp.where(wflag, tmax, base)
        state = kernel.step(state, t, eff)
        _, flat = state_flatten(state)
        return (
            (tuple(flat), tuple(gs.bufs), gs.alive),
            (duration, eff, wflag),
        )

    ts = jnp.arange(1, rounds + 1)
    xs = (ts, jnp.swapaxes(times_all, 0, 1))
    (flat_f, _, _), (dur, eff, wflag) = bkj.scan(
        body, (tuple(flat0), tuple(gs0.bufs), gs0.alive), xs
    )
    state = state_unflatten(cls, list(flat_f))
    return dict(
        rt=jnp.swapaxes(dur, 0, 1),
        done_round=state.done_round,
        dead=state.dead,
        waitouts=wflag.sum(axis=0),
        history=eff,
    )


def _build_jax_runner(scheme, J: int, waitout: str):
    """Stage one spec's whole lockstep sweep as a jitted ``lax.scan``.

    Returns ``_JAX_UNSUPPORTED`` for specs the static-shape path cannot
    express (see :func:`_stageable`).
    """
    bkj = get_backend("jax")
    try:
        kernel = make_kernel(scheme, bkj)
    except KeyError:
        kernel = None
    gate = (
        GateKernel(scheme.design_model, scheme.n, bkj)
        if kernel is not None else None
    )
    if not _stageable(kernel, gate, waitout):
        return _JAX_UNSUPPORTED
    rounds = J + kernel.T
    selective = waitout == "selective"

    def run(traces_dev, mu, alpha, load):
        return _staged_lockstep_run(
            kernel, gate, rounds, selective, traces_dev, mu, alpha, load
        )

    return bkj.jit(run), kernel.name


def _build_jax_grid_runner(scheme, J: int, waitout: str,
                           fused_names: tuple):
    """Stage one shape BUCKET — many specs sharing every static shape —
    as a single ``vmap``-wrapped jitted ``lax.scan``.

    The per-spec scalars (``mu``, ``alpha``, ``load`` and the kernel's
    ``fused_params``) arrive stacked along a leading spec axis; each
    vmap lane rebinds them as traced scalars onto shallow copies of the
    representative kernel / design model (``SchemeKernel.bind_fused``),
    so the whole bucket compiles ONCE and transfers to the host once.
    The traces are shared across lanes (``in_axes=None``) — every spec
    of a ``simulate_batch`` call replays the same trace set.
    """
    bkj = get_backend("jax")
    try:
        kernel0 = make_kernel(scheme, bkj)
    except KeyError:
        kernel0 = None
    gate0 = (
        GateKernel(scheme.design_model, scheme.n, bkj)
        if kernel0 is not None else None
    )
    if not _stageable(kernel0, gate0, waitout):
        return _JAX_UNSUPPORTED
    rounds = J + kernel0.T
    selective = waitout == "selective"
    n = kernel0.n

    def run_one(mu, alpha, load, fused, traces_dev):
        if fused_names:
            kernel, model = kernel0.bind_fused(fused)
            gate = GateKernel(model, n, bkj)
        else:
            kernel, gate = kernel0, gate0
        return _staged_lockstep_run(
            kernel, gate, rounds, selective, traces_dev, mu, alpha, load
        )

    def run(mu, alpha, load, fused, traces_dev):
        return bkj.vmap(run_one, in_axes=(0, 0, 0, 0, None))(
            mu, alpha, load, fused, traces_dev
        )

    return bkj.jit(run), kernel0.name


def _simulate_lockstep_jax(
    name: str,
    params: dict,
    scheme,
    traces: np.ndarray,
    *,
    mu: float,
    alpha: float,
    J: int,
    waitout: str,
    seed: int,
    strict: bool,
) -> list[SimResult | None] | None:
    """The device-resident lockstep path; ``None`` means "spec not
    stageable, use the numpy engine".

    Runs under a scoped ``jax.enable_x64`` so the float timing math is
    f64 like the oracle — the bool/int bookkeeping then matches the
    numpy engine exactly and loads/runtimes allclose (on CPU typically
    bit-equal, but only allclose is contractual).
    """
    import jax

    key = _jax_runner_key(scheme, params, J, waitout, seed)
    with jax.enable_x64(True):
        entry = _runner_cache_lookup(
            key, lambda: _build_jax_runner(scheme, J, waitout)
        )
        if entry is _JAX_UNSUPPORTED:
            return None
        runner, kernel_name = entry
        rounds = J + scheme.T
        # alpha may be a per-worker (n,) vector (heterogeneous load
        # slopes); a 0-d array otherwise — jit re-stages per shape
        out = runner(
            traces[:, :rounds], float(mu),
            np.asarray(alpha, dtype=np.float64),
            float(scheme.normalized_load),
        )
        host = jax.device_get(out)
    return _assemble_results(
        kernel_name, scheme.normalized_load, J,
        np.asarray(host["rt"], dtype=np.float64),
        np.asarray(host["done_round"]),
        np.asarray(host["dead"]),
        np.asarray(host["waitouts"]),
        np.asarray(host["history"]),
        strict, None,
    )


@dataclass(frozen=True)
class _RunEntry:
    """One (spec, seed) run of a ``simulate_batch`` grid after seed
    deduplication (insensitive schemes keep only ``ki == 0``; the
    result row is broadcast across the seed axis afterwards)."""

    si: int
    ki: int
    name: str
    params: dict
    J: int
    seed: int


@dataclass
class _Bucket:
    """One grid-fusion shape bucket: specs sharing every static shape
    (scheme structure, n, J, T, waitout, trace count), differing only
    in stacked scalars."""

    key: tuple
    J: int
    T: int
    fused_names: tuple
    scheme0: object                      # representative prototype
    members: list = field(default_factory=list)  # (entry, scheme, scalars)


def _plan_entries(specs, traces, seeds, J, strict, out):
    """Per-spec prototypes -> fitted J, seed dedup, run entries.

    Infeasible specs (constructor rejects the grid) raise under
    ``strict`` and mark their ``out`` rows ``None`` otherwise.  Returns
    ``(entries, sensitive)`` where ``sensitive[si]`` drives the
    seed-axis broadcast.
    """
    num_traces, rounds_avail, n = traces.shape
    entries: list[_RunEntry] = []
    sensitive_map: dict[int, bool] = {}
    for si, (name, params) in enumerate(specs):
        # one prototype per spec: J, T and normalized_load depend only
        # on the parameters, not on seed or trace.  Probe at the trace
        # length — an upper bound on any fitted J — so registered
        # schemes that validate J accept it.
        try:
            probe = make_scheme(name, n, rounds_avail, seed=seeds[0],
                                **dict(params))
            J_eff = _grid_J(rounds_avail, probe.T, J, f"{name} {params}")
        except ValueError:
            if strict:
                raise
            out[si] = None
            continue
        sensitive = (
            getattr(probe, "seed_sensitive", False)
            or kernel_seed_sensitive(probe.name)
        )
        sensitive_map[si] = sensitive
        run_seeds = seeds if sensitive else seeds[:1]
        for ki, seed in enumerate(run_seeds):
            entries.append(
                _RunEntry(si, ki, name, dict(params), J_eff, seed)
            )
    return entries, sensitive_map


def _plan_buckets(entries, traces_shape, waitout, strict, out):
    """Group stageable run entries into shape buckets (the grid-fusion
    planner).  Entries the fused path cannot express — kernel-less
    schemes, load-adaptive loads, non-analytic gates — come back as
    leftovers for the transparent per-spec fallback; entries whose
    constructor rejects the fitted J mark their rows (strict raises).

    The bucket key is the spec's full STATIC signature: scheme name +
    registered factory/kernel identity, the non-fused ("structural")
    parameters, n, J, T, waitout, the trace count, and — for
    seed-sensitive schemes — the seed (mirroring the per-spec runner
    cache).  The kernel's ``fused_params`` values are excluded: they
    stack into per-bucket spec-axis arrays instead.
    """
    from .kernel import _KERNELS
    from .schemes import _SCHEME_FACTORIES

    num_traces, rounds_avail, n = traces_shape
    nbk = get_backend("numpy")
    leftover: list[_RunEntry] = []
    buckets: dict[tuple, _Bucket] = {}
    for e in entries:
        if not has_kernel(e.name):
            leftover.append(e)
            continue
        try:
            scheme = make_scheme(e.name, n, e.J, seed=e.seed,
                                 **dict(e.params))
        except ValueError:
            if strict:
                raise
            out[e.si, e.ki] = [None] * num_traces
            continue
        try:
            kern = make_kernel(scheme, nbk)
        except KeyError:  # pragma: no cover - has_kernel raced a dereg
            leftover.append(e)
            continue
        gate = (
            GateKernel(scheme.design_model, scheme.n, nbk)
            if waitout == "selective" else None
        )
        if not _stageable(kern, gate, waitout):
            leftover.append(e)
            continue
        fused_names = tuple(kern.fused_params)
        sensitive = (
            getattr(scheme, "seed_sensitive", False)
            or kernel_seed_sensitive(scheme.name)
        )
        structural = tuple(sorted(
            (str(k), v) for k, v in e.params.items()
            if k not in fused_names
        ))
        key = (
            "grid",
            scheme.name,
            _SCHEME_FACTORIES.get(scheme.name),
            _KERNELS.get(scheme.name),
            structural,
            fused_names,
            n,
            e.J,
            kern.T,
            waitout,
            num_traces,
            e.seed if sensitive else None,
        )
        bucket = buckets.get(key)
        if bucket is None:
            bucket = buckets[key] = _Bucket(
                key, e.J, kern.T, fused_names, scheme
            )
        bucket.members.append((e, scheme, kern.fused_scalars(scheme)))
    return leftover, list(buckets.values())


def _simulate_batch_fused(entries, traces, out, *, mu, alpha, waitout,
                          strict):
    """Run the stageable entries of a grid bucket-by-bucket: one
    ``vmap``-wrapped jitted scan and ONE device->host transfer per
    shape bucket.  Returns the entries left for the per-spec path."""
    import jax
    import jax.numpy as jnp

    leftover, buckets = _plan_buckets(
        entries, traces.shape, waitout, strict, out
    )
    if not buckets:
        return leftover
    with jax.enable_x64(True):
        for b in buckets:
            entry = _runner_cache_lookup(
                b.key,
                lambda b=b: _build_jax_grid_runner(
                    b.scheme0, b.J, waitout, b.fused_names
                ),
            )
            if entry is _JAX_UNSUPPORTED:  # pragma: no cover - planner
                leftover.extend(e for e, _, _ in b.members)  # pre-checks
                continue
            runner, kernel_name = entry
            rounds = b.J + b.T
            S = len(b.members)
            mu_s = jnp.full((S,), float(mu), dtype=jnp.float64)
            # scalar alpha stacks to (S,); a per-worker (n,) vector
            # (heterogeneous load slopes) stacks to (S, n) — either
            # way each vmap lane sees its own alpha
            alpha_arr = np.asarray(alpha, dtype=np.float64)
            alpha_s = jnp.broadcast_to(
                jnp.asarray(alpha_arr), (S,) + alpha_arr.shape
            )
            load_s = jnp.asarray(
                [s.normalized_load for _, s, _ in b.members],
                dtype=jnp.float64,
            )
            fused = {
                name: jnp.asarray([sc[name] for _, _, sc in b.members])
                for name in b.fused_names
            }
            res = runner(mu_s, alpha_s, load_s, fused, traces[:, :rounds])
            host = jax.device_get(res)
            for i, (e, scheme, _) in enumerate(b.members):
                out[e.si, e.ki] = _assemble_results(
                    kernel_name, scheme.normalized_load, b.J,
                    np.asarray(host["rt"][i], dtype=np.float64),
                    np.asarray(host["done_round"][i]),
                    np.asarray(host["dead"][i]),
                    np.asarray(host["waitouts"][i]),
                    np.asarray(host["history"][i]),
                    strict, None,
                )
    return leftover


_FUSE_OFF_VALUES = ("0", "false", "off", "no")
_FUSE_ON_VALUES = ("", "1", "true", "on", "yes")


def _fuse_enabled(fuse: bool | None) -> bool:
    """Grid fusion defaults ON for the jax backend; disable per call
    (``fuse=False``) or per process (``REPRO_GRID_FUSE=0``).  An
    unrecognized env value warns (mirroring the
    ``REPRO_RUNNER_CACHE_CAP`` parser) instead of silently acting as
    fuse-ON — a typo like ``"nope"`` should not flip the engine's
    execution strategy without a trace."""
    if fuse is not None:
        return fuse
    raw = os.environ.get("REPRO_GRID_FUSE", "1").strip().lower()
    if raw in _FUSE_OFF_VALUES:
        return False
    if raw not in _FUSE_ON_VALUES:
        warnings.warn(
            f"REPRO_GRID_FUSE={raw!r} is not a recognized on/off value "
            f"(off: {'/'.join(_FUSE_OFF_VALUES)}; on: 1/true/on/yes); "
            "grid fusion stays ON",
            stacklevel=2,
        )
    return True


def grid_plan(
    specs: list[tuple[str, dict]],
    traces: np.ndarray,
    *,
    seeds: tuple[int, ...] = (0,),
    J: int | None = None,
    waitout: str = "selective",
) -> dict:
    """Dry-run the grid-fusion planner: how would ``simulate_batch``
    bucket these specs on the jax backend?

    Returns ``{"buckets": [...], "fallback": [...], "infeasible":
    [...]}`` — every input spec index lands in exactly one of the
    three: a bucket dict (scheme name, member spec indices, the shared
    ``J``/``T``, the fused stacked-scalar parameter names), the
    per-spec ``fallback`` list (stageability blockers), or
    ``infeasible`` (the constructor rejected the spec / grid outright
    — ``strict=False`` None rows).  Purely host-side — nothing is
    traced or compiled — so CLIs and benchmarks can report expected
    compile counts up front.
    """
    traces = np.asarray(traces, dtype=np.float64)
    if traces.ndim == 2:
        traces = traces[None]
    out = np.empty((len(specs), len(seeds), traces.shape[0]), dtype=object)
    entries, _ = _plan_entries(specs, traces, seeds, J, False, out)
    leftover, buckets = _plan_buckets(
        entries, traces.shape, waitout, False, out
    )
    accounted = {e.si for e in leftover}
    for b in buckets:
        accounted.update(e.si for e, _, _ in b.members)
    return {
        "buckets": [
            {
                "scheme": b.scheme0.name,
                "specs": [e.si for e, _, _ in b.members],
                "J": b.J,
                "T": b.T,
                "fused": list(b.fused_names),
                "cells": traces.shape[0],
            }
            for b in buckets
        ],
        # dedupe: seed-sensitive specs contribute one run entry per
        # seed, but the plan reports spec indices
        "fallback": sorted({e.si for e in leftover}),
        "infeasible": sorted(set(range(len(specs))) - accounted),
    }


def simulate_batch(
    specs: list[tuple[str, dict]],
    traces: np.ndarray,
    *,
    seeds: tuple[int, ...] = (0,),
    mu: float = 1.0,
    alpha: float = 1.0,
    J: int | None = None,
    waitout: str = "selective",
    strict: bool = True,
    backend: str | None = None,
    fuse: bool | None = None,
) -> np.ndarray:
    """Run a (specs x seeds x traces) grid on the lockstep engine.

    ``specs``: [(scheme_name, params_dict), ...]
    ``traces``: (num_traces, rounds, n) reference delay profiles.
    Returns an object array of ``SimResult`` with shape
    ``(len(specs), len(seeds), len(traces))``; with ``strict=False``,
    infeasible cells (bad params / wait-out contract violations) hold
    ``None`` instead of raising.

    On the **jax** backend the grid runs **grid-fused** by default:
    specs are bucketed by static shape key (scheme structure, n, J, T,
    wait-out mode, trace count — see :func:`grid_plan`), their scalar
    parameters (``mu``, ``alpha``, load, the kernels' ``fused_params``)
    are stacked into leading spec-axis arrays, and each bucket runs as
    ONE ``vmap``-wrapped jitted ``lax.scan`` with a single device->host
    transfer — a whole parameter sweep pays one compilation per shape
    bucket instead of one per spec (``benchmarks/run.py grid-jax``
    gates this).  ``fuse=False`` (or ``REPRO_GRID_FUSE=0``) restores
    the per-spec runners; specs the fused path cannot stage fall back
    to them transparently, with identical results either way (exact
    bool/int bookkeeping, allclose floats — ``tests/test_grid_fused.py``).

    Otherwise each spec advances all of its traces in lockstep
    (:func:`simulate_lockstep`); ragged grids are fine — every spec
    gets its own ``J``/``T`` (the App-J fit-the-trace rule) and state
    shapes.  ``seeds`` vary only the schemes' gradient-code
    coefficients, which the load-only path never reads: for schemes
    with ``seed_sensitive = False`` (all paper schemes) the trace axis
    runs ONCE and the resulting ``SimResult`` objects are broadcast
    across the seed axis, so Monte-Carlo variance must come from
    ``traces``.  Schemes registered without a lockstep kernel fall back
    to per-cell ``simulate_fast`` runs.
    """
    if backend is not None and backend not in available_backends():
        # validate up front: under strict=False the per-spec loop
        # swallows ValueErrors into None cells
        raise ValueError(
            f"unknown backend {backend!r}; available: "
            f"{available_backends()}"
        )
    traces = np.asarray(traces, dtype=np.float64)
    if traces.ndim == 2:
        traces = traces[None]
    num_traces, rounds_avail, n = traces.shape

    out = np.empty((len(specs), len(seeds), num_traces), dtype=object)
    entries, sensitive_map = _plan_entries(
        specs, traces, seeds, J, strict, out
    )
    bk_name = backend if backend is not None else get_backend().name
    if bk_name == "jax" and _fuse_enabled(fuse):
        entries = _simulate_batch_fused(
            entries, traces, out, mu=mu, alpha=alpha, waitout=waitout,
            strict=strict,
        )
    for e in entries:
        if has_kernel(e.name):
            # contract violations already yield None cells under
            # strict=False; ValueError covers constructors that
            # reject the fitted J_eff (the probe ran at trace
            # length, an upper bound)
            try:
                row = simulate_lockstep(
                    e.name, e.params, traces, mu=mu, alpha=alpha, J=e.J,
                    waitout=waitout, seed=e.seed, strict=strict,
                    backend=backend,
                )
            except ValueError:
                if strict:
                    raise
                row = [None] * num_traces
        else:
            row = []
            for ti in range(num_traces):
                try:
                    scheme = make_scheme(e.name, n, e.J, seed=e.seed,
                                         **dict(e.params))
                    row.append(simulate_fast(
                        scheme, traces[ti], mu=mu, alpha=alpha,
                        J=e.J, waitout=waitout,
                    ))
                except (ValueError, AssertionError):
                    if strict:
                        raise
                    row.append(None)
        out[e.si, e.ki] = row
    for si, sensitive in sensitive_map.items():
        if not sensitive:
            # load-only results are seed-invariant: broadcast the
            # SimResult objects (shared, treat as read-only)
            for ki in range(1, len(seeds)):
                out[si, ki] = out[si, 0]
    return out


def _grid_J(rounds_avail: int, maxT: int, J: int | None, what: str) -> int:
    """Legacy App.-J job-count rule: fit J + T inside the trace."""
    J_eff = J if J is not None else max(1, rounds_avail - maxT)
    if J_eff + maxT > rounds_avail:
        J_eff = rounds_avail - maxT
    if J_eff < 1:
        raise ValueError(
            f"trace of {rounds_avail} rounds too short for {what}"
        )
    return J_eff


def select_parameters_fast(
    name: str,
    n: int,
    probe_delays: np.ndarray,
    *,
    mu: float = 1.0,
    alpha: float | None = None,
    grid: list[dict] | None = None,
    J: int | None = None,
    seed: int = 0,
    backend: str | None = None,
) -> Candidate:
    """App.-J selection on the lockstep batch engine: replay the probe
    profile under each candidate parameterization (load-adjusted) and
    pick the fastest.  Chooses the exact same candidate as the legacy
    per-candidate loop (``simulator.select_parameters_legacy``) — same
    grid order, bit-identical per-job times — at a fraction of the cost.
    """
    alpha = alpha if alpha is not None else estimate_alpha(n)
    if grid is None:
        grid = default_grid(name, n)

    res = simulate_batch(
        [(name, params) for params in grid],
        np.asarray(probe_delays, dtype=np.float64)[None],
        seeds=(seed,), mu=mu, alpha=alpha, J=J, strict=False,
        backend=backend,
    )
    # grid order is selection order: strict < keeps the earliest on
    # ties, like the legacy loop
    best = Candidate(name, {})
    for gi, params in enumerate(grid):
        r = res[gi, 0, 0]
        if r is None:
            continue
        # normalize to per-job time so different T don't skew comparison
        J_eff = len(r.job_done_round)
        per_job = r.total_time / J_eff
        if per_job < best.est_time:
            best = Candidate(name, params, r.normalized_load, per_job)
    if not best.params:
        raise RuntimeError(f"no feasible parameters for scheme {name}")
    return best
