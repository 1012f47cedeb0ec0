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
  speedup gate in ``benchmarks/run.py lockstep``).  On the jax backend
  the spec runs as a one-member bucket of the staged engine below.
* ``simulate_batch`` — runs a (specs x seeds x traces) grid.  On the
  jax backend the grid is **grid-fused**: specs are bucketed by static
  shape key (:func:`grid_plan`), scalar parameters are stacked into
  spec-axis arrays, and each bucket runs as ONE ``vmap``-wrapped
  jitted ``lax.scan`` — a whole parameter sweep pays one compilation
  per shape bucket.  Elsewhere (and for unstageable specs) it runs one
  numpy lockstep batch per spec.  Schemes whose load-only stepping
  ignores the coefficient seed (``seed_sensitive = False``, all paper
  schemes) run the trace axis ONCE and broadcast the results across
  the seed axis.
* ``select_parameters_fast`` — the App.-J probe sweep on top of
  ``simulate_batch``; ``simulator.select_parameters`` delegates here.

Every engine takes the round rule (mu-rule, wait-out, duration) from
``core.simulator`` (:func:`~repro.core.simulator.round_cutoff`,
:func:`~repro.core.simulator.round_duration`,
:class:`~repro.core.simulator.RoundGate`), so the numpy results are
reproducible to the bit, not just to a tolerance.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field

import numpy as np

from .. import tracing
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
    RoundGate,
    SimResult,
    default_grid,
    estimate_alpha,
    round_cutoff,
    round_duration,
    round_duration_all,
)

__all__ = [
    "simulate_fast",
    "simulate_lockstep",
    "simulate_batch",
    "select_parameters_fast",
    "grid_plan",
    "cache_stats",
    "clear_runner_cache",
]


def simulate_fast(
    scheme: Scheme,
    ref_delays: np.ndarray,
    *,
    mu: float = 1.0,
    alpha: float = 1.0,
    J: int | None = None,
    waitout: str = "selective",
) -> SimResult:
    """Load-only fast simulation: bit-for-bit the same ``SimResult`` as
    the legacy ``simulate`` without MiniTask materialization or decode
    solves."""
    n = scheme.n
    J = J if J is not None else scheme.J
    rounds = J + scheme.T
    if ref_delays.shape[0] < rounds or ref_delays.shape[1] != n:
        raise ValueError(
            f"need delays of shape (>={rounds}, {n}), got {ref_delays.shape}"
        )
    extra = (scheme.normalized_load - 1.0 / n) * alpha
    rg = RoundGate(scheme.design_model, n, mu=mu, waitout=waitout)
    round_times = np.zeros(rounds)
    job_done_round: dict[int, int] = {}
    job_done_time: dict[int, float] = {}
    waitouts = 0

    for t in range(1, rounds + 1):
        k = t - 1
        candidate, duration, waited = rg.round(ref_delays[k] + extra)
        waitouts += waited
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
        effective_pattern=rg.history,
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
    (and hence to the legacy ``simulate``): both take the round rule
    from ``core.simulator`` and the elapsed-time accounting replicates
    the scalar sums exactly.

    With ``backend="jax"`` (or when jax is the process default, e.g.
    ``REPRO_BACKEND=jax``) the spec runs as a one-member bucket of the
    staged engine that ``simulate_batch`` uses: the whole (cells x
    rounds) sweep is ONE jitted, ``vmap``-wrapped ``lax.scan`` whose
    per-round transition — gate admission plus ``kernel.step`` — is a
    pure ``(state, (t, stragglers)) -> (state, outputs)`` function
    carried over the rounds axis, and results transfer to the host
    once.  The jax path is an "allclose" contract against the numpy
    oracle: exact on the bool/int bookkeeping (done rounds, dead flags,
    gate patterns, waitouts), allclose on float loads/runtimes.  Specs
    the staged path cannot express (load-adaptive ``round_loads``
    overrides, gate members without analytic wait-out solvers) run on
    the numpy engine.

    ``traces``: (cells, rounds, n).  ``J = None`` fits ``J + T`` inside
    the trace (the App-J rule).  With ``strict=False``, cells whose
    wait-out contract is violated yield ``None`` instead of raising.

    ``alpha`` may be a scalar or a per-worker ``(n,)`` vector
    (heterogeneous fleets, e.g. ``LambdaTraceGenerator.worker_alpha``):
    worker i's round time is ``trace + (load_i - 1/n) * alpha[i]``,
    with the per-cell loads still coming from the kernel's
    ``round_loads`` protocol.  Identical broadcasting on every path
    (scalar, numpy lockstep, staged scan).
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
        out = np.empty((1, 1, cells), dtype=object)
        entry = _RunEntry(0, 0, name, dict(params), J, seed)
        if not _simulate_batch_fused(
            [entry], traces, out, mu=mu, alpha=alpha, waitout=waitout,
            strict=strict, call=tracing.next_call_id(),
        ):
            return list(out[0, 0])

    # numpy engine — the bit-for-bit oracle (and the engine for specs
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
        rule_all = round_cutoff(times_all, mu)

    for t in range(1, rounds + 1):
        k = t - 1
        # per-round timing (loads come from the kernel so load-adaptive
        # schemes can vary them per cell / per round)
        if const_load:
            times = times_all[:, k]
            cutoff, tmax, base, cand, any_cand = (a[:, k] for a in rule_all)
        else:
            # (cells, 1) loads x scalar-or-(n,) alpha: heterogeneous
            # per-worker load slopes broadcast into a (cells, n) extra
            extra = (kernel.round_loads(state, t) - inv_n)[:, None] * alpha
            times = traces[:, k, :] + extra
            cutoff, tmax, base, cand, any_cand = round_cutoff(times, mu)
        if waitout == "selective":
            gs, eff, waited = gate.admit_partial(gs, cand, times, any_cand)
            duration, wo = round_duration(
                times, cutoff, base, eff, waited, np
            )
        else:  # App-J fallback: wait out all workers on violation
            gs, eff, ok_any = gate.admit_all(gs, cand, any_cand)
            duration, wo = round_duration_all(
                tmax, base, any_cand, ok_any, np
            )
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

    Every field comes from whole-member arrays: the dicts and counts
    from one ``.tolist()`` of each bookkeeping array, each cell's
    ``round_times`` and ``effective_pattern`` from a copy of its own.
    ``job_done_time=None`` (the jax path) gathers each job's elapsed
    time ``rt[c, :done_round[c, j]].sum()`` from the prefix table
    ``E[t] = rt[:, :t].sum(axis=1)``, ``E[0] = 0``: one reduction per
    round over all cells instead of one per (cell, job).  Each row of
    a round's reduction is numpy's pairwise summation over a contiguous
    run, the reduction the numpy engine's incremental accounting
    performs, so both paths agree bitwise given identical ``rt``.
    ``np.cumsum`` sums sequentially and would break that.
    """
    if strict and bool(dead.any()):
        bad = np.flatnonzero(dead).tolist()
        raise AssertionError(
            f"{scheme_name}: wait-out contract violated in cell(s) "
            f"{bad[:5]}"
        )
    valid = ~dead.astype(bool) & (done_round[:, 1:] != 0).all(axis=1)
    if strict and not valid.all():
        done = done_round[np.flatnonzero(~valid)[0]]
        missing = np.flatnonzero(done[1:] == 0) + 1
        raise AssertionError(
            f"jobs never finished: {missing.tolist()[:5]}..."
        )
    # C order: each cell's sum runs along one contiguous row
    rt = np.ascontiguousarray(rt, dtype=np.float64)
    cells, rounds = rt.shape
    jobs = done_round[:, 1 : J + 1]
    keys = range(1, J + 1)
    if job_done_time is None:
        prefix = np.zeros((rounds + 1, cells))
        for t in range(1, rounds + 1):
            prefix[t] = rt[:, :t].sum(axis=1)
        times = np.take_along_axis(prefix.T, jobs, axis=1).tolist()
        job_done_time = [dict(zip(keys, row)) for row in times]
    total = rt.sum(axis=1).tolist()
    done_rounds = jobs.tolist()
    counts = waitouts.tolist()
    results: list[SimResult | None] = []
    for c, ok in enumerate(valid.tolist()):
        if not ok:
            results.append(None)
            continue
        results.append(
            SimResult(
                scheme=scheme_name,
                total_time=total[c],
                round_times=rt[c].copy(),
                job_done_round=dict(zip(keys, done_rounds[c])),
                job_done_time=job_done_time[c],
                waitouts=counts[c],
                effective_pattern=history[:, c].copy(),
                normalized_load=normalized_load,
            )
        )
    return results


# staged-scan runners: one vmapped scan per shape bucket
# (:func:`_bucket_key`), held in one FIFO cache, so recompilation is
# paid once per bucket, not once per call (the ``grid-jax`` bench gates
# this).  The FIFO cap (``REPRO_RUNNER_CACHE_CAP``, default 256) keeps
# long parameter sweeps from holding every compiled executable for the
# process lifetime.
_JAX_RUNNERS: dict[tuple, object] = {}
_RUNNER_CACHE_CAP_DEFAULT = 256
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
    ``evictions`` plus ``compiles`` (runners built and staged), and the
    current ``size`` / ``cap`` of the FIFO.  The ``grid-jax`` bench
    asserts one compile per shape bucket off these."""
    return dict(_CACHE_COUNTERS, size=len(_JAX_RUNNERS),
                cap=_runner_cache_cap())


def clear_runner_cache() -> None:
    """Drop every cached runner and zero the :func:`cache_stats`
    counters (benchmarks use this to measure cold-start compiles)."""
    _JAX_RUNNERS.clear()
    for k in _CACHE_COUNTERS:
        _CACHE_COUNTERS[k] = 0


def _runner_cache_lookup(key: tuple, build):
    """FIFO-cached runner lookup; ``build()`` runs on a miss."""
    entry = _JAX_RUNNERS.get(key)
    if entry is not None:
        _CACHE_COUNTERS["hits"] += 1
        return entry
    _CACHE_COUNTERS["misses"] += 1
    with tracing.span("sim.runner_build"):
        entry = build()
    _CACHE_COUNTERS["compiles"] += 1
    cap = _runner_cache_cap()
    while len(_JAX_RUNNERS) >= cap:
        _JAX_RUNNERS.pop(next(iter(_JAX_RUNNERS)))
        _CACHE_COUNTERS["evictions"] += 1
    _JAX_RUNNERS[key] = entry
    return entry


def _stageable(kernel, gate, waitout: str) -> bool:
    """Can the static-shape scan path express this spec?  The planner
    routes unstageable specs (and kernel-less schemes) to the numpy
    engine BEFORE bucketing.  False when: load-adaptive
    ``round_loads`` overrides (the timing precompute assumes one
    constant load), or — in selective wait-out — gate members without
    the analytic ``min_drops_batch`` solver.  ``gate`` is the spec's
    gate, needed only in selective wait-out."""
    if type(kernel).round_loads is not SchemeKernel.round_loads:
        return False
    if waitout == "selective":
        return gate.analytic
    return True


def _staged_lockstep_run(kernel, gate, rounds: int, selective: bool,
                         traces_dev, mu, alpha, load):
    """One spec's whole (cells x rounds) lockstep sweep as a ``scan``
    over the rounds axis — the pure traced core of the (vmapped) bucket
    runner.  ``mu``, ``alpha`` and ``load`` are traced scalars (per-spec
    lanes of the stacked arrays under ``vmap``)."""
    import jax
    import jax.numpy as jnp

    bkj = kernel.bk
    inv_n = 1.0 / kernel.n
    cells = traces_dev.shape[0]
    extra = (load - inv_n) * alpha
    times_all = traces_dev + extra                  # (cells, rounds, n)
    cls, flat0 = state_flatten(kernel.init_state(cells))
    gs0 = gate.init_state(cells)

    def round_body(carry, xs):
        flat, bufs, alive = carry
        t, times = xs
        state = state_unflatten(cls, list(flat))
        # the numpy engine's round rule, one round at a time under the
        # scan
        cutoff, tmax, base, cand, any_cand = round_cutoff(times, mu)
        gs = GateState(bufs=list(bufs), alive=alive,
                       filled=gate.full, history=None)
        if selective:
            with jax.named_scope("gate"):
                gs, eff, waited = gate.admit_partial(
                    gs, cand, times, any_cand
                )
            duration, wflag = round_duration(
                times, cutoff, base, eff, waited, jnp
            )
        else:
            with jax.named_scope("gate"):
                gs, eff, ok_any = gate.admit_all(gs, cand, any_cand)
            duration, wflag = round_duration_all(
                tmax, base, any_cand, ok_any, jnp
            )
        with jax.named_scope("scheme_step"):
            state = kernel.step(state, t, eff)
        _, flat = state_flatten(state)
        return (
            (tuple(flat), tuple(gs.bufs), gs.alive),
            (duration, eff, wflag),
        )

    def body(carry, xs):
        with jax.named_scope("round"):
            return round_body(carry, xs)

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
    of a ``simulate_batch`` call replays the same trace set.  The
    planner has already checked that the spec is stageable.
    """
    bkj = get_backend("jax")
    kernel0 = make_kernel(scheme, bkj)
    gate0 = GateKernel(scheme.design_model, scheme.n, bkj)
    assert _stageable(kernel0, gate0, waitout), scheme.name
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


def _upload(traces: np.ndarray, call: int, buckets: int = 1):
    """Copy ``traces`` to the device in one ``sim.upload`` span, whose
    ``buckets`` counts the bucket programs that read this copy.  The
    copy returns as soon as the runtime lets it: the first fetch that
    needs it waits."""
    import jax

    with tracing.span("sim.upload", call=call, bytes=int(traces.nbytes),
                      buckets=buckets):
        return jax.device_put(traces)


def _staged_call(run, traces_dev, call: int) -> dict:
    """Launch ``run`` on the uploaded ``traces_dev`` and fetch its
    outputs, each step in its own span.  Only the fetch waits for the
    device: the launch returns as soon as the runtime lets it."""
    import jax

    with tracing.span("sim.dispatch", call=call):
        out = run(traces_dev)
    with tracing.span("sim.fetch", call=call) as sp:
        host = jax.device_get(out)
        if tracing.collecting():
            sp.set_metadata(bytes=sum(int(np.asarray(x).nbytes)
                                      for x in jax.tree.leaves(host)))
    return host


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


def _bucket_key(scheme, kern, params: dict, J: int, waitout: str,
                num_traces: int, seed: int) -> tuple:
    """The runner-cache key of a stageable spec: its full STATIC
    signature.  Scheme name + registered factory/kernel OBJECTS (hashed
    by identity, and the key reference keeps them alive so a freed
    address can never be recycled into a colliding id — re-registering
    a scheme or kernel never hits a stale compiled runner), the
    non-fused ("structural") parameters, n, J, T, waitout, the trace
    count, and — for seed-sensitive schemes, whose load-only stepping
    reads the code coefficients — the seed.  The kernel's
    ``fused_params`` values are excluded: they stack into per-bucket
    spec-axis arrays instead."""
    from .kernel import _KERNELS
    from .schemes import _SCHEME_FACTORIES

    fused_names = tuple(kern.fused_params)
    sensitive = (
        getattr(scheme, "seed_sensitive", False)
        or kernel_seed_sensitive(scheme.name)
    )
    structural = tuple(sorted(
        (str(k), v) for k, v in params.items() if k not in fused_names
    ))
    return (
        "grid",
        scheme.name,
        _SCHEME_FACTORIES.get(scheme.name),
        _KERNELS.get(scheme.name),
        structural,
        fused_names,
        scheme.n,
        J,
        kern.T,
        waitout,
        num_traces,
        seed if sensitive else None,
    )


def _plan_buckets(entries, traces_shape, waitout, strict, out):
    """Group stageable run entries into shape buckets (the grid-fusion
    planner, keyed by :func:`_bucket_key`).  Entries the staged path
    cannot express — kernel-less schemes, load-adaptive loads,
    non-analytic gates — come back as leftovers for the numpy engine;
    entries whose constructor rejects the fitted J mark their rows
    (strict raises).
    """
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
        key = _bucket_key(scheme, kern, e.params, e.J, waitout,
                          num_traces, e.seed)
        bucket = buckets.get(key)
        if bucket is None:
            bucket = buckets[key] = _Bucket(
                key, e.J, kern.T, tuple(kern.fused_params), scheme
            )
        bucket.members.append((e, scheme, kern.fused_scalars(scheme)))
    return leftover, list(buckets.values())


def _simulate_batch_fused(entries, traces, out, *, mu, alpha, waitout,
                          strict, call):
    """Run the stageable entries of a grid bucket-by-bucket: one
    ``vmap``-wrapped jitted scan and ONE device->host transfer per
    shape bucket.  Every bucket replays the same traces, so they cross
    to the device once a call; a bucket whose J+T falls short of the
    trace length reads a device slice of its rounds, so its program
    takes only its own J+T.  Returns the entries left for the numpy
    engine."""
    import jax
    import jax.numpy as jnp

    with tracing.span("sim.plan", call=call):
        leftover, buckets = _plan_buckets(
            entries, traces.shape, waitout, strict, out
        )
    if not buckets:
        return leftover
    rounds_avail = traces.shape[1]
    with jax.enable_x64(True):
        traces_dev = _upload(traces, call, buckets=len(buckets))
        for b in buckets:
            runner, kernel_name = _runner_cache_lookup(
                b.key,
                lambda b=b: _build_jax_grid_runner(
                    b.scheme0, b.J, waitout, b.fused_names
                ),
            )
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
            host = _staged_call(
                lambda traces: runner(
                    mu_s, alpha_s, load_s, fused,
                    traces if rounds == rounds_avail
                    else traces[:, :rounds],
                ),
                traces_dev, call,
            )
            with tracing.span("sim.assemble", call=call,
                              cells=S * traces.shape[0]):
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
    ``fallback`` list of specs the numpy engine runs (stageability
    blockers), or
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
    fuse: bool = True,
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
    gates this).  Specs the staged path cannot express run on the numpy
    engine, with identical results either way (exact bool/int
    bookkeeping, allclose floats — ``tests/test_grid_fused.py``).
    ``fuse`` is accepted for callers that pass ``True``; there is no
    other jax engine to turn to.

    Otherwise each spec advances all of its traces in lockstep on the
    numpy engine (:func:`simulate_lockstep`); ragged grids are fine —
    every spec
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
    if not fuse:
        raise ValueError("simulate_batch runs the staged buckets only; "
                         "use backend='numpy' for the lockstep engine")
    traces = np.asarray(traces, dtype=np.float64)
    if traces.ndim == 2:
        traces = traces[None]
    call = tracing.next_call_id()
    with tracing.span("sim.batch", call=call) as sp:
        num_traces, rounds_avail, n = traces.shape
        out = np.empty((len(specs), len(seeds), num_traces), dtype=object)
        with tracing.span("sim.plan", call=call):
            entries, sensitive_map = _plan_entries(
                specs, traces, seeds, J, strict, out
            )
        planned = entries
        bk_name = backend if backend is not None else get_backend().name
        if bk_name == "jax":
            entries = _simulate_batch_fused(
                entries, traces, out, mu=mu, alpha=alpha, waitout=waitout,
                strict=strict, call=call,
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
                        backend="numpy",
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
        if tracing.collecting():
            # lane-rounds run: the seed-broadcast rows below count once
            sp.set_metadata(lane_rounds=sum(
                len(r.round_times) for e in planned
                for r in out[e.si, e.ki] if r is not None
            ))
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
