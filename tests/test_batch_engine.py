"""Differential tests: the vectorized batch engine must reproduce the
legacy scalar simulator bit-for-bit.

``simulate`` (+ ``assign``/``observe``/``collect`` + suffix-rescanning
gate) is the oracle; ``simulate_fast`` / ``simulate_batch`` (+
``step``/``collect_jobs`` + rolling-tracker gate + broadcast round
precompute) must match every ``SimResult`` field exactly — not to a
tolerance — across all four schemes, several seeds, and both wait-out
modes.
"""

import numpy as np
import pytest

from repro.core import (
    GilbertElliotSource,
    SimResult,
    estimate_alpha,
    get_backend,
    make_scheme,
    select_parameters,
    select_parameters_legacy,
    simulate,
    simulate_batch,
    simulate_fast,
)
from repro.core.testing import assert_sim_parity

GE = dict(p_ns=0.08, p_sn=0.6, slow_factor=6.0)

CONFIGS = [
    ("gc", dict(s=3)),                     # 4 | 12 -> GC-Rep
    ("gc", dict(s=3, prefer_rep=False)),   # general code
    ("gc", dict(s=4)),                     # 5 does not divide 12 -> general
    ("sr-sgc", dict(B=1, W=2, lam=3)),
    ("sr-sgc", dict(B=2, W=3, lam=5)),
    ("m-sgc", dict(B=1, W=2, lam=3)),
    ("m-sgc", dict(B=2, W=3, lam=5)),
    ("m-sgc", dict(B=1, W=3, lam=12)),     # lam == n (Remark 3.2, no D2)
    ("uncoded", {}),
]


def _assert_identical(ra, rb):
    """Bit-for-bit on the numpy backend; under ``REPRO_BACKEND=jax``
    (where ``simulate_batch`` routes through the jitted scan engine)
    the bool/int bookkeeping stays exact and floats are allclose."""
    assert_sim_parity(ra, rb, exact=get_backend().name == "numpy")


@pytest.mark.parametrize("name,kw", CONFIGS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(CONFIGS)])
@pytest.mark.parametrize("waitout", ["selective", "all"])
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_fast_matches_legacy_bitforbit(name, kw, waitout, seed):
    n, J = 12, 25
    src = GilbertElliotSource(n=n, seed=seed, **GE)
    sch = make_scheme(name, n, J, **dict(kw))
    delays = src.sample_delays(J + sch.T + 1)
    alpha = estimate_alpha(src)
    ra = simulate(sch, delays, mu=1.0, alpha=alpha, J=J, waitout=waitout)
    rb = simulate_fast(make_scheme(name, n, J, **dict(kw)), delays,
                       mu=1.0, alpha=alpha, J=J, waitout=waitout)
    _assert_identical(ra, rb)
    # a straggler-heavy run is only meaningful if the gate actually fired
    if name != "uncoded" and waitout == "selective":
        assert ra.waitouts > 0 or ra.effective_pattern.any()


def test_fast_matches_legacy_table1_point():
    """Spot check at the paper's n=256 operating point."""
    n, J = 256, 8
    src = GilbertElliotSource(n=n, seed=0, p_ns=0.035, p_sn=0.85,
                              slow_factor=6.0, jitter=0.05)
    delays = src.sample_delays(J + 4)
    alpha = estimate_alpha(src)
    for name, kw in [("m-sgc", dict(B=2, W=3, lam=27)),
                     ("sr-sgc", dict(B=2, W=3, lam=23)),
                     ("gc", dict(s=15))]:
        ra = simulate(make_scheme(name, n, J, **dict(kw)), delays,
                      mu=1.0, alpha=alpha, J=J)
        rb = simulate_fast(make_scheme(name, n, J, **dict(kw)), delays,
                           mu=1.0, alpha=alpha, J=J)
        _assert_identical(ra, rb)


def test_simulate_batch_matches_scalar_runs():
    """Every cell of a (specs x seeds x traces) grid equals the scalar
    fast run (which equals the oracle by the tests above)."""
    n = 12
    specs = [("m-sgc", {"B": 1, "W": 2, "lam": 3}), ("gc", {"s": 3})]
    traces = np.stack([
        GilbertElliotSource(n=n, seed=10 + k, **GE).sample_delays(20)
        for k in range(2)
    ])
    seeds = (0, 5)
    grid = simulate_batch(specs, traces, seeds=seeds, alpha=4.0)
    assert grid.shape == (len(specs), len(seeds), traces.shape[0])
    for i, (name, params) in enumerate(specs):
        for k, seed in enumerate(seeds):
            for t in range(traces.shape[0]):
                res = grid[i, k, t]
                J = res.rounds - make_scheme(name, n, 1, seed=seed,
                                             **dict(params)).T
                ref = simulate(
                    make_scheme(name, n, J, seed=seed, **dict(params)),
                    traces[t], alpha=4.0, J=J,
                )
                _assert_identical(ref, res)


def test_simulate_batch_strict_false_marks_infeasible():
    n = 12
    specs = [("sr-sgc", {"B": 2, "W": 4, "lam": 3}),   # B does not divide W-1
             ("gc", {"s": 3})]
    traces = GilbertElliotSource(n=n, seed=1, **GE).sample_delays(15)[None]
    grid = simulate_batch(specs, traces, alpha=4.0, strict=False)
    assert grid[0, 0, 0] is None
    assert grid[1, 0, 0] is not None
    with pytest.raises(ValueError):
        simulate_batch(specs, traces, alpha=4.0, strict=True)


def test_select_parameters_matches_legacy_oracle():
    """Rewritten App.-J selection picks the identical candidate (params,
    load AND per-job estimate) as the per-candidate legacy loop."""
    n = 16
    delays = GilbertElliotSource(n=n, seed=3).sample_delays(24)
    grids = {
        "gc": None,  # default grid
        "m-sgc": [{"B": B, "W": B + 1, "lam": lam}
                  for B in (1, 2) for lam in (2, 4, 8)],
        "sr-sgc": [{"B": B, "W": B + 1, "lam": lam}
                   for B in (1, 2) for lam in (2, 4, 8)],
    }
    for name, grid in grids.items():
        fast = select_parameters(name, n, delays, grid=grid)
        legacy = select_parameters_legacy(name, n, delays, grid=grid)
        assert fast.params == legacy.params, name
        assert fast.load == legacy.load, name
        assert fast.est_time == legacy.est_time, name


def test_fast_path_skips_decode_and_minitasks():
    """The load-only path must not trigger the O(n^3) encode build."""
    n, J = 12, 10
    sch = make_scheme("gc", n, J, s=4)  # general code (5 does not divide 12)
    delays = GilbertElliotSource(n=n, seed=2, **GE).sample_delays(J + 1)
    simulate_fast(sch, delays, alpha=4.0, J=J)
    assert sch.code._matrix is None, "fast path built the encode matrix"


# -- result assembly: whole-member arrays against the per-cell oracle --


def _assemble_per_cell(scheme_name, normalized_load, J, rt, done_round,
                       dead, waitouts, history, strict, job_done_time=None):
    """The per-(cell, job) assembly, one numpy sum per job: the oracle
    ``batch._assemble_results`` must reproduce field for field."""
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
    results = []
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
        results.append(SimResult(
            scheme=scheme_name,
            total_time=float(rt[c].sum()),
            round_times=rt[c].copy(),
            job_done_round={j: int(done[j]) for j in range(1, J + 1)},
            job_done_time=job_done_time[c],
            waitouts=int(waitouts[c]),
            effective_pattern=np.ascontiguousarray(history[:, c]),
            normalized_load=normalized_load,
        ))
    return results


def _lockstep_outputs(J, T, seed=0, cells=6, n=8):
    """Lockstep outputs of ``cells`` cells: round times spread over six
    decades (so summation order shows in the bits), each job decoded
    within T rounds of its own."""
    rng = np.random.default_rng(seed)
    rounds = J + T
    rt = rng.exponential(size=(cells, rounds)) * 10.0 ** rng.uniform(
        -3, 3, size=(cells, rounds))
    done = np.zeros((cells, J + 1), dtype=np.int64)
    done[:, 1:] = np.arange(1, J + 1) + rng.integers(0, T + 1, (cells, J))
    dead = np.zeros(cells, dtype=bool)
    waitouts = rng.integers(0, rounds + 1, cells)
    history = rng.random((rounds, cells, n)) < 0.8
    return rt, done, dead, waitouts, history


def _dead(out):
    out[2][4] = True


def _unfinished(out):
    out[1][1, 3] = 0
    out[1][1, -1] = 0


def _dead_after_unfinished(out):
    _unfinished(out)
    _dead(out)


ASSEMBLY_CASES = {
    "gc-T0": (40, 0, None),
    "T2": (40, 2, None),
    "J1": (1, 1, None),
    "dead-cell": (20, 1, _dead),
    "unfinished-job": (20, 1, _unfinished),
    "dead-after-unfinished": (20, 1, _dead_after_unfinished),
    "given-job-times": (20, 2, None),
}


@pytest.mark.parametrize("strict", [False, True])
@pytest.mark.parametrize("case", list(ASSEMBLY_CASES))
def test_assemble_results_matches_per_cell_oracle(case, strict):
    """``_assemble_results`` builds every cell's ``SimResult`` from
    whole-member arrays; each field must equal the per-cell oracle's
    to the bit, failing cells included (``None``, or under ``strict``
    the same exception), with Python scalars in the dicts and arrays
    no two results share."""
    from repro.core import batch

    J, T, plant = ASSEMBLY_CASES[case]
    out = _lockstep_outputs(J, T)
    if plant is not None:
        plant(out)
    rt, done, dead, waitouts, history = out
    given = None
    if case == "given-job-times":
        given = [{j: 0.25 * j + c for j in range(1, J + 1)}
                 for c in range(rt.shape[0])]
    args = ("m-sgc", 0.375, J, rt, done, dead, waitouts, history, strict)

    if strict and plant is not None:
        with pytest.raises(AssertionError) as want:
            _assemble_per_cell(*args)
        with pytest.raises(AssertionError) as got:
            batch._assemble_results(*args)
        assert str(got.value) == str(want.value)
        return

    want = _assemble_per_cell(*args, given)
    got = batch._assemble_results(*args, given)
    assert [r is None for r in got] == [r is None for r in want]
    assert any(r is not None for r in got)
    kept = [r for r in got if r is not None]
    for c, (w, g) in enumerate(zip(want, got)):
        if w is None:
            continue
        assert_sim_parity(w, g, exact=True)
        if given is not None:
            assert g.job_done_time is given[c]
        assert type(g.total_time) is float and type(g.waitouts) is int
        for d, kind in ((g.job_done_round, int), (g.job_done_time, float)):
            assert all(type(j) is int for j in d)
            assert all(type(v) is kind for v in d.values())
        for a in (g.round_times, g.effective_pattern):
            assert a.flags.c_contiguous and a.flags.owndata
            assert not np.shares_memory(a, rt)
            assert not np.shares_memory(a, history)
    for i, a in enumerate(kept):
        for b in kept[i + 1:]:
            assert not np.shares_memory(a.round_times, b.round_times)
            assert not np.shares_memory(a.effective_pattern,
                                        b.effective_pattern)


def test_assembly_data_tells_pairwise_from_sequential_sums():
    """The oracle's data is fit to catch a prefix sum taken with
    ``np.cumsum``: sequential and pairwise sums differ in the bits of
    some job's time there."""
    J = 200
    rt, done, *_ = _lockstep_outputs(J, 0)
    sequential = np.cumsum(rt, axis=1)
    pairwise = np.array([[rt[c, :j].sum() for j in range(1, J + 1)]
                         for c in range(rt.shape[0])])
    assert (sequential != pairwise).any()
