"""The round rule written once in ``core.simulator``: the §2 mu-rule
(``round_cutoff``), the wait-out durations (``round_duration``,
``round_duration_all``) on numpy and jax arrays, the host-side
``RoundGate`` that the scalar simulators and the trainers use, the
harness master's analytic duration, and the one staged runner that
``simulate_lockstep`` and ``simulate_batch`` share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    cache_stats,
    clear_runner_cache,
    simulate_batch,
    simulate_lockstep,
)
from repro.core.simulator import (
    RoundGate,
    round_cutoff,
    round_duration,
    round_duration_all,
)
from repro.core.straggler import ConformanceGate, PerRoundModel

MU = 1.0
F, T = False, True

#: (times, per-round straggler budget s, waitout, effective stragglers,
#: waited workers, duration).  Every time is exact in binary, so both
#: backends reach the same numbers to the bit.
ROUNDS = {
    # nobody past the cutoff 2.0: the round closes at the slowest, 1.75
    "no-candidate": ([1.0, 1.5, 1.25, 1.75], 1, "selective",
                     [F, F, F, F], [F, F, F, F], 1.75),
    # one candidate within the budget: cancelled at the cutoff
    "all-admitted": ([1.0, 3.0, 1.25, 1.125], 1, "selective",
                     [F, T, F, F], [F, F, F, F], 2.0),
    # two candidates, budget one: the cheaper is waited out
    "partial-wait": ([1.0, 3.0, 4.0, 1.125], 1, "selective",
                     [F, F, T, F], [F, T, F, F], 3.0),
    # budget zero: every candidate is waited out
    "all-waited": ([1.0, 3.0, 4.0, 1.125], 0, "selective",
                   [F, F, F, F], [F, T, T, F], 4.0),
    # App. J: the gate refuses, everyone is waited out
    "all-mode-violation": ([1.0, 3.0, 4.0, 1.125], 1, "all",
                           [F, F, F, F], [F, T, T, F], 4.0),
}


def _vector_round(xp, times, waitout, eff, waited):
    """The vector form on one round, given the gate's verdict."""
    times = xp.asarray(times)
    cutoff, tmax, base, cand, any_cand = round_cutoff(times, MU)
    if waitout == "selective":
        dur, wo = round_duration(times, cutoff, base, xp.asarray(eff),
                                 xp.asarray(waited), xp)
    else:
        admitted = xp.asarray(not any(waited))
        dur, wo = round_duration_all(tmax, base, any_cand, admitted, xp)
    return cutoff, tmax, base, cand, any_cand, dur, wo


@pytest.mark.parametrize("case", list(ROUNDS))
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_vector_rule_per_round(xp, case):
    times, _, waitout, eff, waited, want = ROUNDS[case]
    with jax.enable_x64(True):
        cutoff, tmax, base, cand, any_cand, dur, wo = _vector_round(
            xp, times, waitout, eff, waited)
        assert float(cutoff) == 2.0 * min(times)
        assert float(tmax) == max(times)
        assert float(base) == min(2.0 * min(times), max(times))
        assert np.asarray(cand).tolist() == [t > 2.0 for t in times]
        assert bool(any_cand) == any(t > 2.0 for t in times)
        assert float(dur) == want
        assert bool(wo) == any(waited)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_vector_rule_over_leading_axes(xp):
    """Five rounds stacked on a leading axis give what each round gives
    alone."""
    rows = list(ROUNDS.values())
    with jax.enable_x64(True):
        stacked = round_cutoff(xp.asarray([r[0] for r in rows]), MU)
        for i, (times, *_) in enumerate(rows):
            alone = round_cutoff(xp.asarray(times), MU)
            for a, b in zip(stacked, alone, strict=True):
                assert np.asarray(a)[i].tolist() == np.asarray(b).tolist()
        selective = [r for r in rows if r[2] == "selective"]
        times = xp.asarray([r[0] for r in selective])
        cutoff, _, base, _, _ = round_cutoff(times, MU)
        dur, wo = round_duration(
            times, cutoff, base, xp.asarray([r[3] for r in selective]),
            xp.asarray([r[4] for r in selective]), xp)
        assert np.asarray(dur).tolist() == [r[5] for r in selective]
        assert np.asarray(wo).tolist() == [any(r[4]) for r in selective]


@pytest.mark.parametrize("case", list(ROUNDS))
def test_round_gate_matches_vector_form(case):
    """``RoundGate`` reaches the verdict of the table on a real gate,
    and the vector form's duration for it."""
    times, s, waitout, eff, waited, want = ROUNDS[case]
    rg = RoundGate(PerRoundModel(s), 4, mu=MU, waitout=waitout)
    got_eff, dur, wflag = rg.round(np.asarray(times))
    assert got_eff.tolist() == eff
    assert wflag == any(waited)
    *_, vec, _ = _vector_round(np, times, waitout, eff, waited)
    assert dur == float(vec) == want
    assert rg.history.tolist() == [eff]


def test_master_analytic_duration_with_a_wait_out():
    """The harness master's path on a planned row: mu-rule, provisional
    decision on a gate copy, analytic duration — as ``RoundGate``."""
    from repro.dist import master

    times = np.asarray([1.0, 3.0, 4.0, 1.125])
    gate = ConformanceGate(PerRoundModel(1), 4)
    cutoff, tmax, base, cand, _ = round_cutoff(times, MU)
    eff, waited = master._decide(gate, cand, times)
    assert waited == [1] and len(gate.history) == 0   # copy decided
    row = np.zeros(4, dtype=bool)
    row[waited] = True
    dur, _ = round_duration(times, cutoff, base, eff, row, np)
    rg = RoundGate(PerRoundModel(1), 4, mu=MU)
    want_eff, want, _ = rg.round(times)
    assert (eff == want_eff).all()
    assert float(dur) == want == 3.0
    assert float(dur) > float(base)      # the wait-out lengthened it


def test_lockstep_then_batch_compiles_once():
    """``simulate_lockstep(backend="jax")`` runs its spec as a
    one-member bucket, so ``simulate_batch`` of the same spec reuses
    the compiled runner."""
    traces = np.random.default_rng(3).exponential(1.0, size=(2, 10, 8))
    clear_runner_cache()
    one = simulate_lockstep("gc", {"s": 2}, traces, alpha=6.0,
                            backend="jax")
    grid = simulate_batch([("gc", {"s": 2})], traces, alpha=6.0,
                          backend="jax")
    st = cache_stats()
    assert st["compiles"] == 1 and st["hits"] == 1
    for a, b in zip(one, grid[0, 0], strict=True):
        assert a.round_times.tobytes() == b.round_times.tobytes()
