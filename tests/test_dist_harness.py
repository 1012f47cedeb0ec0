"""End-to-end contract of the REAL master/worker execution harness
(``repro.dist``): real processes, real coded partial gradients, real
wall clock — against the analytic simulators.

The acceptance pins:

* every job decodes exactly (vs the full-batch gradient truth);
* the recorded straggler pattern and analytic round clocks replay
  BIT-IDENTICALLY through ``simulate_fast`` on the enacted trace;
* injected message drops recover through the timeout/resend path;
* a permanently dead worker degrades to an always-straggler row —
  on the live harness AND (via ``dead_worker_delays``) on both
  simulation backends — without poisoning decode of surviving rows.
"""

import numpy as np
import pytest

from repro.core import (
    GilbertElliotSource,
    make_scheme,
    simulate_fast,
    simulate_lockstep,
)
from repro.core.testing import assert_sim_parity, dead_worker_delays
from repro.dist import FaultSpec, HarnessConfig, run_harness

N = 4
SCALE = 0.01
GE = dict(p_ns=0.15, p_sn=0.5, slow_factor=5.0, jitter=0.05)


def _delays(rounds, seed=7):
    return GilbertElliotSource(n=N, seed=seed, **GE).sample_delays(rounds)


def _cfg(**kw):
    base = dict(alpha=8.0, time_scale=SCALE, seed=1)
    base.update(kw)
    return HarnessConfig(**base)


@pytest.mark.parametrize("name,params", [
    ("gc", {"s": 1}),
    ("m-sgc", {"B": 1, "W": 3, "lam": N}),
    ("uncoded", {}),
])
def test_real_rounds_decode_and_replay(name, params):
    J = 5
    delays = _delays(J + 4)
    res = run_harness(name, N, J, delays, params=params, config=_cfg())
    assert not res.aborted, res.abort_reason
    assert sorted(res.decoded_jobs) == list(range(1, J + 1))
    assert res.decode_max_err < 1e-8
    sim = simulate_fast(make_scheme(name, N, J, **params), delays,
                        mu=1.0, alpha=8.0, J=J)
    # the recording replays bit-identically through the simulator
    assert np.array_equal(res.trace_model.pattern, sim.effective_pattern)
    assert np.allclose(res.analytic_round_times, sim.round_times * SCALE)
    assert res.decoded_jobs == sim.job_done_round
    # the TraceModel recording survives its own JSON round-trip
    back = type(res.trace_model).from_json(res.trace_model.to_json())
    assert np.array_equal(back.pattern, res.trace_model.pattern)
    # measured wall clock tracks the analytic clock (loose bound here;
    # the dist-exec bench owns the documented tolerance gate)
    assert res.measured_makespan >= 0.9 * res.analytic_makespan


def test_message_drops_recover_via_retry():
    J = 4
    delays = _delays(J + 2, seed=11)
    cfg = _cfg(round_timeout=0.25,
               faults={1: FaultSpec(drop_rounds=frozenset({1, 3}))})
    res = run_harness("gc", N, J, delays, params={"s": 1}, config=cfg)
    assert not res.aborted, res.abort_reason
    assert res.retries >= 1
    assert sorted(res.decoded_jobs) == list(range(1, J + 1))
    assert res.decode_max_err < 1e-8


def test_ledger_telemetry_is_coherent():
    J = 4
    delays = _delays(J + 2, seed=3)
    res = run_harness("gc", N, J, delays, params={"s": 1}, config=_cfg())
    assert not res.aborted
    led = res.ledger
    assert led.rounds == len(res.round_times)
    tim = led.measured_times()
    # non-straggler rounds have a full complement of reported times
    clean = ~res.trace_model.pattern.any(axis=1)
    assert np.isfinite(tim[clean]).all()
    # worker-side telemetry ordering: recv -> (+compute+delay) <= sent
    for rec in led.records:
        for st in rec.stats:
            if st.reported is None:
                continue
            assert st.sent <= st.reported
            assert st.compute_s >= 0 and st.delay_s >= 0
    assert led.measured_makespan() == pytest.approx(
        res.measured_makespan)
    assert res.trace_model.timings.shape == (led.rounds, N)


# ---------------------------------------------------------------------------
# permanent worker death
# ---------------------------------------------------------------------------


def test_dead_worker_becomes_always_straggler_without_poisoning_decode():
    J, r_die, w = 5, 2, 3
    delays = _delays(J + 2, seed=5)
    cfg = _cfg(round_timeout=0.25,
               faults={w: FaultSpec(kill_after=r_die)})
    res = run_harness("gc", N, J, delays, params={"s": 1}, config=cfg)
    assert not res.aborted, res.abort_reason
    assert res.deaths == [w]
    pat = res.trace_model.pattern
    # always-straggler row from the round after the last report on
    assert pat[r_die:, w].all()
    # surviving rows still decode every job exactly
    assert sorted(res.decoded_jobs) == list(range(1, J + 1))
    assert res.decode_max_err < 1e-8
    # and the live run matches the simulator fed the death-transformed
    # trace (the same always-straggler row, admitted by the same gate)
    sim = simulate_fast(
        make_scheme("gc", N, J, s=1),
        dead_worker_delays(delays, w, r_die + 1),
        mu=1.0, alpha=8.0, J=J,
    )
    assert np.array_equal(pat, sim.effective_pattern)


@pytest.mark.parametrize("backend", [
    "numpy", "jax",
])
def test_dead_worker_row_on_both_backends(backend):
    n, J, r_die, w = 8, 10, 4, 2
    base = GilbertElliotSource(n=n, seed=9, **GE).sample_delays(J + 4)
    traces = dead_worker_delays(base, w, r_die)[None]
    # per-round design models: the only family whose gate can admit a
    # permanent always-straggler row (a bursty model's B bound must
    # eventually wait the dead worker out, ending the run)
    for name, kw in [("gc", {"s": 2}),
                     ("gc", {"s": 3, "prefer_rep": False})]:
        ref = simulate_fast(make_scheme(name, n, J, **kw), traces[0],
                            mu=1.0, alpha=6.0, J=J)
        assert ref.effective_pattern[r_die - 1:, w].all()
        # decode bookkeeping of surviving rows is intact: every job
        # finishes by its deadline despite the dead lane
        assert sorted(ref.job_done_round) == list(range(1, J + 1))
        got = simulate_lockstep(name, kw, traces, alpha=6.0, J=J,
                                backend=backend)[0]
        assert_sim_parity(ref, got, exact=(backend == "numpy"))
        assert got.effective_pattern[r_die - 1:, w].all()
