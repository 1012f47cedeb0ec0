"""The simulator cells' comparison: the plain reference agrees with the
program where it is sound, its float32 control fails, and runs with a
fault planted under the timed path come out not correct."""

import numpy as np
import pytest

from bench import ge
from bench.drivers.sweep import sim_checks
from bench.refs import sgc_sim
from bench.tests import _tiny

GE = dict(p_ns=0.035, p_sn=0.85, slow_factor=6.0, jitter=0.05)
#: a fleet where the gate waits workers out from the first rounds on,
#: before any model's window has filled
HEAVY = dict(p_ns=0.3, p_sn=0.5, slow_factor=6.0, jitter=0.05)
SPECS = [("m-sgc", {"B": 2, "W": 3, "lam": 7}),
         ("m-sgc", {"B": 1, "W": 2, "lam": 3}),
         ("sr-sgc", {"B": 2, "W": 3, "lam": 6}),
         ("sr-sgc", {"B": 1, "W": 2, "lam": 7}),
         ("sr-sgc", {"B": 1, "W": 3, "lam": 5}),
         ("gc", {"s": 3}), ("gc", {"s": 5}), ("uncoded", {})]


@pytest.mark.parametrize("fleet", [GE, HEAVY], ids=["fig1", "heavy"])
@pytest.mark.parametrize("name,params", SPECS)
def test_reference_matches_the_numpy_engine(name, params, fleet):
    """Second witness: the program's numpy lockstep engine and the
    reference agree to the bit at a small size."""
    from repro.core import simulate_batch

    n, J = 32, 30
    traces = ge.delays(np.random.default_rng(7), 3, J + 3, n, **fleet)
    res = simulate_batch([(name, params)], traces, mu=1.0, alpha=8.0, J=J,
                         backend="numpy")
    for k in range(traces.shape[0]):
        r = res[0, 0, k]
        ref = sgc_sim.simulate(name, params, traces[k], mu=1.0, alpha=8.0,
                               J=J)
        assert np.array_equal(r.round_times, ref["rt"])
        assert np.array_equal(r.effective_pattern, ref["history"])
        assert r.waitouts == int(ref["waited"].sum())
        assert [r.job_done_round[j] for j in range(1, J + 1)] == \
            ref["done_round"][1:].tolist()


class _Res:
    def __init__(self, ref):
        self.round_times = ref["rt"].astype(np.float64)
        self.effective_pattern = ref["history"]
        self.waitouts = int(ref["waited"].sum())
        self.job_done_round = {j: int(r) for j, r in
                               enumerate(ref["done_round"]) if j}


def _pairs(dtype):
    cell = _tiny.sweep_cell()
    cfg = cell.config
    traces = ge.delays(np.random.default_rng(3), 4, cfg["jobs"] + 3,
                       cfg["workers"], **cfg["ge"])
    out = []
    for k, s in enumerate(cfg["table1"]):
        ref = sgc_sim.simulate(s["scheme"], s["params"], traces[k],
                               mu=cfg["mu"], alpha=cfg["alpha"],
                               J=cfg["jobs"], dtype=dtype)
        out.append((_Res(ref), traces[k], s["scheme"], s["params"],
                    cfg["jobs"]))
    return cell, out


@pytest.mark.parametrize("dtype,ok", [(np.float64, True),
                                      (np.float32, False)])
def test_float32_control_fails_the_sweep_limits(dtype, ok):
    cell, pairs = _pairs(dtype)
    checks = sim_checks(pairs, cell.config, cell.traffic["limits"])
    assert all(c["value"] <= c["limit"] for c in checks) is ok


def test_sound_run_is_correct():
    line = _tiny.run(_tiny.sweep_cell())
    assert line["correct"], line
    assert line["metrics"]["sweep_rounds_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"


def _planted(monkeypatch, fault):
    """Break the timed path from the first call after set-up on."""
    import repro.core
    from repro.core import batch

    batch.clear_runner_cache()
    assemble = batch._assemble_results
    full = repro.core.simulate_batch
    calls = []

    def altered(*args, **kw):
        args = list(args)
        if len(calls) > 1 and fault == "answer":
            rt = np.array(args[3], dtype=np.float64)
            rt[:, 0] *= 1.0 + 1e-6
            args[3] = rt
        elif len(calls) > 1 and fault == "state":
            args[4] = np.zeros_like(args[4])      # no job ever done
        return assemble(*args, **kw)

    def counted(specs, traces, **kw):
        calls.append(1)
        if len(calls) > 1 and fault == "half":
            k = traces.shape[0] // 2
            res = full(specs, traces[:k], **kw)
            return np.concatenate([res, res], axis=2)
        return full(specs, traces, **kw)

    monkeypatch.setattr(batch, "_assemble_results", altered)
    monkeypatch.setattr(repro.core, "simulate_batch", counted)


@pytest.mark.parametrize("fault", ["answer", "state", "half"])
def test_run_with_a_planted_fault_is_not_correct(monkeypatch, fault):
    _planted(monkeypatch, fault)
    cell = _tiny.sweep_cell()
    cell.traffic = dict(cell.traffic, compare=8)
    line = _tiny.run(cell)
    assert line["correct"] is False, line
