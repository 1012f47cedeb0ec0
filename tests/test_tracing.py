"""The program's spans (``repro.tracing``) in a CPU profiler trace, and
results that do not depend on them or on the named scopes."""

import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.qwen2_0_5b import SMOKE
from repro.core import batch, make_scheme, simulate_batch, simulate_lockstep
from repro.core.batch import clear_runner_cache
from repro.train import VectorizedCodedTrainer

N, J = 16, 12
SPECS = [("m-sgc", {"B": 1, "W": 2, "lam": 2}), ("gc", {"s": 3}),
         ("uncoded", {})]
CFG = SMOKE.replace(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                    head_dim=16, d_ff=64, vocab_size=64)


def _traces():
    return np.random.default_rng(0).exponential(1.0, size=(3, J + 4, N))


def _sweep():
    return simulate_batch(SPECS, _traces(), J=J, backend="jax")


def _trainer():
    sch = make_scheme("gc", 4, 4, s=1)
    return VectorizedCodedTrainer(scheme=sch, cfg=CFG, num_models=1,
                                  batch_size=4, seq_len=8, lr=1e-3, seed=3)


def _train(tr):
    delays = np.ones((4, 4))
    delays[1, 2] = 40.0
    tr.run(2, delays)
    return tr


def _profiled(tmp_path, fn):
    """``fn()`` under the profiler, and the trace's events as
    ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    # Python frames (``$file:line name``) carry no stats worth reading
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               {} if ev.name.startswith("$") else dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events]
    return out, events


def _named(events, prefix):
    return [e for e in events if e[0].startswith(prefix)]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_simulator_spans_nest_under_one_call(tmp_path):
    _sweep()                        # compiles outside the trace
    res, events = _profiled(tmp_path, _sweep)
    sim = _named(events, "sim.")
    (top,) = [e for e in sim if e[0] == "sim.batch"]
    call = top[3]["call"]
    assert all(_inside(e, top) and e[3]["call"] == call for e in sim)
    names = [e[0] for e in sim]
    buckets = 3                                     # one per scheme
    for name in ("sim.dispatch", "sim.fetch", "sim.assemble"):
        assert names.count(name) == buckets
    assert "sim.plan" in names and "sim.runner_build" not in names
    rounds = [len(r.round_times) for r in res[:, 0, 0]]
    assert top[3]["lane_rounds"] == 3 * sum(rounds)
    # every bucket reads one copy of the whole trace set
    (up,) = [e for e in sim if e[0] == "sim.upload"]
    assert up[3]["bytes"] == _traces().nbytes
    assert up[3]["buckets"] == buckets
    # each fetch brings at least the f64 round times of its bucket
    fetched = sum(e[3]["bytes"] for e in sim if e[0] == "sim.fetch")
    assert fetched >= 3 * sum(rounds) * 8
    assert {e[3]["cells"] for e in sim if e[0] == "sim.assemble"} == {3}


def test_a_runner_cache_miss_shows_by_name(monkeypatch):
    opened = []
    real = tracing.span
    monkeypatch.setattr(tracing, "span",
                        lambda name, **k: opened.append(name)
                        or real(name, **k))
    clear_runner_cache()
    _sweep()
    assert opened.count("sim.runner_build") == 3
    assert opened.count("sim.batch") == 1
    opened.clear()
    _sweep()
    assert "sim.runner_build" not in opened


def test_trainer_spans_nest_under_each_job(tmp_path):
    tr = _train(_trainer())         # compiles outside the trace
    tr, events = _profiled(tmp_path, lambda: _train(tr))
    (run,) = _named(events, "train.run")
    assert run[3]["jobs"] == 2
    rounds = _named(events, "train.round")
    assert [e[3]["t"] for e in rounds] == list(range(1, len(rounds) + 1))
    jobs = _named(events, "train.job")
    assert sorted(e[3]["job"] for e in jobs) == [1, 2]
    for job in jobs:
        assert job[3]["model"] == 0
        assert any(_inside(job, r) for r in rounds)
        kids = [e for e in _named(events, "train.")
                if e[0] in ("train.batch", "train.dispatch", "train.sync")
                and _inside(e, job)]
        assert sorted(e[0] for e in kids) == [
            "train.batch", "train.dispatch", "train.sync"]
        assert {e[3]["job"] for e in kids} == {job[3]["job"]}
    assert len(tr.losses[0]) == 4


def test_per_spec_spans_share_the_call_id(tmp_path):
    """One spec through ``simulate_lockstep(backend="jax")``: a
    one-member bucket whose spans all carry one call id."""
    name, params = SPECS[0]
    run = lambda: simulate_lockstep(name, params, _traces(), J=J,
                                    backend="jax")
    run()                           # compiles outside the trace
    _, events = _profiled(tmp_path, run)
    sim = _named(events, "sim.")
    names = sorted(e[0] for e in sim)
    assert names == ["sim.assemble", "sim.dispatch", "sim.fetch",
                     "sim.plan", "sim.upload"]
    assert len({e[3]["call"] for e in sim}) == 1
    (upload,) = [e for e in sim if e[0] == "sim.upload"]
    assert upload[3]["buckets"] == 1


def test_span_counters_are_left_out_without_a_profiler(monkeypatch):
    seen = []

    class Recording:
        def __init__(self, name, **attrs):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **attrs):
            seen.append((self.name, sorted(attrs)))

    _sweep()
    monkeypatch.setattr(tracing, "span", Recording)
    _sweep()
    assert seen == []
    monkeypatch.setattr(tracing, "collecting", lambda: True)
    _sweep()
    assert ("sim.batch", ["lane_rounds"]) in seen
    assert ("sim.fetch", ["bytes"]) in seen


class _NoSpan:
    def set_metadata(self, **attrs):
        pass


def _plain(monkeypatch):
    """The code path with no spans, no named scopes and the implicit
    upload (of the host array's slice) inside the runner call."""
    monkeypatch.setattr(tracing, "span",
                        lambda name, **attrs: contextlib.nullcontext(
                            _NoSpan()))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(batch, "_upload",
                        lambda traces, call, buckets=1: traces)
    monkeypatch.setattr(batch, "_staged_call",
                        lambda run, traces, call: jax.device_get(run(traces)))
    clear_runner_cache()
    jax.clear_caches()


def _sim_arrays(res):
    return [np.concatenate([r.round_times, r.effective_pattern.ravel(),
                            [r.waitouts], list(r.job_done_round.values())])
            for r in res.flat]


def _train_arrays(tr):
    return [np.asarray(tr.losses[0])] + [
        np.asarray(x) for x in jax.tree.leaves((tr.params, tr.opt))]


def _twice_sweep(profile):
    _sweep()
    return profile(_sweep)


def _twice_train(profile):
    tr = _train(_trainer())
    return profile(lambda: _train(tr))


@pytest.mark.parametrize("twice,arrays", [(_twice_sweep, _sim_arrays),
                                          (_twice_train, _train_arrays)],
                         ids=["simulate_batch", "coded_step"])
def test_results_bitwise_equal_with_and_without_tracing(
        tmp_path, monkeypatch, twice, arrays):
    """A second call (the first compiles) with no profiler, under one,
    and on the code path without spans or scopes."""
    clear_runner_cache()
    off = arrays(twice(lambda fn: fn()))
    on = arrays(twice(lambda fn: _profiled(tmp_path, fn)[0]))
    _plain(monkeypatch)
    plain = arrays(twice(lambda fn: fn()))
    for a, b, c in zip(off, on, plain, strict=True):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
        assert a.tobytes() == b.tobytes() == c.tobytes()
