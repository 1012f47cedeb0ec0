"""The trace reduction, on a small recorded trace."""

import json
from pathlib import Path

import pytest

from bench.trace import Event, breakdown, load_events, reduce_events

DATA = Path(__file__).parent / "data"


def recorded():
    with open(DATA / "small_trace.json") as f:
        return [Event(**e) for e in json.load(f)]


def test_busy_union_and_idle_share():
    red = reduce_events(recorded())
    # window 0..1000 ns; ops [100,300] and [250,400] overlap -> [100,400],
    # [600,700], and [950,1100] clipped to [950,1000]: 450 ns busy
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(450e-9)
    assert red.idle_share == pytest.approx(0.55)
    assert red.devices == 1


def test_time_by_op_and_program_name():
    red = reduce_events(recorded())
    assert red.op_s["fusion.1"] == pytest.approx(200e-9 + 50e-9)
    assert red.op_s["window_stats"] == pytest.approx(150e-9)
    assert red.op_s["buffer_stats"] == pytest.approx(100e-9)
    assert red.module_seconds("jit_run") == pytest.approx(600e-9)


def test_gaps_are_labelled_with_host_spans():
    red = reduce_events(recorded())
    gaps = {label: secs for secs, label in red.gaps}
    # [400,600] lies in the host span bench.assemble; [700,950] and
    # [0,100] only in bench.window's bench.call
    assert gaps["bench.assemble"] == pytest.approx(200e-9)
    assert sorted(s for s, _ in red.gaps)[-1] == pytest.approx(250e-9)
    out = breakdown(red)
    assert out["device_ops"][0][0] == "fusion.1"
    assert len(out["idle_gaps"]) == len(red.gaps) <= 10


def test_no_device_work_is_refused():
    events = [e for e in recorded() if not e.plane.startswith("/device")]
    with pytest.raises(ValueError, match="no device operation"):
        reduce_events(events)


def test_reads_a_profiler_trace(tmp_path):
    jax = pytest.importorskip("jax")
    f = jax.jit(lambda x: (x * 2).sum())
    x = jax.numpy.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = load_events(str(tmp_path))
    assert any(e.name == "bench.window" for e in events)
    # on the CPU no device plane exists: the reduction refuses the run
    with pytest.raises(ValueError, match="no device operation"):
        reduce_events(events)
