"""The program-span reduction and its six readers, on a small recorded
trace (``data/spans_trace.json``): a simulator call of two shape
buckets whose programs share a name and reuse an instruction name with
different scopes, then two training jobs of one step program.  The
scope tables are also read from a CPU profiler trace and from a
hand-encoded program."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spans
from bench.spans import Event, ScopeTime

DATA = Path(__file__).parent / "data"
READERS = ["host_assemble_ms_per_call.sweep", "transfer_ms_per_call.sweep",
           "transfer_bytes_per_lane_round.sweep", "gate_share_of_scan.sweep",
           "trainer_host_ms_per_job.train", "head_share_of_step.train"]


def recorded():
    with open(DATA / "spans_trace.json") as f:
        data = json.load(f)
    events = [Event(**e) for e in data["events"]]
    return events, data["programs"]


def reading(**edit):
    events, tables = recorded()
    tables.update(edit)
    return spans.reduce(events, tables)


def read(name, r):
    cell = harness.resolve("sweep-table1" if name.endswith(".sweep")
                           else "train-gc")
    ctx = SimpleNamespace(spans_reading=r)
    return cell.metric_reader(name).read(ctx)


def test_span_counts_self_times_and_attributes():
    r = reading()
    assert r.window_s == pytest.approx(4000e-9)
    batch = r.spans["sim.batch"]
    # 1800 ns, all of it covered by its nine child spans
    assert (batch.count, batch.total_s) == (1, pytest.approx(1800e-9))
    assert batch.self_s == pytest.approx(0.0)
    assert batch.attrs == {"call": 1, "lane_rounds": 10}
    assert r.spans["sim.upload"].attrs["bytes"] == 1500
    assert r.spans["sim.fetch"].attrs["bytes"] == 300
    # the sim.plan span after the window is left out
    assert r.spans["sim.plan"].count == 1
    # Python frames inside a span do not count against its self time
    assert r.spans["sim.assemble"].self_s == pytest.approx(500e-9)
    assert r.spans["train.sync"].self_s == pytest.approx(1250e-9)
    rounds = r.spans["train.round"]
    assert rounds.count == 3 and rounds.self_s == pytest.approx(300e-9)
    assert rounds.attrs["t"] == 6


def test_idle_by_innermost_span():
    r = reading()
    # busy: [300,700], [1100,1500], [2350,2850], [3200,3800] of 4000 ns
    assert r.idle_s == pytest.approx(2100e-9)
    want = {"(outside)": 300, "sim.plan": 100, "sim.upload": 200,
            "sim.fetch": 200, "sim.assemble": 500, "train.round": 300,
            "train.batch": 200, "train.dispatch": 150, "train.sync": 150}
    assert r.idle_by_span == pytest.approx({k: v * 1e-9
                                            for k, v in want.items()})
    assert sum(r.idle_by_span.values()) == pytest.approx(r.idle_s)


def test_op_self_time_and_program_of_each_op():
    events, _ = recorded()
    ops = spans.op_self_times(events, spans._window(events))
    got = {(m, op.split(" = ")[0]): 0.0 for _, m, op, _ in ops}
    for _, m, op, s in ops:
        got[(m, op.split(" = ")[0])] += s
    # a while's self time is its length less its body's ops
    assert got[("jit_run(111)", "%while.1")] == pytest.approx(200e-9)
    assert got[("jit_run(222)", "%while.1")] == pytest.approx(150e-9)
    assert got[("jit_step(333)", "%while.11")] == pytest.approx(200e-9)
    assert sum(got.values()) == pytest.approx(1900e-9)   # the busy union


def test_same_name_ops_take_their_own_programs_scope():
    r = reading()
    run = r.scopes["jit_run"]
    assert run.total_s == pytest.approx(800e-9)
    assert run.unattributed_s == 0
    gate = [p for p in run.by_scope if spans.in_scope(p, "gate")]
    assert sum(run.by_scope[p] for p in gate) == pytest.approx(350e-9)


def test_in_scope_sees_through_transformations():
    path = "jit(step)/transpose(jvp(coded_loss))/head/dot_general"
    assert spans.in_scope(path, "head")
    assert spans.in_scope(path, "coded_loss")
    assert not spans.in_scope(path, "layers")
    assert not spans.in_scope("jit(run)/round/gate_window_stats", "gate")


def test_host_assemble_ms_per_call():
    # sim.plan 100 + sim.assemble 200 + 300 ns, one sim.batch
    assert read("host_assemble_ms_per_call.sweep", reading()) == \
        pytest.approx(600e-6)


def test_transfer_ms_per_call():
    # chip idle in two uploads and two fetches, 100 ns each, one sim.batch
    assert read("transfer_ms_per_call.sweep", reading()) == \
        pytest.approx(400e-6)


def test_transfer_bytes_per_lane_round():
    # (1000 + 500 + 200 + 100) B over 10 lane-rounds
    assert read("transfer_bytes_per_lane_round.sweep", reading()) == \
        pytest.approx(180.0)


def test_gate_share_of_scan():
    # gate: fusion.2 (100) and the kernel (100) in bucket 111, fusion.3
    # (150) in bucket 222, where fusion.2 is the scheme step; of 800 ns
    assert read("gate_share_of_scan.sweep", reading()) == \
        pytest.approx(350 / 800)


def test_trainer_host_ms_per_job():
    # round self 100 x 3, batch 100 x 2, dispatch 50 + 100; two syncs
    assert read("trainer_host_ms_per_job.train", reading()) == \
        pytest.approx(650e-6 / 2)


def test_head_share_of_step():
    # head fusion 200 + 200 of 500 + 600 ns of the step program
    assert read("head_share_of_step.train", reading()) == \
        pytest.approx(400 / 1100)


def test_unattributed_time_over_five_percent_reads_null():
    _, tables = recorded()
    # fusion.2 of the first bucket has no scope: 100 of 800 ns
    first = dict(tables["jit_run(111)"], **{"fusion.2": ""})
    r = reading(**{"jit_run(111)": first})
    assert r.scopes["jit_run"].unattributed_s == pytest.approx(100e-9)
    assert r.scopes["jit_run"].unattributed == {"fusion.2": 100e-9}
    assert read("gate_share_of_scan.sweep", r) is None
    # a launched program the trace holds no HLO for: all of it
    events, tables = recorded()
    del tables["jit_step(333)"]
    r = spans.reduce(events, tables)
    assert r.scopes["jit_step"].unattributed_s == pytest.approx(1100e-9)
    assert read("head_share_of_step.train", r) is None
    at = ScopeTime(total_s=1.0, by_scope={"a/gate/x": 0.5})
    assert ScopeTime(1.0, 0.05, at.by_scope).share("gate") == 0.5
    assert ScopeTime(1.0, 0.0501, at.by_scope).share("gate") is None
    assert ScopeTime().share("gate") is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_null_without_program_spans(name):
    events, _ = recorded()
    bare = [e for e in events if not e.name.startswith(("sim.", "train."))]
    assert read(name, spans.reduce(bare, {})) is None
    assert read(name, None) is None


def test_reading_is_none_without_the_program_module(monkeypatch):
    monkeypatch.setattr(spans, "_has_spans", lambda: False)
    ctx = SimpleNamespace()
    assert spans.reading(ctx) is None and ctx.spans_reading is None


# -- scope tables from the trace's programs ---------------------------------


def _cpu_trace(tmp_path, fn):
    """``fn()`` under the profiler; the trace file's path."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return spans.trace_file(str(tmp_path))


def test_scope_table_covers_every_op_of_the_trace(tmp_path):
    from jax.profiler import ProfileData

    def f(x):
        with jax.named_scope("gate"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("head"):
            return jnp.cos(y).sum(axis=0) + y.max()

    prog = jax.jit(f)
    small, big = jnp.ones((64, 32)), jnp.ones((128, 32))
    prog(small).block_until_ready()
    prog(big).block_until_ready()
    path = _cpu_trace(tmp_path, lambda: (prog(small).block_until_ready(),
                                         prog(big).block_until_ready()))
    tables = spans.program_scopes(path)
    # on the CPU an op event names its instruction, program and id
    ran: dict[str, set] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("$"):
                    continue
                st = dict(ev.stats)
                if st.get("hlo_module") == "jit_f" and "hlo_op" in st:
                    key = f"jit_f({st['program_id']})"
                    ran.setdefault(key, set()).add(st["hlo_op"])
    assert len(ran) == 2                 # one program per shape
    for key, ops in ran.items():
        scopes = {op: tables[key].get(op, "") for op in ops}
        assert all(s.startswith("jit(f)/") for s in scopes.values()), scopes
        assert any("/gate/" in s for s in tables[key].values())
        assert any("/head/" in s for s in tables[key].values())


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Encode ``(number, value)`` fields: ints as varints, everything
    else length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _inst(name, op="", calls=(), packed=True):
    fields = [(1, name), (2, "add")]
    if op:
        fields.append((7, _msg((1, "add"), (2, op))))
    if calls and packed:
        fields.append((38, b"".join(_varint(c) for c in calls)))
    else:
        fields += [(38, c) for c in calls]
    return _msg(*fields)


def test_parse_scopes_follows_called_computations():
    add = _msg((1, "add"), (5, 3),
               (2, _inst("a")), (2, _inst("b")),
               (2, _inst("s", "jit(g)/head/reduce_sum")))
    body = _msg((1, "body"), (5, 300),
                (2, _inst("m", "jit(g)/round/gate/mul")))
    main = _msg((1, "main"), (5, 7),
                (2, _inst("x")),
                (2, _inst("g", "jit(g)/round/gate/sin")),
                (2, _inst("k", "jit(g)/round/scheme_step/cos")),
                (2, _inst("cp")),
                (2, _inst("r", calls=[3])),
                (2, _inst("w", calls=[300], packed=False)))
    table = spans.module_scopes(_msg((1, "jit_g"), (3, add), (3, body),
                                     (3, main)))
    # no op_name: the scope of a computation it calls ...
    assert table["r"] == "jit(g)/head/reduce_sum"
    assert table["w"] == "jit(g)/round/gate/mul"
    # ... else the scope its computation's instructions share
    assert table["cp"] == "jit(g)/round"
    assert table["g"] == "jit(g)/round/gate/sin"
    # a parameter: its computation's one scoped instruction
    assert table["a"] == "jit(g)/head/reduce_sum"


def test_program_scopes_read_only_the_metadata_plane(tmp_path):
    module = _msg((1, "jit_h"), (3, _msg((5, 1), (2, _inst(
        "fusion.1", "jit(h)/gate/and")))))
    hlo = _msg((1, module))

    def program(name, stat_id):
        return _msg((1, 9), (2, name), (5, _msg((1, stat_id), (6, hlo))))

    def plane(name, *programs):
        entries = [(4, _msg((1, i + 1), (2, p)))
                   for i, p in enumerate(programs)]
        stats = [(5, _msg((1, 21), (2, _msg((1, 21), (2, "Hlo Proto"))))),
                 (5, _msg((1, 22), (2, _msg((1, 22), (2, "other")))))]
        return _msg((1, 4), (2, name), *entries, *stats)

    space = _msg((1, plane("/host:CPU", program("jit_x(1)", 21))),
                 (1, plane("/host:metadata", program("jit_h(42)", 21),
                           program("jit_y(5)", 22))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert spans.program_scopes(str(path)) == {
        "jit_h(42)": {"fusion.1": "jit(h)/gate/and"}}


# -- the traced window --------------------------------------------------------


def _ctx(call):
    cell = SimpleNamespace(traffic={"trace_seconds": 0.0})
    return SimpleNamespace(cell=cell, driver=SimpleNamespace(call=call))


def test_a_failing_call_fails_the_reading():
    def call():
        raise RuntimeError("step failed")

    with pytest.raises(RuntimeError, match="step failed"):
        spans.reading(_ctx(call))
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_an_unreadable_trace_reads_none(monkeypatch, capsys):
    calls = []

    def broken(path):
        raise ValueError("no bench.window span")

    monkeypatch.setattr(spans, "reduce_file", broken)
    ctx = _ctx(lambda: calls.append(1))
    assert spans.reading(ctx) is None and calls == [1]
    assert "bench.spans: ValueError" in capsys.readouterr().err
    # taken once per run
    assert spans.reading(ctx) is None and calls == [1]
