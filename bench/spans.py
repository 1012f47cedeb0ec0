"""The program's own spans and named scopes, read from a profiler trace.

The program opens host spans (``sim.*`` around the simulator's
planning, transfers, launches and result assembly; ``train.*`` around
the trainer's rounds and jobs) with counters as their attributes, and
``jax.named_scope`` names inside its jitted programs (``round``,
``gate``, ``scheme_step`` in the simulator's scan; ``coded_loss``,
``layers``, ``head``, ``adamw`` in the coded train step).  This module
reduces a trace to what the per-layer metrics of those layers read:

- ``spans``: for each program span name, inside the ``bench.window``
  span, its count, total and self seconds (self: its duration less the
  union of the program spans it contains) and the sums of its integer
  attributes;
- ``idle_by_span``: device-idle seconds of the window, keyed by the
  innermost program span running at the time (``(outside)`` where none
  is);
- device self time by scope: an op's self time is its duration less
  the union of the op events it contains on the same line (a
  ``%while`` contains its body's ops); an op belongs to the program
  launch (``XLA Modules`` event, ``jit_run(8686780069922174958)``)
  that contains it in time.  Device op events carry no scope, but the
  trace's ``/host:metadata`` plane holds each program's optimised HLO
  under the same name, and each instruction's metadata there carries
  its scope path (:func:`program_scopes`).  An op without a scope
  counts as unattributed, and a share whose unattributed time exceeds
  5% of its denominator reads ``None``.

:func:`reading` takes the trace these numbers come from: a window of
the cell's own calls, as long as the harness's traced window, right
after it, with the profiler's Python tracer off.  It returns ``None``
where the program has no spans (a checkout without
``repro.tracing``).
"""

from __future__ import annotations

import bisect
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from bench.trace import MODULES_LINE, OPS_LINE, _union
from bench.trace import WINDOW_SPAN as WINDOW

PROGRAM = ("sim.", "train.")
KEPT = PROGRAM + ("bench.",)
OUTSIDE = "(outside)"
NULL_SHARE = 0.05
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    args: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def trace_file(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> list[Event]:
    """The device planes' op and program events and the host's
    ``sim.*``, ``train.*`` and ``bench.*`` spans (with their stats) of
    the trace file ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names: dict[str, str] = {}
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    name = names.setdefault(name, name)
                    args = {}
                elif name.startswith(KEPT):
                    args = {k: v for k, v in ev.stats}
                else:
                    continue
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 args))
    return out


# -- the programs' scope tables ----------------------------------------------
#
# ``ProfileData`` does not expose the metadata plane, so the trace file
# is read here field by field.  Field numbers: tsl's ``xplane.proto``
# (XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5;
# map entries key 1, value 2; XEventMetadata.name 2, stats 5;
# XStatMetadata.id 1, name 2; XStat.metadata_id 1, bytes_value 6) and
# xla's ``hlo.proto`` (HloProto.hlo_module 1; HloModuleProto.computations
# 3; HloComputationProto.instructions 2, id 5; HloInstructionProto.name
# 1, metadata 7, called_computation_ids 38; OpMetadata.op_name 2).


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _first(buf, number: int, default=b""):
    for num, value in _fields(buf):
        if num == number:
            return value
    return default


def _text(buf, number: int) -> str:
    return bytes(_first(buf, number)).decode()


def _varints(value) -> list[int]:
    """A repeated integer field's values: one varint, or packed."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def module_scopes(module) -> dict[str, str]:
    """Instruction name -> scope path of one ``HloModuleProto``.  An
    instruction without an ``op_name`` (a fusion wrapper, a while's
    plumbing, a copy the compiler added) takes the first scope found in
    the computations it calls, else the scope its computation's other
    instructions share."""
    comps: dict[int, list] = {}          # id -> [(name, op_name, calls)]
    for num, comp in _fields(module):
        if num != 3:
            continue
        cid, members = 0, []
        for n, value in _fields(comp):
            if n == 5:
                cid = value
            elif n == 2:
                name, op, calls = "", "", []
                for k, v in _fields(value):
                    if k == 1:
                        name = bytes(v).decode()
                    elif k == 7:
                        op = _text(v, 2)
                    elif k == 38:
                        calls += _varints(v)
                members.append((name, op, calls))
        comps[cid] = members

    def called_scope(ids, seen) -> str:
        for cid in ids:
            if cid in seen:
                continue
            seen.add(cid)
            for _, op, _ in reversed(comps.get(cid, [])):
                if op:
                    return op
            for _, _, calls in reversed(comps.get(cid, [])):
                found = called_scope(calls, seen)
                if found:
                    return found
        return ""

    def shared_scope(members) -> str:
        paths = [op.split("/") for _, op, _ in members if "/" in op]
        if not paths:
            return ""
        common = paths[0]
        for p in paths[1:]:
            n = 0
            while n < min(len(common), len(p)) and common[n] == p[n]:
                n += 1
            common = common[:n]
        return "/".join(common)

    table = {}
    for members in comps.values():
        for name, op, calls in members:
            table[name] = (op or called_scope(calls, set())
                           or shared_scope(members))
    return table


def program_scopes(path: str) -> dict[str, dict[str, str]]:
    """Program name as its launches are named -> instruction -> scope
    path, for each program whose optimised HLO the trace file ``path``
    holds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        stat_names, programs = {}, []
        for n, entry in _fields(plane):
            if n == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1, 0)] = _text(meta, 2)
            elif n == 4:
                programs.append(_first(entry, 2))
        for meta in programs:
            for n, stat in _fields(meta):
                if n != 5:
                    continue
                fields = dict(_fields(stat))
                if stat_names.get(fields.get(1)) == HLO_STAT and 6 in fields:
                    out[_text(meta, 2)] = module_scopes(
                        _first(fields[6], 1))
    return out


# -- host spans -------------------------------------------------------------


def _nest(items):
    """Parent index of each ``(start, end)`` interval: the innermost
    earlier interval that contains it, or -1."""
    order = sorted(range(len(items)),
                   key=lambda i: (items[i][0], -items[i][1]))
    parent = [-1] * len(items)
    stack: list[int] = []
    for i in order:
        lo, hi = items[i]
        while stack and items[stack[-1]][1] < hi:
            if items[stack[-1]][1] <= lo:
                stack.pop()
            else:                  # overlaps without containing: no parent
                break
        if stack and items[stack[-1]][0] <= lo and hi <= items[stack[-1]][1]:
            parent[i] = stack[-1]
        stack.append(i)
    return order, parent


def _self_times(items) -> list[float]:
    """Each interval's length less the union of the intervals it
    directly contains (nested intervals of one line do not overlap)."""
    _, parent = _nest(items)
    own = [hi - lo for lo, hi in items]
    for i, p in enumerate(parent):
        if p >= 0:
            own[p] -= items[i][1] - items[i][0]
    return [max(0.0, x) for x in own]


@dataclass
class SpanStat:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    attrs: dict = field(default_factory=dict)


def _window(events):
    wins = [e for e in events if e.name == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return wins[0]


def program_spans(events, win) -> list[Event]:
    """Program spans on the window's thread, wholly inside it."""
    return [e for e in events
            if (e.plane, e.line) == (win.plane, win.line)
            and e.name.startswith(PROGRAM)
            and e.start_ns >= win.start_ns and e.end_ns <= win.end_ns]


def span_stats(spans: list[Event]) -> dict[str, SpanStat]:
    selfs = _self_times([(e.start_ns, e.end_ns) for e in spans])
    out: dict[str, SpanStat] = {}
    for e, own in zip(spans, selfs):
        st = out.setdefault(e.name, SpanStat())
        st.count += 1
        st.total_s += e.dur_ns / 1e9
        st.self_s += own / 1e9
        for k, v in e.args.items():
            if isinstance(v, int) and not isinstance(v, bool):
                st.attrs[k] = st.attrs.get(k, 0) + v
    return out


# -- device ops -------------------------------------------------------------


def _clipped(evs, w0, w1):
    out = []
    for e in evs:
        lo, hi = max(e.start_ns, w0), min(e.end_ns, w1)
        if hi > lo:
            out.append((e, lo, hi))
    return out


def _device_lines(events) -> dict[str, dict[str, list]]:
    """Device plane -> line (ops, programs) -> its events."""
    out: dict[str, dict[str, list]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line in (OPS_LINE,
                                                          MODULES_LINE):
            out.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
    return out


def idle_by_span(events, win, spans) -> tuple[float, dict[str, float]]:
    """Device-idle seconds of the window (mean over devices), in all and
    split by the innermost program span running at the time."""
    w0, w1 = win.start_ns, win.end_ns
    # the innermost span as pieces (lo, hi, name) that tile the window
    items = [(e.start_ns, e.end_ns) for e in spans]
    order, _ = _nest(items)
    cuts = sorted({w0, w1, *(x for iv in items for x in iv)})
    pieces = [[lo, hi, OUTSIDE] for lo, hi in zip(cuts, cuts[1:])]
    # a span comes after its parent in start order, so painting in that
    # order leaves the innermost name on each piece
    for i in order:
        lo, hi = items[i]
        a = bisect.bisect_left(cuts, lo)
        b = bisect.bisect_left(cuts, hi)
        for k in range(a, b):
            if k < len(pieces):
                pieces[k][2] = spans[i].name
    idle_total = 0.0
    split: dict[str, float] = {}
    devices = 0
    for lines in _device_lines(events).values():
        busy = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = _union([(lo, hi) for _, lo, hi in _clipped(busy, w0, w1)])
        if not merged:
            continue
        devices += 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2])
                if hi > lo]
        j = 0
        for lo, hi in gaps:
            idle_total += hi - lo
            while j < len(pieces) and pieces[j][1] <= lo:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < hi:
                a, b = max(lo, pieces[k][0]), min(hi, pieces[k][1])
                if b > a:
                    split[pieces[k][2]] = split.get(pieces[k][2], 0.0) + b - a
                k += 1
    if devices == 0:
        return 0.0, {}
    return (idle_total / devices / 1e9,
            {k: v / devices / 1e9 for k, v in split.items()})


def op_self_times(events, win):
    """(device, module event name, op name, self seconds) of every op
    in the window."""
    w0, w1 = win.start_ns, win.end_ns
    out = []
    for dev, lines in sorted(_device_lines(events).items()):
        ops = _clipped(lines.get(OPS_LINE, []), w0, w1)
        mods = sorted(_clipped(lines.get(MODULES_LINE, []), w0, w1),
                      key=lambda m: m[1])
        selfs = _self_times([(lo, hi) for _, lo, hi in ops])
        starts = [m[1] for m in mods]
        for (e, lo, hi), own in zip(ops, selfs):
            k = bisect.bisect_right(starts, lo) - 1
            module = ""
            if k >= 0 and lo < mods[k][2]:
                module = mods[k][0].name
            out.append((dev, module, e.name, own / 1e9))
    return out


def module_base(name: str) -> str:
    """``jit_run(8686780069922174958)`` -> ``jit_run``."""
    return name.split("(", 1)[0]


def in_scope(path: str, name: str) -> bool:
    """Whether the scope path passes through the scope ``name``; a
    component may be wrapped by transformations
    (``transpose(jvp(coded_loss))``)."""
    return any(part.rstrip(")").rsplit("(", 1)[-1] == name
               for part in path.split("/"))


def short_op(op_name: str) -> str:
    """An op event's instruction name without its ``%``: the events
    carry the instruction's whole text."""
    return op_name.split(" = ", 1)[0].lstrip("%")


@dataclass
class ScopeTime:
    """Device self seconds of one program name's ops, by scope path."""

    total_s: float = 0.0
    unattributed_s: float = 0.0
    by_scope: dict = field(default_factory=dict)
    unattributed: dict = field(default_factory=dict)  # instruction -> s

    def share(self, name: str) -> float | None:
        """Self time under scope ``name`` over all of the program's; None
        where more than 5% of it is unattributed, or none ran."""
        if self.total_s <= 0 or self.unattributed_s > NULL_SHARE * \
                self.total_s:
            return None
        inside = sum(s for p, s in self.by_scope.items() if in_scope(p, name))
        return inside / self.total_s


def scope_times(ops, tables: dict) -> dict[str, ScopeTime]:
    """Per program name (``jit_run``): self time by scope path.  Each op
    takes its scope from the table of the program it ran in
    (``jit_run(8686780069922174958)``, so programs of one name keep
    their own); an op with no scope there is unattributed."""
    out: dict[str, ScopeTime] = {}
    for _, module, op, secs in ops:
        if not module:
            continue
        st = out.setdefault(module_base(module), ScopeTime())
        st.total_s += secs
        short = short_op(op)
        scope = tables.get(module, {}).get(short, "")
        if scope:
            st.by_scope[scope] = st.by_scope.get(scope, 0.0) + secs
        else:
            st.unattributed_s += secs
            st.unattributed[short] = st.unattributed.get(short, 0.0) + secs
    return out


@dataclass
class Reading:
    window_s: float
    spans: dict                          # name -> SpanStat
    idle_s: float = 0.0
    idle_by_span: dict = field(default_factory=dict)
    scopes: dict = field(default_factory=dict)   # program -> ScopeTime

    def per(self, names, per: str) -> float | None:
        """Self seconds of the spans ``names``, per ``per`` span."""
        n = self.spans.get(per, SpanStat()).count
        if not n:
            return None
        return sum(self.spans[s].self_s for s in names
                   if s in self.spans) / n

    def idle_per(self, names, per: str) -> float | None:
        """Device-idle seconds inside the spans ``names`` (innermost),
        per ``per`` span."""
        n = self.spans.get(per, SpanStat()).count
        if not n:
            return None
        return sum(self.idle_by_span.get(s, 0.0) for s in names) / n

    def attr(self, names, key: str) -> int:
        return sum(self.spans[s].attrs.get(key, 0) for s in names
                   if s in self.spans)


def reduce(events, tables: dict | None = None) -> Reading:
    """Everything above for the ``bench.window`` span of ``events``;
    ``tables`` maps a launched program's name to its scope table
    (:func:`program_scopes`)."""
    win = _window(events)
    spans = program_spans(events, win)
    idle, split = idle_by_span(events, win, spans)
    scopes = scope_times(op_self_times(events, win), tables or {})
    return Reading(window_s=win.dur_ns / 1e9, spans=span_stats(spans),
                   idle_s=idle, idle_by_span=split, scopes=scopes)


# -- the traced window --------------------------------------------------------


def _has_spans() -> bool:
    return importlib.util.find_spec("repro.tracing") is not None


def record(ctx, trace_dir: str) -> None:
    """Trace the cell's own calls for the traced window's length into
    ``trace_dir``.  The Python tracer is off: the host spans then time
    the program, not the tracer, and the trace stays small.  A call
    that fails raises, as in the harness's window."""
    import jax

    seconds = float(ctx.cell.traffic.get("trace_seconds", 4))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    ctx.driver.call()
                if time.perf_counter() - t0 >= seconds:
                    break
    finally:
        jax.profiler.stop_trace()


def reduce_file(path: str) -> Reading:
    return reduce(load(path), program_scopes(path))


def reading(ctx) -> Reading | None:
    """The cell's span reading, taken once per run and kept on the
    context; ``None`` where the program has no spans or its trace could
    not be read (the reason goes to stderr).  The calls themselves are
    not caught: one that fails fails the run."""
    if not hasattr(ctx, "spans_reading"):
        ctx.spans_reading = None
        if _has_spans():
            trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
            try:
                record(ctx, trace_dir)
                try:
                    ctx.spans_reading = reduce_file(trace_file(trace_dir))
                except Exception as e:  # a metric reads None
                    print(f"bench.spans: {type(e).__name__}: {e}",
                          file=sys.stderr)
                else:
                    report(ctx.spans_reading)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
    return ctx.spans_reading


def report(r: Reading) -> None:
    """The reading's idle split and scope coverage, one line on stderr."""
    print("bench.spans " + json.dumps({
        "window_s": r.window_s, "idle_s": r.idle_s,
        "idle_by_span": r.idle_by_span,
        "spans": {k: [v.count, v.total_s, v.self_s, v.attrs]
                  for k, v in r.spans.items()},
        "scopes": {k: {"total_s": v.total_s,
                       "unattributed_s": v.unattributed_s,
                       "unattributed": sorted(v.unattributed.items(),
                                              key=lambda kv: -kv[1])[:8],
                       "by_scope": sorted(v.by_scope.items(),
                                          key=lambda kv: -kv[1])[:12]}
                   for k, v in r.scopes.items()}}), file=sys.stderr)
