"""Reduce a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  It is
loaded into plain event tuples (:func:`load_events`), and everything
after that is arithmetic on those tuples (:func:`reduce_events`), so a
test can feed a small recorded trace through the same code.

Device activity is read from the device planes (``/device:...``): the
``XLA Ops`` line holds one event per operation run on the chip, the
``XLA Modules`` line one per program launch.  Host spans are the
events on the host thread that ran the window: the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.window`` bounds the
traced window) and the Python functions the profiler records there.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                module = ""
                for key, val in ev.stats:
                    if key == "hlo_module":
                        module = str(val)
                        break
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 module))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(lo, hi, w0, w1):
    return max(lo, w0), min(hi, w1)


@dataclass
class Reduction:
    """What the trace says about the traced window."""

    window_s: float
    devices: int
    busy_s: float                       # mean over devices
    op_s: dict = field(default_factory=dict)      # op name -> seconds
    module_s: dict = field(default_factory=dict)  # program name -> seconds
    gaps: list = field(default_factory=list)      # (seconds, host span)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, *substrings: str) -> float:
        return sum(s for name, s in self.module_s.items()
                   if any(k in name for k in substrings))


def reduce_events(events: list[Event], top_gaps: int = 10) -> Reduction:
    """Busy union, idle gaps and time by name inside the window span.

    Busy time of a device is the union of its op intervals (module
    intervals where a device has no op line), clipped to the window.
    Each of the longest idle gaps is labelled with the innermost host
    span that covers its midpoint.
    """
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0 = spans[0].start_ns
    w1 = w0 + spans[0].dur_ns
    window_s = (w1 - w0) / 1e9
    host = [e for e in events if (e.plane, e.line) == (spans[0].plane,
                                                       spans[0].line)
            and e.dur_ns > 0 and e.start_ns < w1
            and e.start_ns + e.dur_ns > w0]
    by_dev: dict[str, list[Event]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line in (OPS_LINE,
                                                          MODULES_LINE):
            by_dev.setdefault(e.plane, []).append(e)
    op_s: dict[str, float] = {}
    module_s: dict[str, float] = {}
    busy_total = 0.0
    gaps: list[tuple[float, str]] = []
    devices = 0
    for plane, evs in sorted(by_dev.items()):
        has_ops = any(e.line == OPS_LINE for e in evs)
        busy_line = OPS_LINE if has_ops else MODULES_LINE
        intervals = []
        for e in evs:
            lo, hi = _clip(e.start_ns, e.start_ns + e.dur_ns, w0, w1)
            if hi <= lo:
                continue
            secs = (hi - lo) / 1e9
            if e.line == OPS_LINE:
                op_s[e.name] = op_s.get(e.name, 0.0) + secs
            else:
                module_s[e.name] = module_s.get(e.name, 0.0) + secs
            if e.line == busy_line:
                intervals.append((lo, hi))
        merged = _union(intervals)
        if not merged:
            continue
        devices += 1
        busy_total += sum(hi - lo for lo, hi in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(hi - lo, (lo + hi) / 2)
                 for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo]
    if devices == 0:
        raise ValueError("no device operation ran in the traced window")
    gaps.sort(key=lambda g: -g[0])
    labels = _labels(host, [mid for _, mid in gaps[:top_gaps]])
    return Reduction(window_s=window_s, devices=devices,
                     busy_s=busy_total / devices, op_s=op_s,
                     module_s=module_s,
                     gaps=[(ns / 1e9, label) for (ns, _), label
                           in zip(gaps[:top_gaps], labels)])


def _labels(host: list[Event], times: list[float]) -> list[str]:
    """The innermost host event (the benchmark's spans, and the Python
    functions the profiler records) running at each of ``times``."""
    import numpy as np

    if not host or not times:
        return [WINDOW_SPAN] * len(times)
    start = np.array([e.start_ns for e in host])
    end = start + np.array([e.dur_ns for e in host])
    out = []
    for t in times:
        on = np.flatnonzero((start <= t) & (t <= end))
        on = [i for i in on if host[i].name != WINDOW_SPAN]
        best = min(on, key=lambda i: host[i].dur_ns, default=None)
        out.append(host[best].name if best is not None else WINDOW_SPAN)
    return out


def short_name(op: str) -> str:
    """An XLA op event's instruction name (``%fusion.12``), with the
    custom call's target where it has one; the events carry the whole
    HLO text."""
    name = op.split(" = ", 1)[0]
    if 'custom_call_target="' in op:
        name += " " + op.split('custom_call_target="', 1)[1].split('"')[0]
    return name


def breakdown(red: Reduction, top: int = 10) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    if not ops:
        ops = sorted(red.module_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(name), secs] for name, secs in ops],
            "idle_gaps": [[label, secs] for secs, label in red.gaps[:top]]}
