"""The benchmark harness: finds a cell by name, runs it once, prints
the result line.

Everything that belongs to one cell is data or a file of its own:

- ``BENCHMARK.json`` (repo root): cells, configurations, metrics;
- ``bench/configs/<config>.json``: a configuration (``file`` in
  ``BENCHMARK.json``);
- ``bench/workloads/<traffic>.json``: a traffic mix, naming its driver
  kind, its parameters and the limits of its output comparison;
- ``bench/drivers/<kind>.py``: the set-up, timed call and comparison of
  one kind of traffic (a ``Driver`` class: ``call()`` in the window,
  ``end_to_end(window_s)``, ``release()`` once the peak memory is read,
  which frees the program's state and may take one more untimed call
  for the check, ``check()``, and optionally ``errors``);
- ``bench/metrics/<metric>.py``: one per-layer metric's reader
  (``read(ctx) -> float | None``); a metric split by cell,
  ``<quantity>.<cell kind>``, falls back to ``<quantity>.py`` when it
  has no file of its own;
- ``bench/costs/<name>.py``: operations and bytes from shapes;
- ``bench/peaks.json``: the chip's peaks by ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TPU_LOG_DIR = ROOT / ".tpu_logs"


class NoChip(RuntimeError):
    """No accelerator of the kind the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    root: Path = ROOT

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"

    def driver(self):
        kind = self.traffic["driver"]
        return load_module(self.bench_dir / "drivers" / f"{kind}.py",
                           f"bench_driver_{kind}")

    def metric_reader(self, name: str):
        path = self.bench_dir / "metrics" / f"{name}.py"
        if not path.exists():
            name = name.split(".")[0]
            path = self.bench_dir / "metrics" / f"{name}.py"
        return load_module(path, f"bench_metric_{name}")


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is None:
        return True
    return metric["moves"] in reported


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` in ``root``'s ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "workloads" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, root)


def peaks(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def cost(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "costs" / f"{name}.py",
                       f"bench_cost_{name}")


# -- the run -------------------------------------------------------------------


@dataclass
class Context:
    """What a driver and a metric reader are given."""

    cell: Cell
    seed: int
    devices: list = field(default_factory=list)
    reduction: object = None          # trace.Reduction, traced runs only
    driver: object = None             # the Driver after the window
    peaks: dict = field(default_factory=dict)


@contextmanager
def span(name: str, trace: bool):
    """A host span in the profiler's trace (no-op when not tracing)."""
    if trace:
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


def setup_runtime() -> None:
    """Compile cache and TPU logs inside the checkout, before jax brings
    up a backend.  The cache directory is fixed: it is part of every
    cached program's key."""
    os.environ.setdefault("TPU_LOG_DIR", str(TPU_LOG_DIR))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no eviction: every run of a cell loads the same programs
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"need {chips} TPU chip(s); jax has {devices}")
    return devices[:chips]


class CompileCounter:
    """Programs compiled (or loaded from the persistent cache) since
    the counter was made, from jax's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0

        def on_duration(name, secs, **_):
            if name == self.EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices: list) -> dict:
    """Set up, measure, compare; returns the result line as a dict."""
    compiles = CompileCounter()
    ctx = Context(cell, seed, devices)
    drv = cell.driver().Driver(ctx)
    ctx.driver = drv
    attempted = failed = 0
    errors: list[str] = []
    window = seconds
    if trace:
        window = min(seconds, float(cell.traffic.get("trace_seconds",
                                                     seconds)))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        import jax

        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    compiled_before = compiles.count
    t0 = time.perf_counter()
    with span("bench.window", trace):
        while True:
            attempted += 1
            try:
                with span("bench.call", trace):
                    drv.call()
            except Exception as e:  # a failed call counts, the run goes on
                failed += 1
                errors.append(f"{type(e).__name__}: {e}")
                if failed >= 3:
                    break
            if time.perf_counter() - t0 >= window:
                break
    window_s = time.perf_counter() - t0
    window_compiles = compiles.count - compiled_before
    if trace:
        import jax

        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    metrics: dict = {}
    out: dict = {}
    if trace:
        from bench.trace import breakdown, load_events, reduce_events

        ctx.reduction = reduce_events(load_events(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.peaks = peaks(devices[0].device_kind, cell.root)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = breakdown(ctx.reduction)
    else:
        values = drv.end_to_end(window_s)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    drv.release()
    checks = drv.check() if failed == 0 else []
    errors += getattr(drv, "errors", [])
    correct = (failed == 0 and attempted > 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = ctx.reduction.busy_s
        device["window_s"] = ctx.reduction.window_s
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    line.update(out)
    line["window_compiles"] = window_compiles
    if errors:
        line["errors"] = errors[:3]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return line


def report(line: dict) -> None:
    """Each compared number beside its limit, last on stderr; the
    result line last on stdout."""
    for err in line.get("errors", []):
        print(f"error: {err}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False, default=_jsonable), flush=True)


def _jsonable(x):
    if hasattr(x, "item"):
        return x.item()
    raise TypeError(type(x))


def finite(x: float) -> float:
    """A compared number as a plain float; NaN or infinity reads 1e300,
    which passes no limit and stays valid JSON."""
    x = float(x)
    return x if math.isfinite(x) else 1e300
