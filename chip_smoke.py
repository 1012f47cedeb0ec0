#!/usr/bin/env python3
"""Bring-up smoke test: the system's two device paths, end to end, on one TPU.

    python3 chip_smoke.py

Runs in one process, which holds the chip:

0. device check: exits non-zero unless jax's default backend is a TPU
   (no CPU fallback);
1. simulator, at the paper's operating point (n=256 workers, J=480
   jobs, GE traces calibrated to Fig. 1, the Table-1 specs, selective
   wait-out): ``simulate_batch(backend="jax")`` grid-fused and per-spec,
   then App.-J ``select_parameters_fast`` over the default grids, each
   checked against the numpy engine;
2. coded training of qwen2-0.5b at its published widths (random
   weights): ``VectorizedCodedTrainer`` under GE delays for GC (T=0)
   and M-SGC (B=1, W=2: T=1, two interleaved models), with the
   compiled step's memory analysis and timed steps, then a float32
   check that the decoded gradient equals the full-batch gradient.

Earlier lines report each phase's wall and compile time, the runner
cache counters, device memory (``process_peak_bytes_in_use`` is the
allocator's peak since the process started, not the phase's) and the
training losses.  Any
failed check exits non-zero; on success the last line is one JSON
object naming the device.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# simulator phase: the paper's operating point (benchmarks/run.py)
SIM_TRACES = 8
PROBE_ROUNDS = 80            # longest App.-J probe of Table 3
# Table 1's coded schemes; uncoded has no parameter to select
SELECT_SCHEMES = ("m-sgc", "sr-sgc", "gc")

# training phase: cut from 256 workers to 8, because the one-chip step
# evaluates all n * slots chunk passes; chunk batch 1 and 64 tokens per
# sequence fit two M-SGC models on 16 GB (described-chip compile)
ARCH = "qwen2-0.5b"
TRAIN_N = 8
TRAIN_SEQ = 64
TRAIN_JOBS = 4
STEP_REPS = 6               # timed steps per scheme, compile excluded
TRAIN_SPECS = (
    # label, scheme, params, models, batch (= num_chunks * chunk batch)
    ("gc", "gc", {"s": 1}, 1, 8),
    ("m-sgc", "m-sgc", {"B": 1, "W": 2, "lam": 1}, 2, 24),
)
#: decoded vs full-batch float32 gradient, per leaf: relative L2 error
GRAD_RTOL = 1e-4


class CompileMeter:
    """Seconds spent compiling programs for the backend or loading them
    from the persistent cache, the number of such programs, and the
    persistent-cache hits among them, read off jax's monitoring
    events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        event = "/jax/core/compile/backend_compile_duration"

        def on_duration(name, secs, **_):
            if name == event:
                self.seconds += secs
                self.programs += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits


def device_memory() -> dict:
    """The first device's allocator counters (none on a CPU)."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def report(phase: str, t0: float, meter: CompileMeter, before, **extra):
    secs, progs, hits = meter.snapshot()
    mem = device_memory()
    line = dict(
        phase=phase,
        wall_s=time.perf_counter() - t0,
        compile_s=secs - before[0],
        programs=progs - before[1],
        persistent_cache_hits=hits - before[2],
        bytes_in_use=mem.get("bytes_in_use"),
        process_peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        bytes_limit=mem.get("bytes_limit"),
        **extra,
    )
    print(json.dumps(line), flush=True)


def device_check():
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (backend={backend!r}, "
                         f"devices={devices}); refusing to run on the CPU")
    return devices


def ge_traces(n: int, rounds: int, count: int, seed: int):
    import numpy as np

    from benchmarks.run import GE
    from repro.core import GilbertElliotSource

    return np.stack([
        GilbertElliotSource(n=n, seed=seed + k, **GE).sample_delays(rounds)
        for k in range(count)
    ])


def simulator_phase(n: int, J: int, num_traces: int, probe_rounds: int,
                    select_schemes, seed: int = 0) -> dict:
    """Table-1 specs through both jax batch paths and App.-J selection,
    each against the numpy engine.  Returns the numbers it prints."""
    import numpy as np

    from benchmarks.run import GE, MU, PARAMS
    from repro.core import (
        GilbertElliotSource,
        cache_stats,
        clear_runner_cache,
        estimate_alpha,
        make_scheme,
        select_parameters_fast,
        simulate_batch,
        simulate_lockstep,
    )
    from repro.core.testing import assert_sim_parity

    alpha = estimate_alpha(GilbertElliotSource(n=n, seed=seed, **GE))
    specs = [(name, PARAMS[name]) for name in ("m-sgc", "sr-sgc", "gc",
                                               "uncoded")]
    max_T = max(make_scheme(nm, n, J, **kw).T for nm, kw in specs)
    traces = ge_traces(n, J + max_T, num_traces, seed)
    kw = dict(mu=MU, alpha=alpha, J=J, waitout="selective")

    ref = {nm: simulate_lockstep(nm, p, traces, backend="numpy", **kw)
           for nm, p in specs}
    clear_runner_cache()
    out = {}
    for label, fuse in (("grid-fused", True), ("per-spec", False)):
        res = simulate_batch(specs, traces, backend="jax", fuse=fuse, **kw)
        for si, (nm, _) in enumerate(specs):
            for k in range(num_traces):
                assert_sim_parity(ref[nm][k], res[si, 0, k], exact=False)
        out[label] = {nm: float(np.mean([r.total_time
                                         for r in res[si, 0]]))
                      for si, (nm, _) in enumerate(specs)}
    stats = cache_stats()
    if stats["unsupported"] != 0 or stats["compiles"] < 1:
        raise AssertionError(f"jax path not taken for every spec: {stats}")

    probe = traces[0, :probe_rounds]
    selected = {}
    for nm in select_schemes:
        got = select_parameters_fast(nm, n, probe, mu=MU, alpha=alpha,
                                     backend="jax")
        want = select_parameters_fast(nm, n, probe, mu=MU, alpha=alpha,
                                      backend="numpy")
        if got.params != want.params:
            raise AssertionError(f"{nm}: jax selected {got.params}, "
                                 f"numpy {want.params}")
        selected[nm] = got.params
    return dict(mean_total_time=out, selected=selected,
                cache_stats=cache_stats(), parity="ok")


def assert_native_gate(n: int, J: int) -> None:
    """The M-SGC runner's device program calls the Pallas gate kernel
    natively (a Mosaic custom call), not its interpreter."""
    import jax
    import numpy as np

    from benchmarks.run import MU, PARAMS
    from repro.core import make_scheme
    from repro.core.batch import _build_jax_runner

    sch = make_scheme("m-sgc", n, J, **PARAMS["m-sgc"])
    runner, _ = _build_jax_runner(sch, J, "selective")
    traces = np.ones((1, J + sch.T, n))
    with jax.enable_x64(True):
        hlo = runner.lower(traces, MU, np.float64(1.0),
                           sch.normalized_load).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("gate_window kernel is not native on the TPU")


def step_profile(tr, reps: int) -> dict:
    """The trainer's coded step on job 1's batch: the compiled
    program's memory analysis (its temporaries are invisible to the
    allocator's peak), device bytes in use around the steps, and
    ``reps`` timed steps, compile excluded.  Donates the trainer's
    model-0 state, so the trainer is spent afterwards."""
    import jax
    import jax.numpy as jnp

    from repro.data import coded_slot_batch

    sch = tr.scheme
    coded = coded_slot_batch(tr._job_batch(1), sch.chunk_slots(1),
                             tr.num_chunks)
    w = jnp.ones((sch.n, tr.slots), jnp.float32)
    p, o = tr.params[0], tr.opt[0]
    step = tr._step.lower(p, o, coded, w).compile()
    ma = step.memory_analysis()
    in_use_before = device_memory().get("bytes_in_use")
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        p, o, metrics = step(p, o, coded, w)
        jax.block_until_ready((p, o, metrics))
        times.append(time.perf_counter() - t0)
    return dict(
        argument_bytes=ma.argument_size_in_bytes,
        output_bytes=ma.output_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        bytes_in_use_before_steps=in_use_before,
        bytes_in_use_after_steps=device_memory().get("bytes_in_use"),
        step_s=times,
        median_step_s=sorted(times)[reps // 2],
    )


def coded_training_phase(cfg, n: int, seq: int, jobs: int, specs,
                         reps: int, seed: int = 0) -> dict:
    """Each spec through ``VectorizedCodedTrainer`` under GE delays;
    every loss must be finite and every job decoded.  Then the step's
    profile (:func:`step_profile`)."""
    from benchmarks.run import GE, MU
    from repro.core import GilbertElliotSource, estimate_alpha, make_scheme
    from repro.train import VectorizedCodedTrainer

    out = {}
    for label, name, params, models, batch in specs:
        sch = make_scheme(name, n, jobs, **params)
        src = GilbertElliotSource(n=n, seed=seed, **GE)
        delays = src.sample_delays(jobs + sch.T + 1)
        tr = VectorizedCodedTrainer(
            scheme=sch, cfg=cfg, num_models=models, batch_size=batch,
            seq_len=seq, lr=1e-4, mu=MU, alpha=estimate_alpha(src),
            seed=seed,
        )
        clock = tr.run(jobs, delays)
        losses = {m: [float(x) for x in tr.losses[m]] for m in tr.losses}
        flat = [x for m in losses for x in losses[m]]
        if len(flat) != jobs or not all(math.isfinite(x) for x in flat):
            raise AssertionError(f"{label}: bad losses {losses}")
        out[label] = dict(losses=losses, sim_clock=clock,
                          chunk_grid=list(sch.chunk_grid()),
                          step=step_profile(tr, reps))
        del tr
    return out


def decode_gradient_check(cfg, n: int, seq: int, specs,
                          seed: int = 0) -> dict:
    """Per spec, one job whose first round had a straggler (conforming
    pattern), decoded: the float32 gradient of the coded loss equals
    the full-batch gradient to ``GRAD_RTOL`` (relative L2, per leaf)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_scheme
    from repro.core.executor import conforming_pattern
    from repro.data import coded_slot_batch, token_batch
    from repro.models import init_params, loss_fn
    from repro.train.coded import make_coded_loss

    cfg = cfg.replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    full_loss = jax.value_and_grad(
        lambda p, batch: loss_fn(p, cfg, batch, aux_weight=0.0))
    out = {}
    for label, name, kw, _, _ in specs:
        jobs = 6
        sch = make_scheme(name, n, jobs, **kw)
        num_chunks, _ = sch.chunk_grid()
        rounds = jobs + sch.T
        pat = conforming_pattern(sch.design_model, rounds, n,
                                 seed=seed + 3, density=0.3)
        weights = {}
        for t in range(1, rounds + 1):
            sch.step(t, pat[t - 1])
            for jd in sch.collect_decodes(t):
                weights[jd.job] = np.asarray(sch.decode_weights(jd))
        job = next((j for j in sorted(weights) if pat[j - 1].any()), None)
        if job is None:
            raise AssertionError(f"{label}: no job met a straggler")
        batch = token_batch(seed, job, num_chunks, seq, cfg.vocab_size)
        coded = coded_slot_batch(batch, sch.chunk_slots(job), num_chunks)
        coded_loss = jax.value_and_grad(make_coded_loss(cfg, num_chunks))

        @jax.jit
        def both(p, coded, w, batch):
            with jax.default_matmul_precision("highest"):
                lc, gc = coded_loss(p, coded, w)
                lf, gf = full_loss(p, batch)
            err = jax.tree.map(
                lambda a, b: jnp.linalg.norm((a - b).ravel())
                / jnp.linalg.norm(b.ravel()), gc, gf)
            return lc, lf, jnp.max(jnp.stack(jax.tree.leaves(err)))

        lc, lf, worst = (float(x) for x in both(
            params, coded, jnp.asarray(weights[job]), batch))
        if not worst <= GRAD_RTOL or not math.isclose(lc, lf,
                                                        rel_tol=1e-5):
            raise AssertionError(f"{label}: decoded != full-batch: worst "
                                 f"leaf rel L2 {worst}, loss {lc} vs {lf}")
        out[label] = dict(job=job,
                          stragglers=np.flatnonzero(pat[job - 1]).tolist(),
                          worst_leaf_rel_l2=worst, coded_loss=lc,
                          full_loss=lf)
    return dict(tol=GRAD_RTOL, **out)


def main() -> int:
    from repro.launch.cache import confine_tpu_logs, enable_compile_cache

    confine_tpu_logs()              # before the TPU library loads
    cache_dir = enable_compile_cache()
    devices = device_check()

    from benchmarks.run import J_TOTAL, N_WORKERS
    from repro.configs import get_config

    meter = CompileMeter()
    print(json.dumps({"compile_cache_dir": cache_dir}), flush=True)

    t0, before = time.perf_counter(), meter.snapshot()
    sim = simulator_phase(N_WORKERS, J_TOTAL, SIM_TRACES, PROBE_ROUNDS,
                          SELECT_SCHEMES)
    assert_native_gate(N_WORKERS, J_TOTAL)
    report("simulator", t0, meter, before, n=N_WORKERS, J=J_TOTAL,
           traces=SIM_TRACES, **sim)

    cfg = get_config(ARCH)
    t0, before = time.perf_counter(), meter.snapshot()
    train = coded_training_phase(cfg, TRAIN_N, TRAIN_SEQ, TRAIN_JOBS,
                                 TRAIN_SPECS, STEP_REPS)
    report("coded-training", t0, meter, before, arch=ARCH, n=TRAIN_N,
           seq=TRAIN_SEQ, jobs=TRAIN_JOBS, **train)

    t0, before = time.perf_counter(), meter.snapshot()
    grad = decode_gradient_check(cfg, TRAIN_N, TRAIN_SEQ, TRAIN_SPECS)
    report("decode-gradient-f32", t0, meter, before, arch=ARCH, **grad)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
