"""Benchmark harness — one function per paper table / figure.

Prints ``name,value,derived`` CSV lines per benchmark plus readable
tables.  All experiments run against the Gilbert-Elliott straggler
source calibrated to the paper's Fig. 1 profile (256 workers, ~5%
straggler fraction, short bursts) since the AWS Lambda cluster is not
reproducible offline; relative orderings are the reproduction target.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run table1       # one benchmark
  PYTHONPATH=src python -m benchmarks.run --json ...   # + BENCH_*.json
  PYTHONPATH=src python -m benchmarks.run --list       # registered benches

``--json`` additionally writes one machine-readable
``BENCH_<name>.json`` per benchmark (parsed metric lines, wall time,
pass/fail) so the perf trajectory is tracked across PRs — the nightly
workflow uploads them as artifacts.
"""

from __future__ import annotations

import io
import json
import sys
import time

import numpy as np

from repro.core import (
    GilbertElliotSource,
    estimate_alpha,
    load_gc,
    load_m_sgc,
    load_sr_sgc,
    lower_bound_bursty,
    make_scheme,
    select_parameters,
    select_parameters_legacy,
    simulate_batch,
    simulate_fast,
)
from repro.core.gc import GradientCode, RepGradientCode

N_WORKERS = 256
J_TOTAL = 480
MU = 1.0
SEED = 0

# measured-vs-analytic wall-clock tolerance for the dist-exec gates:
# real processes only ever run SLOW of the analytic clock (IPC, pickle,
# scheduler jitter), and at time_scale=0.02 the observed overhead is
# 5-15%; 35% keeps the gate meaningful yet robust on loaded CI hosts
DIST_EXEC_TOL = 0.35

# GE chain calibrated to Fig. 1: ~4-5% stragglers, short bursts (mean
# ~1.2 rounds), heavy right tail on completion times.
GE = dict(p_ns=0.035, p_sn=0.85, slow_factor=6.0, jitter=0.05)

# Table-1 operating points.  The paper selects per-scheme parameters by
# the App-J probe procedure on ITS cluster (B=1, W=2 for M-SGC there);
# our GE chain has slightly longer bursts, so the same procedure picks
# B=2, W=3 (see bench_table3_probe).  T = 3 <= M-1 still holds for the
# M=4 interleaved models.
PARAMS = {
    "m-sgc": dict(B=2, W=3, lam=27),
    "sr-sgc": dict(B=2, W=3, lam=23),
    "gc": dict(s=15),
    "uncoded": {},
}


def _source(seed=SEED, n=N_WORKERS):
    return GilbertElliotSource(n=n, seed=seed, **GE)


def bench_fig1_trace_stats():
    """Fig. 1: straggler statistics of the (synthetic) worker profile."""
    from repro.core.straggler import burst_lengths

    src = _source()
    pat = src.sample_pattern(100)
    frac = pat.mean()
    bursts = burst_lengths(pat)
    hist = {k: int((bursts == k).sum()) for k in range(1, 6)}
    delays = src.sample_delays(100)
    p50, p95, p99 = np.percentile(delays, [50, 95, 99])
    print(f"fig1.straggler_fraction,{frac:.4f},")
    print(f"fig1.burst_hist,{hist},")
    print(f"fig1.completion_p50_p95_p99,{p50:.2f}/{p95:.2f}/{p99:.2f},"
          "long right tail as in Fig. 1(c)")
    assert bursts.mean() < 3.0, "bursts should be short (Fig. 1b)"


def bench_fig16_load_runtime():
    """Fig. 16: per-round time grows linearly with normalized load."""
    src = _source()
    delays = src.sample_delays(100)
    alpha = estimate_alpha(src)
    loads = [1 / N_WORKERS, 0.05, 0.1, 0.25, 0.5, 1.0]
    times = [float(np.mean(delays + (L - 1 / N_WORKERS) * alpha)) for L in loads]
    slope = np.polyfit(loads, times, 1)[0]
    for L, t in zip(loads, times):
        print(f"fig16.load_{L:.3f},{t:.3f},avg worker seconds")
    print(f"fig16.slope,{slope:.3f},alpha (s per unit load)")


def _run_scheme(name, J=J_TOTAL, seed=SEED, params=None):
    params = params if params is not None else PARAMS[name]
    sch = make_scheme(name, N_WORKERS, J, **params)
    src = _source(seed)
    delays = src.sample_delays(J + sch.T + 1)
    # batch engine: bit-for-bit the same SimResult as legacy simulate()
    res = simulate_fast(sch, delays, mu=MU, alpha=estimate_alpha(src), J=J)
    return sch, res


def bench_table1_runtime(repeats: int = 3):
    """Table 1: total runtime of M-SGC / SR-SGC / GC / uncoded, J=480."""
    rows = []
    for name in ("m-sgc", "sr-sgc", "gc", "uncoded"):
        times = []
        for r in range(repeats):
            sch, res = _run_scheme(name, seed=SEED + r)
            times.append(res.total_time)
        mean, std = float(np.mean(times)), float(np.std(times))
        rows.append((name, sch.normalized_load, mean, std))
        print(f"table1.{name},{mean:.1f},load={sch.normalized_load:.4f} "
              f"std={std:.1f}")
    by = {r[0]: r[2] for r in rows}
    gain = 1 - by["m-sgc"] / by["gc"]
    print(f"table1.msgc_vs_gc_gain,{gain:.3f},paper reports 0.16")
    assert by["m-sgc"] < by["sr-sgc"] < by["gc"] < by["uncoded"], (
        "Table-1 ordering must hold: M-SGC < SR-SGC < GC < uncoded"
    )


def bench_table3_probe():
    """Table 3: parameter selection vs probe length T_probe."""
    src = _source(SEED + 100)
    full = src.sample_delays(120)
    for name in ("m-sgc", "sr-sgc", "gc"):
        for t_probe in (10, 20, 40, 80):
            cand = select_parameters(
                name, N_WORKERS, full[:t_probe], mu=MU,
                alpha=estimate_alpha(src),
                grid=_small_grid(name),
            )
            sch, res = _run_scheme(name, J=120, seed=SEED + 1,
                                   params=cand.params)
            print(
                f"table3.{name}.Tprobe{t_probe},{res.total_time:.1f},"
                f"params={cand.params} load={cand.load:.4f}"
            )


def _small_grid(name):
    if name == "gc":
        return [{"s": s} for s in (4, 8, 12, 15, 20, 24)]
    if name == "sr-sgc":
        return [
            {"B": B, "W": B + 1, "lam": lam}
            for B in (1, 2) for lam in (8, 16, 23, 28)
        ] + [{"B": 2, "W": 3, "lam": 23}]
    return [
        {"B": B, "W": W, "lam": lam}
        for B, W in ((1, 2), (2, 3))
        for lam in (8, 16, 24, 27, 32)
    ]


def bench_table4_decode():
    """Table 4: master decode time (solve + combine) per scheme."""
    rng = np.random.default_rng(0)
    grad_dim = 120_000  # ~ the paper's CNN gradient size

    def time_decode(code, survivors, parts, reps=5):
        t0 = time.perf_counter()
        for _ in range(reps):
            beta = code.decode_vector(survivors)
            _ = beta[survivors] @ parts
            code._decode_cache.clear() if hasattr(code, "_decode_cache") else None
        return (time.perf_counter() - t0) / reps * 1e3

    # GC s=15 -> GC-Rep (16 | 256); M-SGC lam=27 -> general code
    rep = RepGradientCode(N_WORKERS, 15)
    gen = GradientCode(N_WORKERS, 27, seed=0)
    surv_rep = sorted(rng.choice(N_WORKERS, N_WORKERS - 10, replace=False).tolist())
    surv_gen = sorted(rng.choice(N_WORKERS, N_WORKERS - 20, replace=False).tolist())
    parts_rep = rng.standard_normal((len(surv_rep), grad_dim))
    parts_gen = rng.standard_normal((len(surv_gen), grad_dim))
    ms_rep = time_decode(rep, surv_rep, parts_rep)
    ms_gen = time_decode(gen, surv_gen, parts_gen)
    print(f"table4.gc_rep_decode_ms,{ms_rep:.1f},s=15 n=256 (GC-Rep App. G)")
    print(f"table4.general_decode_ms,{ms_gen:.1f},lam=27 n=256 (M-SGC groups)")
    print("table4.note,0,decode hidden in master idle time when M > T+1 (App. K)")


def bench_fig2_progress():
    """Fig. 2(a): jobs completed vs clock time."""
    for name in ("m-sgc", "gc", "uncoded"):
        sch, res = _run_scheme(name, J=120)
        times = sorted(res.job_done_time.values())
        q = [times[int(len(times) * f) - 1] for f in (0.25, 0.5, 0.75, 1.0)]
        print(f"fig2.{name}.jobs_25_50_75_100pct,"
              f"{q[0]:.0f}/{q[1]:.0f}/{q[2]:.0f}/{q[3]:.0f},seconds")


def bench_fig11_load_bounds():
    """Fig. 11: normalized loads vs the Thm-F.1 converse, n=20 B=3 lam=4."""
    n, B, lam = 20, 3, 4
    for W in (4, 7, 10, 13, 16):
        m = load_m_sgc(n, B, W, lam)
        lb = lower_bound_bursty(n, B, W, lam)
        line = f"fig11.W{W},{m:.4f},bound={lb:.4f}"
        if (W - 1) % B == 0:
            line += f" srsgc={load_sr_sgc(n, B, W, lam):.4f}"
        print(line)
        assert m >= lb - 1e-12


def bench_fig17_sensitivity():
    """Fig. 17 / App. J.1: runtime sensitivity to (B, W, lam)."""
    src = _source(SEED + 7)
    delays = src.sample_delays(90)
    alpha = estimate_alpha(src)
    J = 80
    # M-SGC: sweep lam at fixed (B, W); runtime should be flat above a
    # threshold (Remark J.1: "lam not critical once large enough")
    msgc_times = {}
    for lam in (8, 16, 32, 48, 64):
        sch = make_scheme("m-sgc", N_WORKERS, J, B=2, W=3, lam=lam)
        msgc_times[lam] = simulate_fast(sch, delays, mu=MU, alpha=alpha, J=J).total_time
        print(f"fig17.msgc_lam{lam},{msgc_times[lam]:.1f},"
              f"load={sch.normalized_load:.4f}")
    # runtime flattens once lam clears the per-window distinct-straggler
    # count (~35 for this chain); load stays ~2/n throughout
    flat = max(msgc_times[48], msgc_times[64]) / min(msgc_times[48], msgc_times[64])
    assert flat < 1.1, "M-SGC should be insensitive to lam above threshold"
    assert msgc_times[8] > msgc_times[48], "below threshold, wait-outs dominate"
    # SR-SGC: lam drives the load directly -> runtime must grow
    for lam in (8, 16, 24, 32):
        sch = make_scheme("sr-sgc", N_WORKERS, J, B=2, W=3, lam=lam)
        t = simulate_fast(sch, delays, mu=MU, alpha=alpha, J=J).total_time
        print(f"fig17.srsgc_lam{lam},{t:.1f},load={sch.normalized_load:.4f}")
    # B sensitivity for M-SGC at fixed W-B gap
    for B, W in ((1, 2), (2, 3), (3, 4)):
        sch = make_scheme("m-sgc", N_WORKERS, J, B=B, W=W, lam=24)
        t = simulate_fast(sch, delays, mu=MU, alpha=alpha, J=J).total_time
        print(f"fig17.msgc_B{B}W{W},{t:.1f},T={sch.T}")


def bench_ge_fit():
    """App. C: the GE chain fits the observed straggler transitions."""
    from repro.core.straggler import fit_gilbert_elliot, suggest_parameters

    src = _source(SEED)
    pat = src.sample_pattern(300)
    fit = fit_gilbert_elliot(pat)
    print(f"gefit.p_ns,{fit['p_ns']:.4f},true={GE['p_ns']}")
    print(f"gefit.p_sn,{fit['p_sn']:.4f},true={GE['p_sn']}")
    print(f"gefit.stationary,{fit['stationary']:.4f},")
    assert abs(fit["p_ns"] - GE["p_ns"]) < 0.01
    assert abs(fit["p_sn"] - GE["p_sn"]) < 0.05
    sugg = suggest_parameters(pat)
    print(f"gefit.suggested_B,{sugg['B']},lam_by_W={sugg['lam_by_W']}")


def bench_fig18_switchover():
    """Fig. 18 / App. K.2: start uncoded, switch to coded after T_probe.

    Uses the REAL multi-model training driver (every gradient computed
    and decoded) at a reduced worker count so the python master stays
    fast; compares against never switching."""
    from repro.core import GilbertElliotSource
    from repro.core.schemes import make_scheme as _mk
    from repro.core.simulator import simulate as _sim
    from repro.train import run_adaptive

    n, J, t_probe = 64, 60, 20
    delays = GilbertElliotSource(
        n=n, p_ns=GE["p_ns"], p_sn=GE["p_sn"],
        slow_factor=GE["slow_factor"], seed=SEED + 11,
    ).sample_delays(J + 8)
    total, probe, params, drv = run_adaptive(
        4, J, delays, scheme_name="m-sgc", t_probe=t_probe,
        grid=[{"B": B, "W": B + 1, "lam": lam}
              for B in (1, 2) for lam in (8, 16, 24)],
    )
    print(f"fig18.adaptive_total,{total:.1f},probe={probe:.1f} "
          f"selected={params}")
    never = _sim(
        _mk("uncoded", n, J), delays, mu=MU, alpha=8.0, J=J
    ).total_time
    print(f"fig18.never_switch,{never:.1f},pure uncoded")
    assert total < never, "switching must beat staying uncoded"
    final = [drv.losses[m][-1] for m in range(4)]
    print(f"fig18.final_losses,{[f'{l:.3f}' for l in final]},"
          "training carried across the switch")


def bench_appg_rep():
    """App. G: GC-Rep vs general GC — same load, superset tolerance,
    hence fewer wait-outs and no slower runtime."""
    n, J, s = 256, 120, 15  # (s+1) | n -> Rep available
    src = _source(SEED + 3)
    delays = src.sample_delays(J + 2)
    alpha = estimate_alpha(src)
    rows = {}
    for rep in (True, False):
        sch = make_scheme("gc", n, J, s=s, prefer_rep=rep)
        res = simulate_fast(sch, delays, mu=MU, alpha=alpha, J=J)
        rows[rep] = res
        print(f"appg.gc_{'rep' if rep else 'general'},"
              f"{res.total_time:.1f},waitouts={res.waitouts}")
    assert rows[True].waitouts <= rows[False].waitouts
    assert rows[True].total_time <= rows[False].total_time + 1e-9
    # SR-SGC-Rep (Algorithm 3) vs the same parameters
    sch = make_scheme("sr-sgc", n, J, B=2, W=3, lam=23)
    res = simulate_fast(sch, delays, mu=MU, alpha=alpha, J=J)
    print(f"appg.sr_sgc_s{sch.s},{res.total_time:.1f},"
          f"rep={'RepGradientCode' in type(sch.code).__name__} "
          f"waitouts={res.waitouts}")


def bench_batch_speedup():
    """Batch engine acceptance: the App-J probe sweep at the Table-1
    operating point (n=256) must beat the legacy per-candidate loop by
    >= 10x while choosing the identical candidate."""
    src = _source(SEED + 42)
    probe = src.sample_delays(30)
    alpha = estimate_alpha(src)
    for name in ("m-sgc", "gc"):
        grid = _small_grid(name)
        # best-of-3 for the fast timing: the observed margin is >100x,
        # so only scheduler noise in a single short run could ever drag
        # the ratio near the 10x gate on a loaded CI runner
        t_fast = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fast = select_parameters(name, N_WORKERS, probe, mu=MU,
                                     alpha=alpha, grid=grid)
            t_fast = min(t_fast, time.perf_counter() - t0)
        t0 = time.perf_counter()
        legacy = select_parameters_legacy(name, N_WORKERS, probe, mu=MU,
                                          alpha=alpha, grid=grid)
        t_legacy = time.perf_counter() - t0
        assert fast.params == legacy.params, (fast, legacy)
        assert fast.est_time == legacy.est_time, (fast, legacy)
        speedup = t_legacy / t_fast
        print(f"batch.select_{name}_fast_s,{t_fast:.3f},params={fast.params}")
        print(f"batch.select_{name}_legacy_s,{t_legacy:.3f},oracle (same choice)")
        print(f"batch.select_{name}_speedup,{speedup:.1f},acceptance >= 10x")
        assert speedup >= 10.0, f"batch engine only {speedup:.1f}x faster"


def bench_lockstep(repeats: int = 3):
    """Lockstep-engine acceptance: an App-J-sized (specs x traces) grid
    at n=256 through `simulate_batch` (one lockstep batch per spec)
    must beat the PR-1 per-cell `simulate_fast` loop by >= 5x while
    producing bit-identical `SimResult`s in every cell."""
    from repro.core import simulate_lockstep
    from repro.core.simulator import params_delay

    num_traces, rounds = 64, 44
    traces = np.stack(
        [_source(SEED + 60 + k).sample_delays(rounds) for k in range(num_traces)]
    )
    alpha = estimate_alpha(_source())
    names = ("m-sgc", "sr-sgc", "gc", "uncoded")
    Js = {nm: rounds - params_delay(nm, PARAMS[nm]) for nm in names}

    # per-cell fast loop (the PR-1 path); best-of-2 so scheduler noise
    # on a loaded runner skews neither side of the ratio
    t_cell = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        cell_results = {
            nm: [
                simulate_fast(make_scheme(nm, N_WORKERS, Js[nm], **PARAMS[nm]),
                              traces[ti], mu=MU, alpha=alpha, J=Js[nm])
                for ti in range(num_traces)
            ]
            for nm in names
        }
        t_cell = min(t_cell, time.perf_counter() - t0)

    # lockstep engine: one untimed warmup (allocator/caches), then
    # best-of-N so scheduler noise on a loaded CI runner can't drag
    # the observed ~6x margin near the 5x gate
    simulate_lockstep("gc", PARAMS["gc"], traces[:8], mu=MU, alpha=alpha,
                      J=Js["gc"])
    t_lock = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        lock_results = {
            nm: simulate_lockstep(nm, PARAMS[nm], traces, mu=MU, alpha=alpha,
                                  J=Js[nm])
            for nm in names
        }
        t_lock = min(t_lock, time.perf_counter() - t0)

    for nm in names:
        for ra, rb in zip(cell_results[nm], lock_results[nm]):
            assert ra.total_time == rb.total_time
            assert (ra.round_times == rb.round_times).all()
            assert ra.job_done_round == rb.job_done_round
            assert ra.job_done_time == rb.job_done_time
            assert ra.waitouts == rb.waitouts
            assert (ra.effective_pattern == rb.effective_pattern).all()
    sims = len(names) * num_traces
    speedup = t_cell / t_lock
    print(f"lockstep.grid,{sims},(specs x traces) cells at n={N_WORKERS}")
    print(f"lockstep.percell_s,{t_cell:.3f},PR-1 simulate_fast loop")
    print(f"lockstep.lockstep_s,{t_lock:.3f},bit-identical results")
    print(f"lockstep.speedup,{speedup:.1f},acceptance >= 5x")
    assert speedup >= 5.0, f"lockstep engine only {speedup:.1f}x faster"


def bench_lockstep_jax(waves: int = 6, wave_traces: int = 8, repeats: int = 3):
    """Device-resident lockstep acceptance: the jitted-``lax.scan``
    engine on the Table-1 grid at n=256, fed Monte-Carlo waves of GE
    traces (how ``simulate_batch``/``select_parameters`` consume the
    engine — many modest batches per spec, where the compiled round
    loop's elimination of per-round Python dispatch bites hardest;
    very large single batches converge to memory-bound parity).

    Gates: (1) compile-cache reuse — the steady-state sweep must run
    >= 3x faster than the first (compiling) call over the same wave;
    (2) >= 2x steady-state speedup over the numpy lockstep engine on
    CPU; plus exact-bookkeeping/allclose parity on one wave.
    """
    from repro.core import simulate_lockstep
    from repro.core.simulator import params_delay

    rounds = 44
    alpha = estimate_alpha(_source())
    names = ("m-sgc", "sr-sgc", "gc", "uncoded")
    Js = {nm: rounds - params_delay(nm, PARAMS[nm]) for nm in names}
    wave_list = [
        np.stack([
            _source(SEED + 300 + w * wave_traces + k).sample_delays(rounds)
            for k in range(wave_traces)
        ])
        for w in range(waves)
    ]

    def sweep(backend, wave_subset):
        out = {}
        for wi, tr in enumerate(wave_subset):
            for nm in names:
                out[(wi, nm)] = simulate_lockstep(
                    nm, PARAMS[nm], tr, mu=MU, alpha=alpha, J=Js[nm],
                    backend=backend,
                )
        return out

    # first call: compiles one scan per spec
    t0 = time.perf_counter()
    jax_first = sweep("jax", wave_list[:1])
    t_first = time.perf_counter() - t0
    # steady state: every later wave reuses the compiled runners
    t_jax = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax_res = sweep("jax", wave_list)
        t_jax = min(t_jax, time.perf_counter() - t0)
    t_np = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        np_res = sweep("numpy", wave_list)
        t_np = min(t_np, time.perf_counter() - t0)

    # parity: exact bool/int bookkeeping, allclose floats (wave 0)
    from repro.core.testing import assert_sim_parity

    for nm in names:
        for a, b in zip(np_res[(0, nm)], jax_first[(0, nm)]):
            assert_sim_parity(a, b, exact=False)

    sims = waves * wave_traces * len(names)
    t_wave = t_jax / waves
    reuse = t_first / t_wave
    speedup = t_np / t_jax
    print(f"lockstepjax.grid,{sims},(waves x traces x specs) sims at "
          f"n={N_WORKERS}")
    print(f"lockstepjax.first_call_s,{t_first:.3f},one compile per spec")
    print(f"lockstepjax.steady_s,{t_jax:.3f},{waves} waves, cache warm")
    print(f"lockstepjax.numpy_s,{t_np:.3f},numpy lockstep engine")
    print(f"lockstepjax.cache_reuse,{reuse:.1f},first/steady-wave, "
          "acceptance >= 3x")
    print(f"lockstepjax.speedup,{speedup:.2f},acceptance >= 2x")
    assert reuse >= 3.0, (
        f"compile cache not reused: first call only {reuse:.1f}x a "
        "steady-state wave"
    )
    assert speedup >= 2.0, f"jax lockstep only {speedup:.2f}x numpy"


def bench_grid_jax(num_specs: int = 64, num_traces: int = 8,
                   rounds: int = 24, n: int = N_WORKERS,
                   smoke: bool = False, repeats: int = 3):
    """Grid-fused engine acceptance: a same-shape ``num_specs``-spec GC
    sweep at n=256 through ``simulate_batch(backend="jax")``.

    Gates: (1) ONE compilation per shape bucket, verified via the
    runner-cache compile counter (the sweep folds into a single bucket,
    so exactly one vmapped scan is built and jitted); (2) >= 3x
    end-to-end — compiles included, how a fresh sweep actually pays —
    over the per-spec cached-runner path (``fuse=False``), which
    compiles one scan per spec; (3) grid-fused outputs exact on the
    bool/int bookkeeping and allclose on floats vs the numpy oracle.
    The ``grid-jax-smoke`` variant shrinks the sweep for tier-1 CI and
    skips the timing gate (compile-count + parity only).
    """
    from repro.core import (
        cache_stats,
        clear_runner_cache,
        grid_plan,
    )

    # general-GC s sweep: every spec shares (scheme, n, J, T=0, waitout,
    # cells) — `s` is consumed as a traced threshold, so ONE bucket
    specs = [("gc", {"s": s, "prefer_rep": False})
             for s in range(8, 8 + num_specs)]
    traces = np.stack([
        _source(SEED + 500 + k, n=n).sample_delays(rounds)
        for k in range(num_traces)
    ])
    alpha = estimate_alpha(_source(n=n))

    plan = grid_plan(specs, traces)
    buckets = len(plan["buckets"])
    print(f"gridjax.buckets,{buckets},{num_specs} same-shape specs at n={n}")
    assert buckets == 1, f"expected one shape bucket, planner made {buckets}"

    clear_runner_cache()
    t0 = time.perf_counter()
    fused = simulate_batch(specs, traces, mu=MU, alpha=alpha,
                           backend="jax", fuse=True)
    t_fused_e2e = time.perf_counter() - t0
    compiles = cache_stats()["compiles"]
    print(f"gridjax.compiles,{compiles},acceptance == {buckets} "
          "(one per shape bucket)")
    assert compiles == buckets, (
        f"{compiles} runner compiles for {buckets} shape bucket(s)"
    )

    # parity: exact bool/int bookkeeping, allclose floats vs the oracle
    from repro.core.testing import assert_sim_parity

    oracle = simulate_batch(specs, traces, mu=MU, alpha=alpha,
                            backend="numpy")
    for si in range(len(specs)):
        for c in range(num_traces):
            assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                              exact=False)
    print(f"gridjax.parity,{len(specs) * num_traces},cells vs numpy oracle")

    if smoke:
        print(f"gridjax.fused_e2e_s,{t_fused_e2e:.3f},smoke (no timing gate)")
        return

    # steady state: the bucket runner is cached
    t_fused = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        simulate_batch(specs, traces, mu=MU, alpha=alpha,
                       backend="jax", fuse=True)
        t_fused = min(t_fused, time.perf_counter() - t0)

    # per-spec cached-runner path: one compile per spec end-to-end
    clear_runner_cache()
    t0 = time.perf_counter()
    simulate_batch(specs, traces, mu=MU, alpha=alpha,
                   backend="jax", fuse=False)
    t_spec_e2e = time.perf_counter() - t0
    spec_compiles = cache_stats()["compiles"]
    t_spec = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        simulate_batch(specs, traces, mu=MU, alpha=alpha,
                       backend="jax", fuse=False)
        t_spec = min(t_spec, time.perf_counter() - t0)

    speedup = t_spec_e2e / t_fused_e2e
    print(f"gridjax.fused_e2e_s,{t_fused_e2e:.3f},1 compile + sweep")
    print(f"gridjax.perspec_e2e_s,{t_spec_e2e:.3f},{spec_compiles} compiles "
          "+ sweep")
    print(f"gridjax.fused_steady_s,{t_fused:.3f},cache warm")
    print(f"gridjax.perspec_steady_s,{t_spec:.3f},cache warm")
    print(f"gridjax.steady_speedup,{t_spec / t_fused:.2f},informational")
    print(f"gridjax.e2e_speedup,{speedup:.2f},acceptance >= 3x")
    assert speedup >= 3.0, (
        f"grid fusion only {speedup:.2f}x the per-spec runners end-to-end"
    )


def bench_batch_montecarlo():
    """Monte-Carlo scheme comparison on the batch engine: Table-1
    operating points x independent GE traces in one simulate_batch
    call (sim results are seed-invariant on the load-only path, so
    the variance axis is traces)."""
    traces = np.stack([_source(SEED + 50 + k).sample_delays(64) for k in range(8)])
    specs = [(name, PARAMS[name]) for name in ("m-sgc", "sr-sgc", "gc", "uncoded")]
    t0 = time.perf_counter()
    grid = simulate_batch(specs, traces, mu=MU, alpha=estimate_alpha(_source()))
    dt = time.perf_counter() - t0
    sims = grid.size
    for i, (name, _) in enumerate(specs):
        per_job = [r.total_time / len(r.job_done_round) for r in grid[i].ravel()]
        print(f"batch.mc_{name}_per_job_s,{np.mean(per_job):.3f},"
              f"std={np.std(per_job):.3f} over {traces.shape[0]} traces")
    print(f"batch.mc_sims_per_s,{sims / dt:.1f},{sims} sims in {dt:.2f}s")


def bench_scenario_sweep(n: int = 64, rounds: int = 40,
                         num_traces: int = 4, smoke: bool = False):
    """Scenario sweep (Sec. 6): paper schemes vs the dynamic-clustering
    (Buyukates et al.) and stochastic-block (Charles & Papailiopoulos)
    GC baselines over the straggler trace library — five naturally
    occurring worker profiles (bursty/heavy GE, Lambda cold starts,
    heterogeneous fleets with per-worker alpha, replayed recorded
    waves), one ``simulate_batch`` grid per scenario.

    Gates: (1) the per-round baselines (gc / dc-gc / sb-gc / sr-sgc
    here) run at EQUAL normalized load, so the comparison isolates
    tolerance placement; (2) at equal load the clustered baselines'
    admissible sets are supersets of plain GC's per round, so their
    mean runtime must not exceed GC's on any scenario (the paper's
    Sec.-6 argument, which the differential suite pins per trace).
    The ``scenario-sweep-smoke`` variant shrinks the grid for tier-1.
    """
    from repro.core import trace_library

    lib = trace_library(n=n, rounds=rounds, num_traces=num_traces,
                        seed=SEED)
    s = 3
    # labeled specs: gc-rep (the paper's App-G default at (s+1) | n)
    # and general-code gc are separate baselines — Rep's coverage model
    # is itself a superset tolerance, so the dominance gate below
    # compares the clustered baselines against the GENERAL code
    specs = [
        ("m-sgc", "m-sgc", dict(B=1, W=2, lam=8)),
        ("sr-sgc", "sr-sgc", dict(B=1, W=2, lam=2 * s)),  # same s / load
        ("gc-rep", "gc", dict(s=s)),
        ("gc", "gc", dict(s=s, prefer_rep=False)),
        ("dc-gc", "dc-gc", dict(C=4, s=s)),
        ("sb-gc", "sb-gc", dict(C=4, s=s)),
        ("uncoded", "uncoded", {}),
    ]
    eq_load = {"gc-rep", "gc", "dc-gc", "sb-gc", "sr-sgc"}
    t0 = time.perf_counter()
    means: dict[tuple, float] = {}
    for sc in lib:
        grid = simulate_batch([(nm, p) for _, nm, p in specs], sc.delays,
                              mu=MU, alpha=sc.alpha)
        for i, (label, _, _) in enumerate(specs):
            cells = [r for r in grid[i].ravel()]
            per_job = [r.total_time / len(r.job_done_round) for r in cells]
            wo = float(np.mean([r.waitouts for r in cells]))
            load = cells[0].normalized_load
            means[(sc.name, label)] = float(np.mean(per_job))
            print(f"scenario.{sc.name}.{label},{np.mean(per_job):.4f},"
                  f"per-job s (std={np.std(per_job):.4f} "
                  f"waitouts={wo:.1f} load={load:.4f})")
            if label in eq_load:
                assert abs(load - (s + 1) / n) < 1e-12, (sc.name, label)
        order = sorted((means[(sc.name, lb)], lb) for lb, _, _ in specs)
        print(f"scenario.{sc.name}.winner,{order[0][1]},"
              f"fastest per-job of {len(specs)} schemes")
    dt = time.perf_counter() - t0
    sims = len(lib) * len(specs) * num_traces
    print(f"scenario.sims,{sims},{len(lib)} scenarios x {len(specs)} "
          f"schemes x {num_traces} traces (n={n}) in {dt:.1f}s")
    # equal-load dominance: per round, <= s total stragglers implies
    # <= s per cluster/block, so the clustered baselines admit a
    # superset of general-GC's patterns and can never run slower on
    # the same trace (tests/test_scenarios.py pins this per trace)
    for sc in lib:
        for lb in ("dc-gc", "sb-gc"):
            assert means[(sc.name, lb)] <= means[(sc.name, "gc")] + 1e-9, (
                f"{lb} slower than general gc at equal load on {sc.name}"
            )
    if smoke:
        print("scenario.status,1,smoke (reduced grid)")


def bench_coded_train(n: int = 8, models: int = 4, jobs: int = 24,
                      smoke: bool = False):
    """Sec. 6 end-to-end: concurrent multi-model coded TRAINING.

    Runs all 7 registered schemes (``examples.multimodel_training.
    scheme_grid``) through ``train.driver.VectorizedCodedTrainer`` —
    real transformer LMs, real decoded gradients via one jitted
    ``make_coded_train_step`` per scheme — under the adversarial
    ``trace_library()`` profiles (bursty GE + replayed waves), and
    reports the simulated wall clock plus the MEASURED per-job step
    time (jit-warmed, so compile cost is excluded).

    Gates: (1) M-SGC beats plain GC on simulated clock on the bursty
    trace (the Table-1 ordering, end to end through training); (2)
    M-SGC's measured jitted step time beats GC's — its normalized load
    is lower, so the coded view carries fewer examples per step; (3)
    every training loss is finite for every scheme.  The
    ``coded-train-smoke`` variant shrinks jobs/models for tier-1.
    """
    import jax
    import jax.numpy as jnp

    from examples.multimodel_training import scheme_grid
    from repro.configs.qwen2_0_5b import SMOKE
    from repro.core import trace_library
    from repro.data import coded_slot_batch
    from repro.train import VectorizedCodedTrainer

    cfg = SMOKE.replace(num_layers=1, d_model=64, num_heads=2,
                        num_kv_heads=1, head_dim=32, d_ff=128,
                        vocab_size=128)
    lib = {sc.name: sc for sc in trace_library(
        n=n, rounds=jobs + 8, num_traces=1, seed=SEED)}
    traces = ["ge-bursty"] if smoke else ["ge-bursty", "replayed-waves"]
    batch = 32
    reps = 3 if smoke else 10

    sim_clock: dict[tuple, float] = {}
    step_ms: dict[str, float] = {}
    for label, name, kw in scheme_grid(n):
        for tr_name in traces:
            sc = lib[tr_name]
            sch = make_scheme(name, n, jobs, **kw)
            trainer = VectorizedCodedTrainer(
                scheme=sch, cfg=cfg, num_models=models,
                batch_size=batch, seq_len=8, lr=1e-3, mu=MU,
                alpha=float(np.mean(sc.alpha)), seed=SEED,
            )
            if tr_name == traces[0]:
                # measure the jitted coded step in isolation (the
                # per-round master compute the Sec.-6 claim is about);
                # warm first so compile stays outside the timing
                coded = coded_slot_batch(
                    trainer._job_batch(1), sch.chunk_slots(1),
                    trainer.num_chunks,
                )
                # (the step donates params/opt, so feed each output
                # back in as the next input)
                w0 = jnp.ones((n, trainer.slots), jnp.float32)
                p0, o0, _ = trainer._step(trainer.params[0],
                                          trainer.opt[0], coded, w0)
                jax.block_until_ready(p0)
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    p0, o0, _ = trainer._step(p0, o0, coded, w0)
                    jax.block_until_ready(p0)
                    ts.append(time.perf_counter() - t0)
                trainer.params[0], trainer.opt[0] = p0, o0
                step_ms[label] = 1e3 * float(np.median(ts))
            clock = trainer.run(jobs, sc.delays[0])
            sim_clock[(tr_name, label)] = clock
            finals = [trainer.losses[m][-1] for m in range(models)]
            assert all(np.isfinite(f) for f in finals), (label, tr_name)
            print(f"codedtrain.{tr_name}.{label},{clock:.2f},sim clock "
                  f"(load={sch.normalized_load:.4f} T={sch.T} "
                  f"final_loss={np.mean(finals):.3f})")
    for label in step_ms:
        print(f"codedtrain.step_ms.{label},{step_ms[label]:.2f},"
              f"measured jitted coded step (median of {reps})")
    for tr_name in traces:
        gain = 1 - sim_clock[(tr_name, "m-sgc")] / sim_clock[(tr_name, "gc")]
        print(f"codedtrain.{tr_name}.msgc_vs_gc_gain,{gain:.4f},"
              "sim-clock gain (paper Table 1: 16%)")
    ratio = step_ms["m-sgc"] / step_ms["gc"]
    print(f"codedtrain.msgc_vs_gc_step_ratio,{ratio:.3f},"
          "measured step-time ratio (< 1: lower coded load wins)")
    assert sim_clock[("ge-bursty", "m-sgc")] < sim_clock[("ge-bursty", "gc")], (
        "M-SGC must beat plain GC on the bursty trace end to end"
    )
    assert ratio < 1.0, f"M-SGC measured step time regressed: {ratio:.3f}"
    if smoke:
        print("codedtrain.status,1,smoke (reduced jobs/models)")


def bench_dist_exec(n=8, jobs=16, time_scale=0.02, smoke=False,
                    transport="pipe"):
    """§Harness: REAL master/worker rounds vs the analytic clock.

    Spawns ``n`` real worker processes (``repro.dist``), runs GC and
    M-SGC end to end on an injected GE-bursty trace (workers enact
    their planned delays before reporting, the master applies the
    mu-rule + Remark-2.3 gate on wall clock), and gates:

    1. the recorded straggler pattern replays BIT-IDENTICALLY through
       ``simulate_fast`` on the same trace (same gate decisions);
    2. every job decodes exactly (max |err| vs the full-batch gradient);
    3. measured wall-clock makespan agrees with the analytic clock
       within ``DIST_EXEC_TOL`` relative (measured carries real IPC +
       scheduling overhead, so it only ever runs slow);
    4. M-SGC's measured makespan <= GC's — the Table-1 ordering holds
       on real processes, not just in simulation;
    5. an injected message drop is recovered by the retry path.

    ``transport`` selects the wire (``"pipe"`` or ``"tcp"``): the
    ``dist-exec-tcp`` variant runs the identical gates over real
    sockets with length-prefixed CRC framing, plus the compute-vs-
    communication split from the wire timestamps.  The
    ``dist-exec-smoke`` / ``dist-exec-tcp-smoke`` tier-1 variants
    shrink to 4 workers.
    """
    from repro.core.straggler import trace_library
    from repro.dist import FaultSpec, HarnessConfig, run_harness

    src = GilbertElliotSource(n=n, seed=SEED, p_ns=0.09, p_sn=0.5,
                              slow_factor=6.0, jitter=0.05)
    delays = src.sample_delays(jobs + 8)
    alpha = src.alpha
    # lam == n puts M-SGC in the Remark-3.2 regime: load (W-1+B)/(n(W-1))
    # < GC's (s+1)/n, so the ordering gate measures a real load gap
    schemes = [("gc", {"s": 1}), ("m-sgc", {"B": 1, "W": 3, "lam": n})]

    measured = {}
    for name, params in schemes:
        cfg = HarnessConfig(alpha=alpha, time_scale=time_scale, seed=SEED,
                            transport=transport)
        res = run_harness(name, n, jobs, delays, params=params, config=cfg)
        assert not res.aborted, (name, res.abort_reason)
        sim = simulate_fast(make_scheme(name, n, jobs, **params), delays,
                            mu=MU, alpha=alpha, J=jobs)
        assert np.array_equal(res.trace_model.pattern,
                              sim.effective_pattern), (
            f"{name}: recorded pattern does not replay through "
            "simulate_fast"
        )
        assert np.allclose(res.analytic_round_times,
                           sim.round_times * time_scale), name
        assert res.decode_max_err < 1e-8, (name, res.decode_max_err)
        assert abs(res.agreement - 1.0) <= DIST_EXEC_TOL, (
            f"{name}: measured/analytic = {res.agreement:.3f} outside "
            f"±{DIST_EXEC_TOL}"
        )
        measured[name] = res.measured_makespan
        print(f"distexec.{name}.measured_s,{res.measured_makespan:.3f},"
              f"wall clock over {n} worker processes")
        print(f"distexec.{name}.analytic_s,{res.analytic_makespan:.3f},"
              f"simulate_fast clock x time_scale={time_scale}")
        print(f"distexec.{name}.agreement,{res.agreement:.3f},"
              f"measured/analytic (gate: within ±{DIST_EXEC_TOL})")
        print(f"distexec.{name}.decode_max_err,{res.decode_max_err:.2e},"
              "max |decoded - full-batch gradient|")
        print(f"distexec.{name}.waitouts,{res.waitouts},"
              f"retries={res.retries} deaths={len(res.deaths)}")
        # compute-vs-communication split from the wire timestamps
        wcn = res.ledger.worker_counters()
        wire = sum(wcn["wire_send_s"]) + sum(wcn["wire_recv_s"])
        print(f"distexec.{name}.wire_send_s,{sum(wcn['wire_send_s']):.4f},"
              f"master->worker wire seconds ({transport})")
        print(f"distexec.{name}.wire_recv_s,{sum(wcn['wire_recv_s']):.4f},"
              f"worker->master wire seconds ({transport})")
        print(f"distexec.{name}.wire_frac,{wire / (n * res.measured_makespan):.4f},"
              "per-worker comms share of the measured makespan")
    assert measured["m-sgc"] <= measured["gc"], (
        "M-SGC measured makespan must not exceed GC's: "
        f"{measured['m-sgc']:.3f} vs {measured['gc']:.3f}"
    )
    gain = 1.0 - measured["m-sgc"] / measured["gc"]
    print(f"distexec.msgc_vs_gc_gain,{gain:.4f},measured-makespan gain")

    # retry path: one worker drops its first-attempt result once
    drop_jobs = 4 if smoke else 6
    cfg = HarnessConfig(
        alpha=alpha, time_scale=time_scale, seed=SEED, round_timeout=0.3,
        faults={0: FaultSpec(drop_rounds=frozenset({2}))},
    )
    res = run_harness("gc", n, drop_jobs, delays, params={"s": 1},
                      config=cfg)
    assert not res.aborted, res.abort_reason
    assert res.retries >= 1, "dropped message must trigger a resend"
    assert len(res.decoded_jobs) == drop_jobs
    print(f"distexec.drop.retries,{res.retries},"
          "resends recovering an injected message drop")
    # per-worker flakiness counters ride into the JSON artifact
    wc = res.ledger.worker_counters()
    print(f"distexec.workers.resends,{sum(wc['resends'])},"
          f"per-worker {wc['resends']}")
    print(f"distexec.workers.respawns,{sum(wc['respawns'])},"
          f"per-worker {wc['respawns']}")
    print(f"distexec.workers.deaths,{sum(wc['deaths'])},"
          f"per-worker {wc['deaths']}")

    if not smoke:
        # the checked-in recorded-harness scenario replays what a run
        # like this recorded (provenance for the trace library)
        rec = [sc for sc in trace_library(n=n, rounds=jobs, num_traces=1,
                                          seed=SEED)
               if sc.name == "recorded-harness"]
        assert rec, "recorded-harness scenario missing from the library"
        print(f"distexec.recorded_scenario,1,"
              f"library replay shape {rec[0].delays.shape}")
    else:
        print("distexec.status,1,smoke (4 workers, reduced jobs)")


def bench_chaos(n=6, jobs=10, time_scale=0.02, smoke=False):
    """§Fault tolerance: chaos campaigns + checkpoint/resume gates.

    Two hard gates for the elastic harness (``docs/fault_tolerance.md``):

    1. **Kill-and-respawn wave** — >=2 workers killed at different
       rounds (1 in the smoke variant) under a bursty design model, so
       the gate MUST block on each rejoin: the campaign auditor
       requires zero aborts, every job exact-decoded, full telemetry,
       and the expected respawn/rejoin transitions in the supervision
       log.  The full run also audits a correlated regional outage, a
       flapping worker, and a delayed rejoin.
    2. **Checkpoint/resume bit-identity** — a fault-free master is
       killed mid-run (``stop_after_round``) and resumed from its
       latest ``checkpoint_every``-rounds checkpoint; the resumed
       recording (restored prefix + freshly measured suffix) must
       replay BIT-IDENTICALLY through ``simulate_fast`` and decode
       every job.

    The whole bench runs under a hard ``SIGALRM`` job timeout: a
    deadlocked campaign fails the gate instead of hanging CI.
    """
    import signal
    import tempfile

    from repro.dist import (
        HarnessConfig,
        delayed_rejoin,
        flapping,
        kill_wave,
        regional_outage,
        run_campaign,
        run_harness,
    )

    budget_s = 180 if smoke else 540

    def _alarm(signum, frame):
        raise TimeoutError(
            f"chaos bench exceeded its {budget_s}s hard job timeout "
            "(deadlocked campaign?)"
        )

    old_handler = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(budget_s)
    try:
        # -- gate 1: kill-and-respawn wave -------------------------------
        kills = {1: 2} if smoke else {1: 2, 4: 5}
        camps = [kill_wave(n, jobs, kills, respawn_backoff_s=0.1)]
        if not smoke:
            camps += [
                regional_outage(n, jobs, [0, 3], at_round=3,
                                respawn_backoff_s=0.1),
                flapping(n, jobs, worker=2, first_kill=2, rekill_after=6,
                         respawn_backoff_s=0.1),
                delayed_rejoin(n, jobs, worker=1, at_round=3,
                               ready_delay=0.5, respawn_backoff_s=0.1),
            ]
        for camp in camps:
            report = run_campaign(camp, time_scale=time_scale, seed=SEED)
            assert report.passed, (camp.name, report.violations)
            res = report.result
            tag = camp.name.replace("-", "")
            print(f"chaos.{tag}.decoded,{len(res.decoded_jobs)},"
                  f"all {res.J} jobs exact-decoded, zero aborts")
            print(f"chaos.{tag}.respawns,{res.respawns},"
                  f"rejoins={res.rejoins} deaths={res.deaths}")
            print(f"chaos.{tag}.decode_max_err,{res.decode_max_err:.2e},"
                  "certificate vs full-batch gradient")
        wave = run_campaign(camps[0], time_scale=time_scale,
                            seed=SEED + 1).result
        assert wave.respawns >= len(kills) and wave.rejoins >= len(kills)
        wc = wave.ledger.worker_counters()
        print(f"chaos.killwave.worker_respawns,{sum(wc['respawns'])},"
              f"per-worker {wc['respawns']}")

        # -- gate 2: master killed mid-run, resumed from checkpoint ------
        name, params = "m-sgc", {"B": 1, "W": 3, "lam": n}
        src = GilbertElliotSource(n=n, seed=SEED, p_ns=0.09, p_sn=0.5,
                                  slow_factor=6.0, jitter=0.05)
        sch = make_scheme(name, n, jobs, **params)
        delays = src.sample_delays(jobs + sch.T + 2)
        stop_at = 4 if smoke else 7
        with tempfile.TemporaryDirectory() as td:
            ck = f"{td}/master.npz"
            base = dict(alpha=src.alpha, time_scale=time_scale, seed=SEED,
                        checkpoint_path=ck, checkpoint_every=3)
            first = run_harness(name, n, jobs, delays, params=params,
                                config=HarnessConfig(
                                    stop_after_round=stop_at, **base))
            assert first.stopped and not first.aborted, first.abort_reason
            res = run_harness(name, n, jobs, delays, params=params,
                              config=HarnessConfig(**base),
                              resume_from=ck)
        assert not res.aborted, res.abort_reason
        assert len(res.decoded_jobs) == jobs
        sim = simulate_fast(make_scheme(name, n, jobs, **params), delays,
                            mu=MU, alpha=src.alpha, J=jobs)
        assert np.array_equal(res.trace_model.pattern,
                              sim.effective_pattern), (
            "resumed recording does not replay bit-identically"
        )
        assert np.allclose(res.analytic_round_times,
                           sim.round_times * time_scale)
        assert res.decoded_jobs == sim.job_done_round
        ck_round = (stop_at // 3) * 3
        print(f"chaos.resume.rounds,{res.ledger.rounds},"
              f"master killed after round {stop_at}, resumed from the "
              f"round-{ck_round} checkpoint, pattern bit-identical "
              "through simulate_fast")
        print(f"chaos.resume.decode_max_err,{res.decode_max_err:.2e},"
              "post-resume decode certificate")
        if smoke:
            print("chaos.status,1,smoke (4 workers, one kill+respawn)")
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)


def bench_dist_exec_tcp():
    """§Harness over TCP: the identical dist-exec gates on real sockets
    (CRC framing, id-deduped delivery) plus the wire-time split."""
    bench_dist_exec(transport="tcp")


def bench_chaos_net(n=6, jobs=10, time_scale=0.02, smoke=False):
    """§Network faults: partition-vs-death and lossy-wire gates (TCP).

    Two hard gates for the transport tier (``repro.dist.net``,
    ``docs/fault_tolerance.md`` §Network transport & partitions):

    1. **Partition heal** — one worker's TCP link goes dark mid-run
       (both directions; the full bench also audits the one-way
       variant) and heals within the round hard-deadline.  The
       supervisor must classify it PARTITIONED (process alive), block
       the bursty gate on the heal, and take the worker back via the
       open-round replay with ZERO respawns burned — partition-vs-death
       discrimination, audited by the campaign.
    2. **Lossy network** — every link carries added latency + jitter
       plus probabilistic drop / duplicate / reorder.  The timeout /
       resend tier plus message-id dedup must still decode every job
       exactly with no corrupted gradient.

    Runs under a hard ``SIGALRM`` job timeout like ``bench_chaos``.
    """
    import signal

    from repro.dist import lossy_network, partition_heal, run_campaign

    budget_s = 180 if smoke else 480

    def _alarm(signum, frame):
        raise TimeoutError(
            f"chaos-net bench exceeded its {budget_s}s hard job timeout "
            "(wedged partition?)"
        )

    old_handler = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(budget_s)
    try:
        camps = [partition_heal(n, jobs, worker=1, at_round=3, heal_s=0.8,
                                respawn_backoff_s=0.1)]
        if not smoke:
            camps += [
                partition_heal(n, jobs, worker=2, at_round=2, heal_s=0.6,
                               mode="oneway", respawn_backoff_s=0.1,
                               name="partition-heal-oneway"),
            ]
        camps += [lossy_network(n, jobs)]
        for camp in camps:
            report = run_campaign(camp, time_scale=time_scale, seed=SEED)
            assert report.passed, (camp.name, report.violations)
            res = report.result
            tag = camp.name.replace("-", "")
            print(f"chaosnet.{tag}.decoded,{len(res.decoded_jobs)},"
                  f"all {res.J} jobs exact-decoded, zero aborts")
            print(f"chaosnet.{tag}.partitions,{res.partitions},"
                  f"heals={res.heals} respawns={res.respawns}")
            print(f"chaosnet.{tag}.decode_max_err,{res.decode_max_err:.2e},"
                  "certificate vs full-batch gradient")
            if camp.name.startswith("partition-heal"):
                assert res.respawns == 0, (
                    f"{camp.name}: partition burned {res.respawns} "
                    "respawn(s) — must heal instead"
                )
        if smoke:
            print("chaosnet.status,1,smoke (twoway partition + lossy wire)")
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)


def bench_roofline():
    """§Roofline: three terms per (arch, shape, mesh) from the dry-run."""
    from . import roofline

    rows = roofline.roofline_table()
    if not rows:
        print("roofline.status,0,no dry-run artifacts — run "
              "`python -m repro.launch.dryrun --all` first")
        return
    print(roofline.format_table(rows))
    for r in rows:
        print(
            f"roofline.{r.arch}.{r.shape}.{r.mesh}{'.coded' if r.coded else ''},"
            f"{r.step_s:.3e},dominant={r.dominant} ratio={r.ratio:.2f}"
        )


BENCHES = {
    "fig1": bench_fig1_trace_stats,
    "fig16": bench_fig16_load_runtime,
    "table1": bench_table1_runtime,
    "table3": bench_table3_probe,
    "table4": bench_table4_decode,
    "fig2": bench_fig2_progress,
    "fig11": bench_fig11_load_bounds,
    "fig17": bench_fig17_sensitivity,
    "fig18": bench_fig18_switchover,
    "gefit": bench_ge_fit,
    "appg": bench_appg_rep,
    "batch": bench_batch_speedup,
    "batchmc": bench_batch_montecarlo,
    "lockstep": bench_lockstep,
    "lockstep-jax": bench_lockstep_jax,
    "grid-jax": bench_grid_jax,
    "grid-jax-smoke": lambda: bench_grid_jax(
        num_specs=8, num_traces=4, rounds=20, n=64, smoke=True
    ),
    "scenario-sweep": bench_scenario_sweep,
    "scenario-sweep-smoke": lambda: bench_scenario_sweep(
        n=32, rounds=24, num_traces=2, smoke=True
    ),
    "coded-train": bench_coded_train,
    "coded-train-smoke": lambda: bench_coded_train(
        n=8, models=2, jobs=8, smoke=True
    ),
    "dist-exec": bench_dist_exec,
    "dist-exec-smoke": lambda: bench_dist_exec(
        n=4, jobs=6, smoke=True
    ),
    "dist-exec-tcp": bench_dist_exec_tcp,
    "dist-exec-tcp-smoke": lambda: bench_dist_exec(
        n=4, jobs=6, smoke=True, transport="tcp"
    ),
    "chaos": bench_chaos,
    "chaos-smoke": lambda: bench_chaos(
        n=4, jobs=6, smoke=True
    ),
    "chaos-net": bench_chaos_net,
    "chaos-net-smoke": lambda: bench_chaos_net(
        n=4, jobs=6, smoke=True
    ),
    "roofline": bench_roofline,
}


def _bench_description(name: str, fn) -> str:
    """One-line description for ``--list``: the first docstring line,
    or the smoke-variant convention for the lambda wrappers."""
    doc = (fn.__doc__ or "").strip()
    if doc:
        return doc.splitlines()[0]
    if name.endswith("-smoke"):
        return f"tier-1 smoke variant of '{name[:-len('-smoke')]}'"
    return "(no description)"


class _Tee(io.StringIO):
    """Duplicate bench stdout into a buffer for the --json recorder."""

    def __init__(self, stream):
        super().__init__()
        self._stream = stream

    def write(self, s):
        self._stream.write(s)
        return super().write(s)


def _parse_metrics(text: str) -> dict:
    """Pull ``key,value,note`` CSV lines out of a bench's output."""
    metrics = {}
    for line in text.splitlines():
        parts = line.split(",", 2)
        if len(parts) < 2 or " " in parts[0] or "." not in parts[0]:
            continue
        key, value = parts[0], parts[1]
        note = parts[2] if len(parts) > 2 else ""
        try:
            value = float(value)
        except ValueError:
            pass
        metrics[key] = {"value": value, "note": note}
    return metrics


def _write_json(name: str, seconds: float, status: str, text: str,
                error: str | None) -> None:
    payload = {
        "bench": name,
        "status": status,
        "seconds": round(seconds, 3),
        "metrics": _parse_metrics(text),
    }
    if error:
        payload["error"] = error
    path = f"BENCH_{name.replace('-', '_')}.json"
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"{name}.json_written,{path},")


def main() -> None:
    from repro.launch.cache import confine_tpu_logs, enable_compile_cache

    args = sys.argv[1:]
    if "--list" in args:
        width = max(len(name) for name in BENCHES)
        for name, fn in BENCHES.items():
            print(f"{name:<{width}}  {_bench_description(name, fn)}")
        return
    confine_tpu_logs()
    enable_compile_cache()
    json_mode = "--json" in args
    # the -smoke variants are tier-1 stand-ins for their full benches;
    # a no-name invocation (the nightly sweep) runs only the full ones
    which = [a for a in args if a != "--json"] or [
        name for name in BENCHES if not name.endswith("-smoke")
    ]
    failed = []
    for name in which:
        print(f"\n===== {name} =====")
        t0 = time.time()
        tee = _Tee(sys.stdout) if json_mode else None
        error = None
        try:
            if tee is not None:
                old, sys.stdout = sys.stdout, tee
                try:
                    BENCHES[name]()
                finally:
                    sys.stdout = old
            else:
                BENCHES[name]()
        except Exception as exc:  # noqa: BLE001 - record, then re-raise
            error = f"{type(exc).__name__}: {exc}"
            if tee is None:
                raise
        dt = time.time() - t0
        if tee is not None:
            _write_json(name, dt, "fail" if error else "pass",
                        tee.getvalue(), error)
        if error:
            print(f"{name}.status,fail,{error}")
            failed.append(name)
        else:
            print(f"{name}.bench_seconds,{dt:.1f},")
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
