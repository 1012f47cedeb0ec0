"""Scenario-sweep subsystem tests: the dynamic-clustering /
stochastic-block gate models, the straggler trace library, and the
heterogeneous (per-worker) alpha plumbing.

The kernel-vs-oracle differential coverage for the two new schemes
lives in ``tests/test_lockstep.py`` (CONFIGS) and
``tests/test_grid_fused.py`` (fused buckets); this module pins the
model-level math (closed-form minimal drops vs brute force, assignment
properties), the library's determinism, and the per-worker alpha
contract across every engine path.
"""

import numpy as np
import pytest

from repro.core import (
    DynamicClusterModel,
    GilbertElliotSource,
    LambdaTraceGenerator,
    StochasticBlockModel,
    TraceModel,
    make_scheme,
    simulate,
    simulate_batch,
    simulate_fast,
    simulate_lockstep,
    trace_library,
)
from repro.core.straggler import _round_robin_clusters
from repro.core.testing import assert_sim_parity

GE = dict(p_ns=0.10, p_sn=0.5, slow_factor=6.0)


def _traces(n, rounds, num, seed0=0):
    return np.stack([
        GilbertElliotSource(n=n, seed=seed0 + k, **GE).sample_delays(rounds)
        for k in range(num)
    ])


# ---------------------------------------------------------------------------
# cluster models
# ---------------------------------------------------------------------------


def test_round_robin_assignment_properties():
    rng = np.random.default_rng(0)
    n, C = 12, 4
    # no history: identity layout worker i -> cluster i mod C
    cid0 = _round_robin_clusters(np.zeros(n, dtype=bool), C)
    assert (cid0 == np.arange(n) % C).all()
    for _ in range(50):
        prev = rng.random(n) < rng.uniform(0.05, 0.6)
        cid = np.asarray(_round_robin_clusters(prev, C))
        # balanced clusters (n % C == 0 here)
        assert (np.bincount(cid, minlength=C) == n // C).all()
        # previous stragglers spread evenly: at most ceil(S/C) per cluster
        S = int(prev.sum())
        per = np.bincount(cid[prev], minlength=C)
        assert per.max(initial=0) <= -(-S // C)


def test_dynamic_cluster_model_incremental_matches_conforms():
    """Committing rows one at a time through admits_round must agree
    with the global conforms() on the full pattern."""
    rng = np.random.default_rng(1)
    n, C, s = 12, 3, 2
    m = DynamicClusterModel(n, C, s)
    for _ in range(30):
        pat = rng.random((8, n)) < 0.25
        ok_inc, hist = True, np.zeros((0, n), dtype=bool)
        for t in range(pat.shape[0]):
            if not m.admits_round(hist, pat[t]):
                ok_inc = False
                break
            hist = np.concatenate([hist, pat[t][None]], axis=0)
        assert m.conforms(pat[: t + 1] if not ok_inc else pat) == ok_inc


def test_stochastic_block_model_and_scheme_seed_draw():
    n, C, s = 12, 3, 1
    a = make_scheme("sb-gc", n, 5, C=C, s=s, seed=3)
    b = make_scheme("sb-gc", n, 5, C=C, s=s, seed=3)
    c = make_scheme("sb-gc", n, 5, C=C, s=s, seed=4)
    assert (a.block_of == b.block_of).all()
    assert not (a.block_of == c.block_of).all()
    # equal blocks of size n/C
    assert (np.bincount(a.block_of, minlength=C) == n // C).all()
    m = a.design_model
    assert isinstance(m, StochasticBlockModel)
    # a round concentrated inside one block violates; spread across
    # blocks with <= s each conforms
    one_block = np.zeros(n, dtype=bool)
    one_block[np.asarray(a.block_of) == 0] = True
    assert not m.conforms(one_block[None])
    spread = np.zeros(n, dtype=bool)
    for blk in range(C):
        spread[np.flatnonzero(np.asarray(a.block_of) == blk)[0]] = True
    assert m.conforms(spread[None])


@pytest.mark.parametrize("which", ["dc", "sb"])
def test_cluster_min_drops_matches_brute_force(which):
    """The closed-form minimal-drop solver == brute force over drop
    prefixes of the stable ascending-cost order (the scalar gate's
    greedy semantics)."""
    rng = np.random.default_rng(7)
    n, C, s = 12, 3, 1
    if which == "dc":
        model = DynamicClusterModel(n, C, s)
    else:
        blocks = tuple(int(b) for b in rng.permutation(n) % C)
        model = StochasticBlockModel(n, C, s, blocks)
    for trial in range(60):
        prev = rng.random(n) < 0.3
        cand = rng.random(n) < rng.uniform(0.1, 0.7)
        cost = rng.random(n)
        kh = 1 if (which == "dc" and trial % 2) else 0
        buf = prev[None, None, :] if kh else np.zeros((1, 0, n), dtype=bool)
        order = np.argsort(np.where(cand, cost, np.inf),
                           kind="stable")[None, :]
        rank = np.empty_like(order)
        rank[0, order[0]] = np.arange(n)
        k_analytic = int(model.min_drops_batch(
            buf, cand[None], rank, order
        )[0])
        # brute force: smallest k whose drop prefix admits
        k_brute = None
        for k in range(int(cand.sum()) + 1):
            reduced = cand & (rank[0] >= k)
            win = (
                np.concatenate([buf[0], reduced[None]], axis=0)
                if kh else reduced[None]
            )
            if model.suffix_ok(win):
                k_brute = k
                break
        assert k_brute is not None
        assert k_analytic == k_brute, (which, trial, k_analytic, k_brute)


# ---------------------------------------------------------------------------
# trace library
# ---------------------------------------------------------------------------


def test_trace_library_shapes_and_determinism():
    n, rounds, num = 8, 12, 2
    lib = trace_library(n=n, rounds=rounds, num_traces=num, seed=3)
    lib2 = trace_library(n=n, rounds=rounds, num_traces=num, seed=3)
    names = [sc.name for sc in lib]
    assert names == ["ge-bursty", "ge-heavy", "lambda-cold",
                     "lambda-hetero", "replayed-waves",
                     "recorded-harness", "recorded-netfault"]
    for sc, sc2 in zip(lib, lib2):
        assert sc.delays.shape == (num, rounds, n)
        assert (sc.delays == sc2.delays).all()      # seed-deterministic
        assert np.isfinite(sc.delays).all() and (sc.delays > 0).all()
    het = dict((sc.name, sc) for sc in lib)["lambda-hetero"]
    assert np.shape(het.alpha) == (n,)              # per-worker slope
    assert (np.asarray(het.alpha) > 0).all()


def test_lambda_generator_cold_start_and_hetero():
    gen = LambdaTraceGenerator(n=16, seed=2, cold_fraction=1.0,
                               cold_start=5.0, p_event=0.0, p_ns=0.0)
    d = gen.sample_delays(6)
    # every worker pays the cold start exactly once, on round 0
    assert (d[0] > d[1:].max(axis=0) + 2.0).all()
    hot = LambdaTraceGenerator(n=16, seed=2, hetero=0.5)
    assert hot.worker_alpha().shape == (16,)
    assert hot.worker_alpha().std() > 0
    assert isinstance(hot.alpha, float)
    # shared fleet across trace seeds via speed_seed
    a = LambdaTraceGenerator(n=16, seed=5, hetero=0.5, speed_seed=99)
    b = LambdaTraceGenerator(n=16, seed=6, hetero=0.5, speed_seed=99)
    assert (a.speed_factors() == b.speed_factors()).all()


def test_trace_model_replays_recorded_pattern():
    rng = np.random.default_rng(4)
    pat = rng.random((6, 10)) < 0.2
    tm = TraceModel(pat, base_time=1.0, slow_factor=6.0, jitter=0.0)
    # cyclic tiling past the recorded horizon
    assert (tm.sample_pattern(15)[:6] == pat).all()
    assert (tm.sample_pattern(15)[6:12] == pat).all()
    d = tm.sample_delays(6)
    assert (d[pat] > 1.0 - 1e-12).all()
    assert np.allclose(d[~pat], 1.0)


# ---------------------------------------------------------------------------
# heterogeneous per-worker alpha
# ---------------------------------------------------------------------------


def test_hetero_alpha_scalar_paths_bitforbit():
    """Vector (n,) alpha: legacy simulate == simulate_fast == numpy
    lockstep, bit for bit, for schemes across T shapes."""
    n, J = 12, 14
    gen = LambdaTraceGenerator(n=n, seed=1, hetero=0.4)
    alpha = gen.worker_alpha()
    traces = np.stack([
        LambdaTraceGenerator(n=n, seed=1 + k, hetero=0.4,
                             speed_seed=3).sample_delays(J + 4)
        for k in range(2)
    ])
    for name, kw in [("gc", dict(s=3)), ("sr-sgc", dict(B=1, W=2, lam=3)),
                     ("dc-gc", dict(C=4, s=1)), ("sb-gc", dict(C=3, s=1))]:
        rl = simulate_lockstep(name, kw, traces, alpha=alpha, J=J,
                               backend="numpy")
        for c in range(2):
            legacy = simulate(make_scheme(name, n, J, **dict(kw)),
                              traces[c], alpha=alpha, J=J)
            fast = simulate_fast(make_scheme(name, n, J, **dict(kw)),
                                 traces[c], alpha=alpha, J=J)
            assert_sim_parity(legacy, fast, exact=True)
            assert_sim_parity(legacy, rl[c], exact=True)


def test_hetero_alpha_jax_lockstep_allclose():
    n, J = 12, 12
    alpha = LambdaTraceGenerator(n=n, seed=1, hetero=0.4).worker_alpha()
    traces = _traces(n, J + 4, 2, seed0=11)
    for name, kw in [("gc", dict(s=3)), ("m-sgc", dict(B=1, W=2, lam=3)),
                     ("dc-gc", dict(C=3, s=1))]:
        ref = simulate_lockstep(name, kw, traces, alpha=alpha, J=J,
                                backend="numpy")
        got = simulate_lockstep(name, kw, traces, alpha=alpha, J=J,
                                backend="jax")
        for a, b in zip(ref, got):
            assert_sim_parity(a, b, exact=False)


def test_hetero_alpha_through_round_loads_protocol():
    """The per-cell ``round_loads`` branch of the numpy engine (the
    path load-adaptive kernels take) must broadcast a per-worker alpha
    exactly like the constant-load precompute: a kernel that OVERRIDES
    round_loads with the same constant value must reproduce the
    built-in scheme bit for bit."""
    from repro.core import register_scheme
    from repro.core.kernel import _KERNELS, UncodedKernel, register_kernel
    from repro.core.schemes import _SCHEME_FACTORIES, NoCodingScheme

    class AdaptiveScheme(NoCodingScheme):
        name = "adaptive-load-test"

        def __init__(self, n, J, *, seed=0):
            super().__init__(n, J)

    class AdaptiveKernel(UncodedKernel):
        name = "adaptive-load-test"

        def round_loads(self, state, t):  # same value, overridden path
            return self.bk.xp.full(state.cells, self.normalized_load)

    register_scheme("adaptive-load-test",
                    lambda n, J, **kw: AdaptiveScheme(n, J, **kw))
    register_kernel("adaptive-load-test", AdaptiveKernel)
    try:
        n, J = 12, 10
        alpha = LambdaTraceGenerator(n=n, seed=2, hetero=0.5).worker_alpha()
        traces = _traces(n, J, 2, seed0=21)
        ref = simulate_lockstep("uncoded", {}, traces, alpha=alpha, J=J,
                                backend="numpy")
        got = simulate_lockstep("adaptive-load-test", {}, traces,
                                alpha=alpha, J=J, backend="numpy")
        for a, b in zip(ref, got):
            b2 = type(b)(**{**b.__dict__, "scheme": "uncoded"})
            assert_sim_parity(a, b2, exact=True)
    finally:
        _SCHEME_FACTORIES.pop("adaptive-load-test", None)
        _KERNELS.pop("adaptive-load-test", None)


# ---------------------------------------------------------------------------
# sb-gc seed fan-out (the core/testing.py fixture pattern, on a real
# scheme) and the equal-load dominance property of the baselines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend",
    ["numpy", "jax"],
)
def test_sbgc_seed_fan_out_both_backends(backend):
    """sb-gc is seed-sensitive: the batch engine must fan the seed axis
    out (distinct objects AND distinct gate behaviour per seed), with
    every cell equal to its scalar run."""
    n, num_traces = 12, 2
    seeds = tuple(range(5))
    traces = _traces(n, 14, num_traces, seed0=31)
    kw = {"C": 3, "s": 1}
    grid = simulate_batch([("sb-gc", kw)], traces, seeds=seeds, alpha=6.0,
                          J=12, backend=backend)
    assert grid.shape == (1, len(seeds), num_traces)
    for ki, seed in enumerate(seeds):
        for ti in range(num_traces):
            r = grid[0, ki, ti]
            assert r is not grid[0, 0, ti] or ki == 0
            ref = simulate_fast(
                make_scheme("sb-gc", n, 12, seed=seed, **kw),
                traces[ti], alpha=6.0, J=12,
            )
            assert_sim_parity(ref, r, exact=backend == "numpy")
    # the block draw actually moves the runtimes across seeds
    totals = {round(grid[0, ki, ti].total_time, 9)
              for ki in range(len(seeds)) for ti in range(num_traces)}
    assert len(totals) > num_traces


@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_clustered_baselines_dominate_gc_at_equal_load(waitout):
    """Per-round, any candidate set with <= s total stragglers keeps
    <= s per cluster/block, so at EQUAL load the clustered baselines'
    admissible sets are supersets of plain GC's — round durations (and
    wait-out counts) must never exceed GC's on the same trace."""
    n, J, s = 12, 16, 2
    traces = _traces(n, J, 3, seed0=41)
    gc = simulate_lockstep("gc", {"s": s, "prefer_rep": False}, traces,
                           alpha=6.0, J=J, waitout=waitout)
    for name, kw in [("dc-gc", {"C": 4, "s": s}),
                     ("sb-gc", {"C": 4, "s": s})]:
        got = simulate_lockstep(name, kw, traces, alpha=6.0, J=J,
                                waitout=waitout)
        for a, b in zip(gc, got):
            assert b.normalized_load == a.normalized_load
            assert b.waitouts <= a.waitouts
            assert (b.round_times <= a.round_times + 1e-9).all()
            assert b.total_time <= a.total_time + 1e-9
