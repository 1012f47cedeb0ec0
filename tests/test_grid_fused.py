"""Parity + planner suite for the grid-fused jax engine:
``simulate_batch(..., backend="jax")`` buckets specs by static shape
key and runs each bucket as ONE vmapped jitted ``lax.scan``
(``core.batch``).  Contract: grid-fused results == the per-spec
``simulate_lockstep`` runners == the numpy oracle, EXACT on the
bool/int bookkeeping (done rounds, waitout counts, gate patterns) and
allclose on float loads/runtimes — across every scheme, both wait-out
modes, ragged J/T grids forcing multiple buckets, and seed-sensitive
fan-out.  Also gates the planner (same-shape sweeps fold into one
bucket) and the one-compile-per-bucket property (the tier-1 smoke
variant of ``benchmarks/run.py grid-jax``)."""

import numpy as np
import pytest

from repro.core import (
    GilbertElliotSource,
    cache_stats,
    clear_runner_cache,
    grid_plan,
    make_scheme,
    simulate_batch,
    simulate_fast,
)
from repro.core.testing import assert_sim_parity

GE = dict(p_ns=0.08, p_sn=0.6, slow_factor=6.0)

# mixed grid: a GC-Rep spec (structural s), two general-GC specs that
# fuse on s, two SR-SGC shapes, two M-SGC specs that fuse on lam plus
# a third shape, and the uncoded baseline
SPECS = [
    ("gc", {"s": 3}),
    ("gc", {"s": 4, "prefer_rep": False}),
    ("gc", {"s": 7, "prefer_rep": False}),
    ("sr-sgc", {"B": 1, "W": 2, "lam": 3}),
    ("sr-sgc", {"B": 2, "W": 3, "lam": 5}),
    ("m-sgc", {"B": 2, "W": 3, "lam": 5}),
    ("m-sgc", {"B": 2, "W": 3, "lam": 7}),
    ("m-sgc", {"B": 1, "W": 3, "lam": 12}),
    ("uncoded", {}),
]


def _traces(n, rounds, num, seed0=0):
    return np.stack([
        GilbertElliotSource(n=n, seed=seed0 + k, **GE).sample_delays(rounds)
        for k in range(num)
    ])


@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_grid_fused_matches_perspec_and_oracle(waitout):
    n, rounds, cells = 12, 22, 3
    traces = _traces(n, rounds, cells, seed0=20)
    fused = simulate_batch(SPECS, traces, alpha=6.0, waitout=waitout,
                           backend="jax", fuse=True)
    perspec = simulate_batch(SPECS, traces, alpha=6.0, waitout=waitout,
                             backend="jax", fuse=False)
    oracle = simulate_batch(SPECS, traces, alpha=6.0, waitout=waitout,
                            backend="numpy")
    for si in range(len(SPECS)):
        for c in range(cells):
            # fused == per-spec staged runners and == the numpy oracle
            assert_sim_parity(perspec[si, 0, c], fused[si, 0, c],
                              exact=False)
            assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                              exact=False)


def test_grid_plan_same_shape_sweep_is_one_bucket():
    n, rounds = 12, 14
    traces = _traces(n, rounds, 2)
    specs = [("gc", {"s": s, "prefer_rep": False}) for s in range(3, 9)]
    plan = grid_plan(specs, traces)
    assert plan["fallback"] == [] and plan["infeasible"] == []
    assert len(plan["buckets"]) == 1
    (bucket,) = plan["buckets"]
    assert bucket["fused"] == ["s"]
    assert bucket["specs"] == list(range(len(specs)))


def test_grid_plan_splits_structural_shapes():
    """GC-Rep (structural s), general GC (fused s), and a different-T
    scheme must land in distinct buckets."""
    n, rounds = 12, 18
    traces = _traces(n, rounds, 2)
    plan = grid_plan(SPECS, traces)
    assert plan["fallback"] == []
    assert len(plan["buckets"]) > 3
    by_scheme = {}
    for b in plan["buckets"]:
        by_scheme.setdefault(b["scheme"], []).append(b)
    # the two general-GC specs share one bucket; the Rep spec does not
    gc_specs = sorted(sum((b["specs"] for b in by_scheme["gc"]), []))
    assert gc_specs == [0, 1, 2]
    assert any(b["specs"] == [1, 2] for b in by_scheme["gc"])
    # the two (B=2, W=3) m-sgc specs fuse on lam
    assert any(b["specs"] == [5, 6] and b["fused"] == ["lam"]
               for b in by_scheme["m-sgc"])


def test_grid_single_compile_per_bucket_smoke():
    """Tier-1 smoke variant of the ``grid-jax`` bench gate: a
    same-shape sweep compiles ONCE, and repeat calls are pure cache
    hits."""
    n, rounds, cells = 16, 12, 2
    traces = _traces(n, rounds, cells, seed0=33)
    specs = [("gc", {"s": s, "prefer_rep": False}) for s in (3, 5, 7, 9)]
    plan = grid_plan(specs, traces)
    assert len(plan["buckets"]) == 1
    clear_runner_cache()
    fused = simulate_batch(specs, traces, alpha=6.0, backend="jax",
                           fuse=True)
    st = cache_stats()
    assert st["compiles"] == len(plan["buckets"]) == 1
    simulate_batch(specs, traces, alpha=6.0, backend="jax", fuse=True)
    st2 = cache_stats()
    assert st2["compiles"] == st["compiles"]
    assert st2["hits"] > st["hits"]
    oracle = simulate_batch(specs, traces, alpha=6.0, backend="numpy")
    for si in range(len(specs)):
        for c in range(cells):
            assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                              exact=False)


def test_grid_ragged_and_strict_false():
    """Ragged J/T (multiple buckets) plus an infeasible spec under
    strict=False — None rows, everything else at full parity."""
    n, rounds = 12, 22
    specs = [
        ("gc", {"s": 3}),
        ("sr-sgc", {"B": 2, "W": 4, "lam": 3}),   # B does not divide W-1
        ("m-sgc", {"B": 2, "W": 3, "lam": 5}),
        ("uncoded", {}),
    ]
    traces = _traces(n, rounds, 2, seed0=40)
    plan = grid_plan(specs, traces)
    assert len(plan["buckets"]) == 3     # three distinct (J, T) shapes
    assert plan["infeasible"] == [1]     # the rejected spec is reported
    grid = simulate_batch(specs, traces, alpha=6.0, strict=False,
                          backend="jax", fuse=True)
    assert all(r is None for r in grid[1].ravel())
    for i in (0, 2, 3):
        name, params = specs[i]
        T = make_scheme(name, n, 1, **dict(params)).T
        J = rounds - T
        for c in range(2):
            ref = simulate_fast(make_scheme(name, n, J, **dict(params)),
                                traces[c], alpha=6.0, J=J)
            assert_sim_parity(ref, grid[i, 0, c], exact=False)


def test_grid_seed_sensitive_fanout():
    """Seed-sensitive schemes fan the seed axis out through the fused
    path (per-seed prototypes feed the stacked load), insensitive
    schemes broadcast."""
    from repro.core.testing import (
        SEEDED_UNCODED,
        register_testing_schemes,
        unregister_testing_schemes,
    )

    register_testing_schemes()
    try:
        n, rounds = 12, 14
        traces = _traces(n, rounds, 2, seed0=60)
        specs = [(SEEDED_UNCODED, {}), ("gc", {"s": 3})]
        seeds = (0, 1, 2)
        fused = simulate_batch(specs, traces, seeds=seeds, alpha=6.0,
                               backend="jax", fuse=True)
        ref = simulate_batch(specs, traces, seeds=seeds, alpha=6.0,
                             backend="numpy")
        for si in range(len(specs)):
            for ki in range(len(seeds)):
                for c in range(2):
                    assert_sim_parity(ref[si, ki, c], fused[si, ki, c],
                                      exact=False)
        # the sensitive scheme's seeds produce different runtimes...
        assert fused[0, 0, 0].total_time != fused[0, 1, 0].total_time
        # ...while the insensitive row is broadcast (shared objects)
        assert fused[1, 0, 0] is fused[1, 1, 0]
    finally:
        unregister_testing_schemes()


def test_grid_unsupported_gate_falls_back():
    """Specs the fused path cannot stage route to the per-spec fallback
    transparently (planner ``fallback`` + identical results)."""
    from repro.core import NoCodingScheme, register_scheme
    from repro.core.kernel import _KERNELS, UncodedKernel, register_kernel
    from repro.core.schemes import _SCHEME_FACTORIES
    from repro.core.straggler import StragglerModel

    class OddModel(StragglerModel):
        # no min_drops_batch, no vectorized batch hooks
        def conforms(self, pattern):
            return bool(pattern.sum() % 2 == 0) or not pattern.any()

        def suffix_ok(self, win):
            return not win.any()

        @property
        def window(self):
            return 1

    class OddScheme(NoCodingScheme):
        name = "odd-gate-fused"

        def __init__(self, n, J, *, seed=0):
            super().__init__(n, J)
            self.design_model = OddModel()

    class OddKernel(UncodedKernel):
        name = "odd-gate-fused"

    register_scheme("odd-gate-fused",
                    lambda n, J, **kw: OddScheme(n, J, **kw))
    register_kernel("odd-gate-fused", OddKernel)
    try:
        n, rounds = 12, 12
        traces = _traces(n, rounds, 2, seed0=70)
        specs = [("odd-gate-fused", {}), ("gc", {"s": 3})]
        plan = grid_plan(specs, traces)
        assert plan["fallback"] == [0]
        assert len(plan["buckets"]) == 1
        fused = simulate_batch(specs, traces, alpha=6.0, backend="jax",
                               fuse=True)
        ref = simulate_batch(specs, traces, alpha=6.0, backend="numpy")
        for si in range(2):
            for c in range(2):
                assert_sim_parity(ref[si, 0, c], fused[si, 0, c],
                                  exact=False)
    finally:
        _SCHEME_FACTORIES.pop("odd-gate-fused", None)
        _KERNELS.pop("odd-gate-fused", None)


@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_grid_fused_dead_lanes_do_not_poison_bucket(waitout):
    """strict=False dead-lane handling on the FUSED path: a spec whose
    lanes die mid-run (wait-out contract violated) shares a vmap bucket
    with healthy specs; the dead lanes must yield None and the sibling
    specs' results must stay identical to the per-spec staged runners
    and the numpy oracle."""
    from repro.core.testing import (
        FRAGILE_GC,
        register_fragile_gc,
        unregister_fragile_gc,
    )

    register_fragile_gc()
    try:
        n, rounds, cells = 12, 18, 3
        traces = _traces(n, rounds, cells, seed0=80)
        # one doomed spec (admits up to d=6 stragglers, decodes only 1)
        # between two healthy ones; s and d both fuse, so the planner
        # folds all three into ONE bucket
        specs = [
            (FRAGILE_GC, {"s": 4, "d": 4}),
            (FRAGILE_GC, {"s": 1, "d": 6}),
            (FRAGILE_GC, {"s": 5, "d": 5}),
        ]
        plan = grid_plan(specs, traces, waitout=waitout)
        assert len(plan["buckets"]) == 1
        assert plan["buckets"][0]["fused"] == ["s", "d"]

        fused = simulate_batch(specs, traces, alpha=6.0, waitout=waitout,
                               strict=False, backend="jax", fuse=True)
        perspec = simulate_batch(specs, traces, alpha=6.0, waitout=waitout,
                                 strict=False, backend="jax", fuse=False)
        oracle = simulate_batch(specs, traces, alpha=6.0, waitout=waitout,
                                strict=False, backend="numpy")
        # the doomed spec actually died somewhere (else the fixture
        # tests nothing), and None-ness agrees across all three paths
        assert any(r is None for r in fused[1].ravel())
        for si in range(len(specs)):
            for c in range(cells):
                assert (fused[si, 0, c] is None) \
                    == (perspec[si, 0, c] is None) \
                    == (oracle[si, 0, c] is None)
                if fused[si, 0, c] is None:
                    continue
                assert_sim_parity(perspec[si, 0, c], fused[si, 0, c],
                                  exact=False)
                assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                                  exact=False)
        # sibling specs' lanes are fully healthy end to end
        for si in (0, 2):
            assert all(r is not None for r in fused[si].ravel())
    finally:
        unregister_fragile_gc()


def test_grid_new_kernels_fuse_and_match():
    """Scenario-sweep baselines (dc-gc, sb-gc) bucket on their fused
    ``s`` — one compile per (scheme, C) bucket — and match the numpy
    oracle through the vmapped scan."""
    from repro.core import cache_stats, clear_runner_cache

    n, rounds, cells = 12, 16, 2
    traces = _traces(n, rounds, cells, seed0=90)
    specs = (
        [("dc-gc", {"C": 4, "s": s}) for s in (0, 1, 2)]
        + [("sb-gc", {"C": 3, "s": s}) for s in (1, 2, 3)]
    )
    plan = grid_plan(specs, traces)
    assert plan["fallback"] == [] and plan["infeasible"] == []
    assert len(plan["buckets"]) == 2
    assert all(b["fused"] == ["s"] for b in plan["buckets"])
    clear_runner_cache()
    fused = simulate_batch(specs, traces, alpha=6.0, backend="jax",
                           fuse=True)
    assert cache_stats()["compiles"] == 2
    oracle = simulate_batch(specs, traces, alpha=6.0, backend="numpy")
    for si in range(len(specs)):
        for c in range(cells):
            assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                              exact=False)


def test_grid_fused_heterogeneous_alpha():
    """A per-worker (n,) alpha vector broadcasts through the stacked
    fused scalars: fused == numpy oracle under heterogeneous load
    slopes."""
    from repro.core import LambdaTraceGenerator

    n, rounds, cells = 12, 14, 2
    gen = LambdaTraceGenerator(n=n, seed=7, hetero=0.4)
    alpha = gen.worker_alpha()
    traces = np.stack([
        LambdaTraceGenerator(n=n, seed=7 + k, hetero=0.4,
                             speed_seed=9).sample_delays(rounds)
        for k in range(cells)
    ])
    specs = [("gc", {"s": s, "prefer_rep": False}) for s in (3, 4, 5)] \
        + [("dc-gc", {"C": 3, "s": 2})]
    fused = simulate_batch(specs, traces, alpha=alpha, backend="jax",
                           fuse=True)
    oracle = simulate_batch(specs, traces, alpha=alpha, backend="numpy")
    for si in range(len(specs)):
        for c in range(cells):
            assert_sim_parity(oracle[si, 0, c], fused[si, 0, c],
                              exact=False)


# (the REPRO_GRID_FUSE toggle/parser matrix lives in
# tests/test_runner_cache.py::test_grid_fuse_env_parser)


@pytest.mark.slow
@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_grid_fused_large_n_pallas_path(waitout):
    """n = 128 crosses the Pallas gate-window threshold inside the
    vmapped scan: the reshape-to-cells spec fold must leave every
    verdict untouched."""
    n, rounds, cells = 128, 16, 2
    traces = _traces(n, rounds, cells, seed0=50)
    specs = [("m-sgc", dict(B=2, W=3, lam=14)),
             ("m-sgc", dict(B=2, W=3, lam=20)),
             ("sr-sgc", dict(B=1, W=2, lam=11)),
             ("gc", dict(s=7))]
    fused = simulate_batch(specs, traces, alpha=6.0, waitout=waitout,
                           backend="jax", fuse=True)
    for si, (name, kw) in enumerate(specs):
        T = make_scheme(name, n, 1, **dict(kw)).T
        J = rounds - T
        for c in range(cells):
            ref = simulate_fast(make_scheme(name, n, J, **dict(kw)),
                                traces[c], alpha=6.0, J=J, waitout=waitout)
            assert_sim_parity(ref, fused[si, 0, c], exact=False)
