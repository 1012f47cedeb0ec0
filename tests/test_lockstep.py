"""Differential tests for the lockstep batch engine (``core.kernel`` +
``simulate_lockstep``).

Batched kernel stepping over a cells axis must reproduce the per-run
scalar path (``simulate_fast``, itself bit-for-bit vs the legacy
descriptor-path ``simulate`` — see ``tests/test_batch_engine.py``)
EXACTLY: every ``SimResult`` field, across all schemes, both wait-out
modes, ragged grids (mixed specs with different ``T``/``J``), and
``strict=False`` infeasible-cell handling.  Also pins the seed-axis
dedup contract of ``simulate_batch`` and the backend shim.
"""

import numpy as np
import pytest

from repro.core import (
    GilbertElliotSource,
    NoCodingScheme,
    available_backends,
    get_backend,
    make_scheme,
    register_scheme,
    simulate,
    simulate_batch,
    simulate_fast,
    simulate_lockstep,
    use_backend,
)
from repro.core.schemes import _SCHEME_FACTORIES
from repro.core.testing import (
    SeededUncodedScheme,
    assert_sim_parity,
    register_testing_schemes,
    unregister_testing_schemes,
)

GE = dict(p_ns=0.08, p_sn=0.6, slow_factor=6.0)

CONFIGS = [
    ("gc", dict(s=3)),                     # 4 | 12 -> GC-Rep
    ("gc", dict(s=3, prefer_rep=False)),   # general code
    ("gc", dict(s=4)),                     # 5 does not divide 12 -> general
    ("sr-sgc", dict(B=1, W=2, lam=3)),
    ("sr-sgc", dict(B=2, W=3, lam=5)),
    ("sr-sgc", dict(B=1, W=4, lam=4)),     # W >= B+3: multi-row gate
                                           # buffers inside WindowwiseOr
    ("m-sgc", dict(B=1, W=2, lam=3)),
    ("m-sgc", dict(B=2, W=3, lam=5)),
    ("m-sgc", dict(B=1, W=3, lam=12)),     # lam == n (Remark 3.2, no D2)
    ("dc-gc", dict(C=3, s=2)),             # dynamic clustering (window-2
    ("dc-gc", dict(C=4, s=1)),             #  gate member, tight s)
    ("sb-gc", dict(C=3, s=2)),             # stochastic blocks (seed 0)
    ("sb-gc", dict(C=2, s=3)),
    ("uncoded", {}),
]


def _assert_identical(ra, rb):
    """Bit-for-bit on the numpy backend; with jax active (e.g.
    ``REPRO_BACKEND=jax``) the bool/int bookkeeping must still be exact
    while float loads/runtimes are held to the allclose contract."""
    assert_sim_parity(ra, rb, exact=get_backend().name == "numpy")


def _traces(n, rounds, num, seed0=0):
    return np.stack([
        GilbertElliotSource(n=n, seed=seed0 + k, **GE).sample_delays(rounds)
        for k in range(num)
    ])


@pytest.mark.parametrize("name,kw", CONFIGS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(CONFIGS)])
@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_lockstep_matches_fast_bitforbit(name, kw, waitout):
    """Every cell of a lockstep run == the scalar fast run on that
    trace (which == the legacy oracle, test_batch_engine)."""
    n, J, cells = 12, 20, 3
    traces = _traces(n, 26, cells, seed0=20)
    rl = simulate_lockstep(name, kw, traces, alpha=6.0, J=J, waitout=waitout)
    assert len(rl) == cells
    for c in range(cells):
        ref = simulate_fast(make_scheme(name, n, J, **dict(kw)), traces[c],
                            alpha=6.0, J=J, waitout=waitout)
        _assert_identical(ref, rl[c])


def test_lockstep_matches_legacy_direct():
    """Belt and braces: one lockstep cell straight against the legacy
    descriptor-path simulate (not via simulate_fast)."""
    n, J = 12, 18
    traces = _traces(n, 24, 2, seed0=5)
    for name, kw in [("m-sgc", dict(B=2, W=3, lam=5)),
                     ("sr-sgc", dict(B=2, W=3, lam=5)),
                     ("dc-gc", dict(C=4, s=1)),
                     ("sb-gc", dict(C=3, s=1))]:
        rl = simulate_lockstep(name, kw, traces, alpha=6.0, J=J)
        for c in range(2):
            ref = simulate(make_scheme(name, n, J, **dict(kw)), traces[c],
                           alpha=6.0, J=J)
            _assert_identical(ref, rl[c])


@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_ragged_grid_mixed_specs(waitout):
    """simulate_batch over mixed specs with different T/J: each spec
    advances its own lockstep batch; every cell equals the scalar run
    with that spec's fitted J."""
    n, rounds = 12, 22
    specs = [
        ("gc", {"s": 3}),                   # T=0 -> J=22
        ("sr-sgc", {"B": 2, "W": 3, "lam": 5}),  # T=2 -> J=20
        ("m-sgc", {"B": 2, "W": 3, "lam": 5}),   # T=3 -> J=19
        ("dc-gc", {"C": 3, "s": 1}),        # T=0 -> J=22
        ("sb-gc", {"C": 4, "s": 1}),        # T=0 -> J=22
        ("uncoded", {}),                    # T=0 -> J=22
    ]
    traces = _traces(n, rounds, 2, seed0=40)
    grid = simulate_batch(specs, traces, alpha=6.0, waitout=waitout)
    assert grid.shape == (len(specs), 1, 2)
    for i, (name, params) in enumerate(specs):
        T = make_scheme(name, n, 1, **dict(params)).T
        J = rounds - T
        for c in range(2):
            res = grid[i, 0, c]
            assert res.rounds == J + T
            ref = simulate_fast(make_scheme(name, n, J, **dict(params)),
                                traces[c], alpha=6.0, J=J, waitout=waitout)
            _assert_identical(ref, res)


def test_ragged_grid_strict_false_infeasible_cells():
    n = 12
    specs = [
        ("sr-sgc", {"B": 2, "W": 4, "lam": 3}),  # B does not divide W-1
        ("gc", {"s": 3}),
        ("m-sgc", {"B": 3, "W": 2, "lam": 2}),   # needs B < W
    ]
    traces = _traces(n, 16, 2, seed0=60)
    grid = simulate_batch(specs, traces, alpha=6.0, strict=False)
    assert all(r is None for r in grid[0].ravel())
    assert all(r is not None for r in grid[1].ravel())
    assert all(r is None for r in grid[2].ravel())
    with pytest.raises(ValueError):
        simulate_batch(specs, traces, alpha=6.0, strict=True)


def test_seed_axis_deduplicated():
    """Load-only results are seed-invariant: the engine must run the
    trace axis once and broadcast the SimResult objects across seeds."""
    n = 12
    specs = [("m-sgc", {"B": 1, "W": 2, "lam": 3}), ("gc", {"s": 3})]
    traces = _traces(n, 16, 2, seed0=80)
    grid = simulate_batch(specs, traces, seeds=(0, 5, 9), alpha=6.0)
    assert grid.shape == (2, 3, 2)
    for i in range(len(specs)):
        for t in range(2):
            assert grid[i, 1, t] is grid[i, 0, t]
            assert grid[i, 2, t] is grid[i, 0, t]


@pytest.fixture
def _seeded_scheme():
    """The registered seed-sensitive fixture (``core.testing``), with a
    kernel-LESS variant forcing the per-cell fallback path."""
    register_testing_schemes()
    register_scheme(
        "seeded-uncoded-nokernel",
        lambda n, J, **kw: SeededUncodedScheme(n, J, **kw),
    )
    yield
    unregister_testing_schemes()
    _SCHEME_FACTORIES.pop("seeded-uncoded-nokernel", None)


def test_seed_sensitive_schemes_fan_out(_seeded_scheme):
    n = 12
    traces = _traces(n, 10, 2, seed0=90)
    # no kernel registered under this name: per-cell fallback path
    grid = simulate_batch([("seeded-uncoded-nokernel", {})], traces,
                          seeds=(0, 1), alpha=6.0)
    assert grid[0, 0, 0] is not grid[0, 1, 0]
    # seed changes the load, hence the runtime
    assert grid[0, 0, 0].normalized_load != grid[0, 1, 0].normalized_load
    assert grid[0, 0, 0].total_time != grid[0, 1, 0].total_time
    # and each cell still equals its scalar run
    ref = simulate_fast(SeededUncodedScheme(n, 10, seed=1), traces[1],
                        alpha=6.0, J=10)
    _assert_identical(ref, grid[0, 1, 1])


@pytest.mark.parametrize(
    "backend",
    ["numpy", "jax"],
)
def test_seed_fan_out_at_scale(_seeded_scheme, backend):
    """ROADMAP item: the seed axis fans out correctly on a
    (specs x 8 seeds x traces) grid, through the LOCKSTEP path (the
    fixture kernel is registered), under both backends."""
    n, num_traces = 12, 3
    seeds = tuple(range(8))
    traces = _traces(n, 12, num_traces, seed0=95)
    specs = [("seeded-uncoded", {}), ("gc", {"s": 3})]
    grid = simulate_batch(specs, traces, seeds=seeds, alpha=6.0,
                          backend=backend)
    assert grid.shape == (2, 8, num_traces)
    # seed-sensitive spec: distinct objects per seed, loads cycling
    # with seed % 3, and runtimes moving with the load
    for ki, seed in enumerate(seeds):
        for ti in range(num_traces):
            r = grid[0, ki, ti]
            assert r.normalized_load == (1.0 + 0.5 * (seed % 3)) / n
            ref = simulate_fast(SeededUncodedScheme(n, 12, seed=seed),
                                traces[ti], alpha=6.0, J=12)
            with use_backend(backend):
                _assert_identical(ref, r)
    assert grid[0, 0, 0].total_time != grid[0, 1, 0].total_time
    # seed-INsensitive spec on the same grid: broadcast, not fanned
    for ki in range(1, len(seeds)):
        for ti in range(num_traces):
            assert grid[1, ki, ti] is grid[1, 0, ti]


def test_gate_kernel_windowwise_or_buffer_violation():
    """Inside a WindowwiseOr, committed rows may violate one arm (the
    window was admitted through another): the analytic minimal-drop
    solver must not credit that arm.  Regression for a divergence
    between GateKernel and the scalar ConformanceGate."""
    from repro.core.kernel import GateKernel
    from repro.core.straggler import (
        BurstyModel,
        ConformanceGate,
        PerRoundModel,
        WindowwiseOr,
    )

    n = 6
    model = WindowwiseOr((BurstyModel(2, 4, 4), PerRoundModel(2)), 4)
    # worker 0 straggles twice, 2 >= B rounds apart: each row is
    # PerRound-admissible but the Bursty arm can never admit the window
    rows = [np.eye(1, n, 0, dtype=bool)[0], np.zeros(n, bool),
            np.eye(1, n, 0, dtype=bool)[0]]
    cand = np.array([0, 1, 1, 1, 0, 0], dtype=bool)
    cost = np.arange(n, dtype=float) + 1.0

    scalar = ConformanceGate(model, n)
    for r in rows:
        assert scalar.admit(r.copy())
    eff_s, waited_s = scalar.admit_partial(cand.copy(), cost)

    gk = GateKernel(model, n)
    gs = gk.init_state(1)
    for r in rows:
        gs, eff, _ = gk.admit_partial(gs, r[None], cost[None],
                                      np.array([bool(r.any())]))
        assert (eff[0] == r).all()
    gs, eff_b, waited_b = gk.admit_partial(gs, cand[None], cost[None],
                                           np.array([True]))
    assert (eff_b[0] == eff_s).all()
    assert sorted(np.flatnonzero(waited_b[0]).tolist()) == sorted(waited_s)


def test_registered_scheme_extension_api():
    """Extension-API contract for new scheme reproductions: the spec
    probe must accept constructors that validate J (probe at trace
    length, not J=1), register_kernel normalizes names like
    register_scheme, and a kernel-side seed_sensitive flag fans the
    seed axis out."""
    from repro.core import has_kernel
    from repro.core.kernel import (
        _KERNELS,
        UncodedKernel,
        kernel_seed_sensitive,
        register_kernel,
    )

    class JPicky(NoCodingScheme):
        name = "j-picky"

        def __init__(self, n, J, *, seed=0):
            if J < 5:
                raise ValueError("J must be >= 5")
            super().__init__(n, J)

    class JPickyKernel(UncodedKernel):
        name = "j-picky"
        seed_sensitive = True

    register_scheme("J_Picky", lambda n, J, **kw: JPicky(n, J, **kw))
    register_kernel("J_PICKY", JPickyKernel)  # name gets normalized
    try:
        assert has_kernel("j-picky") and has_kernel("J_Picky")
        assert kernel_seed_sensitive("j-picky")
        traces = np.stack([
            GilbertElliotSource(n=8, seed=k, p_ns=0.0).sample_delays(12)
            for k in range(2)
        ])
        grid = simulate_batch([("j-picky", {})], traces, seeds=(0, 1))
        assert grid[0, 0, 0] is not None          # J=1 probe would raise
        assert grid[0, 0, 0] is not grid[0, 1, 0]  # seed axis fanned out
    finally:
        _SCHEME_FACTORIES.pop("j-picky", None)
        _KERNELS.pop("j-picky", None)


def test_lockstep_rejects_short_trace():
    with pytest.raises(ValueError):
        simulate_lockstep("m-sgc", dict(B=2, W=3, lam=5),
                          _traces(12, 3, 1), J=10)


def test_backend_shim():
    import os

    expected = os.environ.get("REPRO_BACKEND", "").strip().lower() or "numpy"
    assert get_backend().name == expected
    assert "numpy" in available_backends()
    with use_backend("numpy") as bk:
        a = bk.xp.zeros((2, 3), dtype=bool)
        a = bk.at_set(a, (0, 1), True)
        a = bk.at_or(a, (slice(None), 2), True)
        assert a.tolist() == [[False, True, True], [False, False, True]]
    assert get_backend().name == expected


def test_jax_backend_functional_updates():
    bk = get_backend("jax")
    a = bk.xp.zeros((2, 3), dtype=bool)
    b = bk.at_set(a, (0, 1), True)
    assert not bool(a[0, 1]) and bool(b[0, 1])  # non-mutating
    c = bk.at_or(b, (slice(None), 2), True)
    assert c.tolist() == [[False, True, True], [False, False, True]]
