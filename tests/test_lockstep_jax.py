"""Parity suite for the device-resident (jitted ``lax.scan``) lockstep
path: ``simulate_lockstep(..., backend="jax")`` against the numpy
oracle, per the allclose contract — EXACT on the bool/int bookkeeping
(done rounds, dead flags, waitout counts, effective gate patterns),
allclose on float loads/runtimes — across every scheme, both wait-out
modes, ragged grids, ``strict=False``, and the Pallas gate path at
n >= 128."""

import numpy as np
import pytest

from repro.core import (
    GilbertElliotSource,
    make_scheme,
    simulate_batch,
    simulate_fast,
    simulate_lockstep,
)
from repro.core.batch import _JAX_RUNNERS
from repro.core.testing import assert_sim_parity

GE = dict(p_ns=0.08, p_sn=0.6, slow_factor=6.0)

CONFIGS = [
    ("gc", dict(s=3)),
    ("gc", dict(s=3, prefer_rep=False)),
    ("gc", dict(s=4)),
    ("sr-sgc", dict(B=1, W=2, lam=3)),
    ("sr-sgc", dict(B=2, W=3, lam=5)),
    ("sr-sgc", dict(B=1, W=4, lam=4)),
    ("m-sgc", dict(B=1, W=2, lam=3)),
    ("m-sgc", dict(B=2, W=3, lam=5)),
    ("m-sgc", dict(B=1, W=3, lam=12)),
    ("uncoded", {}),
]


def _traces(n, rounds, num, seed0=0):
    return np.stack([
        GilbertElliotSource(n=n, seed=seed0 + k, **GE).sample_delays(rounds)
        for k in range(num)
    ])


def _assert_allclose_parity(ref, got):
    assert_sim_parity(ref, got, exact=False)


@pytest.mark.parametrize("name,kw", CONFIGS,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(CONFIGS)])
@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_jax_lockstep_matches_numpy_oracle(name, kw, waitout):
    n, J, cells = 12, 20, 3
    traces = _traces(n, 26, cells, seed0=20)
    got = simulate_lockstep(name, kw, traces, alpha=6.0, J=J,
                            waitout=waitout, backend="jax")
    assert len(got) == cells
    for c in range(cells):
        ref = simulate_fast(make_scheme(name, n, J, **dict(kw)), traces[c],
                            alpha=6.0, J=J, waitout=waitout)
        _assert_allclose_parity(ref, got[c])


@pytest.mark.parametrize("waitout", ["selective", "all"])
def test_jax_large_n_pallas_gate_path(waitout):
    """n = 128 crosses the Pallas gate-window threshold: the kernelized
    suffix/buffer reductions must leave the verdicts untouched."""
    n, J, cells = 128, 12, 2
    traces = _traces(n, 16, cells, seed0=50)
    for name, kw in [("m-sgc", dict(B=2, W=3, lam=14)),
                     ("sr-sgc", dict(B=1, W=2, lam=11)),
                     ("gc", dict(s=7))]:
        got = simulate_lockstep(name, kw, traces, alpha=6.0, J=J,
                                waitout=waitout, backend="jax")
        for c in range(cells):
            ref = simulate_fast(make_scheme(name, n, J, **dict(kw)),
                                traces[c], alpha=6.0, J=J, waitout=waitout)
            _assert_allclose_parity(ref, got[c])


def test_jax_ragged_grid_and_strict_false():
    """simulate_batch(backend="jax") over mixed specs with different
    T/J, including an infeasible spec under strict=False."""
    n, rounds = 12, 22
    specs = [
        ("gc", {"s": 3}),
        ("sr-sgc", {"B": 2, "W": 4, "lam": 3}),   # B does not divide W-1
        ("m-sgc", {"B": 2, "W": 3, "lam": 5}),
        ("uncoded", {}),
    ]
    traces = _traces(n, rounds, 2, seed0=40)
    grid = simulate_batch(specs, traces, alpha=6.0, strict=False,
                          backend="jax")
    assert all(r is None for r in grid[1].ravel())
    for i in (0, 2, 3):
        name, params = specs[i]
        T = make_scheme(name, n, 1, **dict(params)).T
        J = rounds - T
        for c in range(2):
            ref = simulate_fast(make_scheme(name, n, J, **dict(params)),
                                traces[c], alpha=6.0, J=J)
            _assert_allclose_parity(ref, grid[i, 0, c])


def test_jax_runner_cache_reuse():
    """Same spec key -> the staged runner is built once and reused
    across calls (what makes repeated Monte-Carlo waves cheap)."""
    from repro.core import cache_stats

    n = 12
    traces = _traces(n, 16, 2, seed0=70)
    simulate_lockstep("gc", {"s": 3}, traces, alpha=6.0, J=16,
                      backend="jax")
    size = len(_JAX_RUNNERS)
    hits = cache_stats()["hits"]
    simulate_lockstep("gc", {"s": 3}, _traces(n, 16, 2, seed0=80),
                      alpha=6.0, J=16, backend="jax")
    assert len(_JAX_RUNNERS) == size
    assert cache_stats()["hits"] == hits + 1


def test_runner_cache_cap_and_eviction(monkeypatch):
    """The FIFO cap is configurable via REPRO_RUNNER_CACHE_CAP and
    evictions / rebuilds show up on cache_stats()."""
    from repro.core import cache_stats, clear_runner_cache

    monkeypatch.setenv("REPRO_RUNNER_CACHE_CAP", "2")
    clear_runner_cache()
    n = 12
    traces = _traces(n, 12, 1, seed0=90)
    for J in (8, 10, 12):                # three distinct spec keys
        simulate_lockstep("gc", {"s": 3}, traces, alpha=6.0, J=J,
                          backend="jax")
    st = cache_stats()
    assert st["cap"] == 2 and st["size"] <= 2
    assert st["misses"] == 3 and st["compiles"] == 3
    assert st["evictions"] >= 1
    # the most recent key survived the FIFO -> pure hit
    simulate_lockstep("gc", {"s": 3}, traces, alpha=6.0, J=12,
                      backend="jax")
    assert cache_stats()["hits"] == st["hits"] + 1
    # the oldest was evicted -> rebuilds (a new miss + compile)
    simulate_lockstep("gc", {"s": 3}, traces, alpha=6.0, J=8,
                      backend="jax")
    st2 = cache_stats()
    assert st2["misses"] == 4 and st2["compiles"] == 4
    clear_runner_cache()


def test_jax_runner_cache_invalidated_on_reregistration():
    """Re-registering a scheme's kernel must change the runner key, so
    the extension API's register/unregister pattern never hits a stale
    compiled runner (or a stale 'unsupported' verdict)."""
    from repro.core.batch import _jax_runner_key
    from repro.core.kernel import _KERNELS, UncodedKernel, register_kernel
    from repro.core.schemes import _SCHEME_FACTORIES
    from repro.core.testing import (
        SeededUncodedScheme,
        register_testing_schemes,
        unregister_testing_schemes,
    )

    register_testing_schemes()
    try:
        scheme = SeededUncodedScheme(8, 4)
        key1 = _jax_runner_key(scheme, {}, 4, "selective", 0)

        class Replacement(UncodedKernel):
            name = scheme.name
            seed_sensitive = True

        register_kernel(scheme.name, Replacement)
        key2 = _jax_runner_key(scheme, {}, 4, "selective", 0)
        assert key1 != key2
    finally:
        unregister_testing_schemes()
        _SCHEME_FACTORIES.pop(SeededUncodedScheme.name, None)
        _KERNELS.pop(SeededUncodedScheme.name, None)


def test_unknown_backend_rejected():
    n = 12
    traces = _traces(n, 10, 1, seed0=85)
    with pytest.raises(ValueError, match="unknown backend"):
        simulate_lockstep("gc", {"s": 3}, traces, alpha=6.0, J=10,
                          backend="jaxx")
    with pytest.raises(ValueError, match="unknown backend"):
        simulate_batch([("gc", {"s": 3})], traces, alpha=6.0,
                       backend="nope")


def test_jax_unsupported_gate_falls_back_to_numpy():
    """A custom design model without vectorized/analytic members cannot
    stage; the jax entry point must transparently fall back to the
    numpy engine with identical results."""
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
        name = "odd-gate"

        def __init__(self, n, J, *, seed=0):
            super().__init__(n, J)
            self.design_model = OddModel()

    class OddKernel(UncodedKernel):
        name = "odd-gate"

    register_scheme("odd-gate", lambda n, J, **kw: OddScheme(n, J, **kw))
    register_kernel("odd-gate", OddKernel)
    try:
        traces = _traces(12, 10, 2, seed0=60)
        got = simulate_lockstep("odd-gate", {}, traces, alpha=6.0, J=10,
                                backend="jax")
        ref = simulate_lockstep("odd-gate", {}, traces, alpha=6.0, J=10,
                                backend="numpy")
        for a, b in zip(ref, got):
            assert a.total_time == b.total_time
            assert (a.effective_pattern == b.effective_pattern).all()
    finally:
        _SCHEME_FACTORIES.pop("odd-gate", None)
        _KERNELS.pop("odd-gate", None)
