"""Unit tests for the ``core.backend`` shim: functional updates, the
``scan``/``jit`` staging hooks (with their numpy Python-loop
fallbacks), and the segment/argsort helpers, on every registered
backend."""

import numpy as np
import pytest

from repro.core.backend import get_backend, xp_of

BACKENDS = ["numpy", "jax"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_at_set_semantics(backend):
    bk = get_backend(backend)
    a = bk.xp.zeros((2, 3), dtype=bool)
    b = bk.at_set(a, (0, 1), True)
    c = bk.at_set(b, (slice(None), 2), True)
    assert np.asarray(c).tolist() == [
        [False, True, True],
        [False, False, True],
    ]


@pytest.mark.parametrize("backend", BACKENDS)
def test_at_or_bool_semantics(backend):
    """OR-update on bool arrays: True never reverts to False, False
    stays False unless or-ed with True (the single-scatter jax path
    must match numpy's ``|=`` exactly)."""
    bk = get_backend(backend)
    a = bk.xp.zeros((2, 3), dtype=bool)
    a = bk.at_set(a, (0, 0), True)
    val = bk.xp.asarray(np.array([[True, False, False],
                                  [False, True, False]]))
    out = bk.at_or(a, (slice(None), slice(None)), val)
    assert np.asarray(out).tolist() == [
        [True, False, False],
        [False, True, False],
    ]
    # or-ing False is a no-op on set bits
    out = bk.at_or(out, (slice(None), 0), False)
    assert np.asarray(out)[0, 0]


@pytest.mark.parametrize("backend", BACKENDS)
def test_at_or_int_semantics(backend):
    bk = get_backend(backend)
    a = bk.xp.asarray(np.array([[1, 2], [4, 8]], dtype=np.int64))
    out = bk.at_or(a, (slice(None), 0), 2)
    assert np.asarray(out).tolist() == [[3, 2], [6, 8]]


def test_jax_at_helpers_do_not_mutate():
    bk = get_backend("jax")
    a = bk.xp.zeros((2, 3), dtype=bool)
    b = bk.at_set(a, (0, 1), True)
    assert not bool(a[0, 1]) and bool(b[0, 1])
    c = bk.at_or(b, (slice(None), 2), True)
    assert not bool(b[0, 2]) and bool(c[0, 2])


@pytest.mark.parametrize("backend", BACKENDS)
def test_scan_matches_python_loop(backend):
    """The scan hook follows the ``lax.scan`` contract: carry
    threading, ``(t, x)`` tuple xs, stacked pytree ys."""
    bk = get_backend(backend)
    xp = bk.xp
    ts = xp.arange(1, 6)
    xs = xp.asarray(np.arange(10.0).reshape(5, 2))

    def f(carry, tx):
        t, x = tx
        carry = carry + x.sum() * t
        return carry, (carry, x * 2)

    carry, (ys, doubled) = bk.scan(f, xp.asarray(0.0), (ts, xs))
    expect = 0.0
    rows = []
    for t in range(1, 6):
        expect += (2 * (t - 1) + (2 * (t - 1) + 1)) * t
        rows.append(expect)
    assert np.isclose(float(carry), expect)
    assert np.allclose(np.asarray(ys), rows)
    assert np.allclose(np.asarray(doubled), np.arange(10.0).reshape(5, 2) * 2)


@pytest.mark.parametrize("backend", BACKENDS)
def test_jit_hook_runs(backend):
    bk = get_backend(backend)

    def f(x):
        return x * 2 + 1

    g = bk.jit(f)
    assert np.allclose(np.asarray(g(bk.xp.arange(3.0))), [1.0, 3.0, 5.0])


@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_sum(backend):
    bk = get_backend(backend)
    data = bk.xp.asarray(np.array([1.0, 2.0, 3.0, 4.0]))
    ids = bk.xp.asarray(np.array([0, 2, 0, 2]))
    out = bk.segment_sum(data, ids, 3)
    assert np.allclose(np.asarray(out), [4.0, 0.0, 6.0])


@pytest.mark.parametrize("backend", BACKENDS)
def test_argsort_stable(backend):
    bk = get_backend(backend)
    arr = bk.xp.asarray(np.array([[2.0, 1.0, 1.0, 0.5]]))
    order = bk.argsort_stable(arr, axis=1)
    assert np.asarray(order).tolist() == [[3, 1, 2, 0]]


@pytest.mark.parametrize("backend", BACKENDS)
def test_where_and_lax(backend):
    bk = get_backend(backend)
    out = bk.where(bk.xp.asarray(np.array([True, False])), 1.0, 2.0)
    assert np.allclose(np.asarray(out), [1.0, 2.0])
    if backend == "numpy":
        assert bk.lax is None
        assert bk.concrete
    else:
        assert bk.lax is not None
        assert not bk.concrete


def test_xp_of_dispatch():
    assert xp_of(np.zeros(3)) is np
    bk = get_backend("jax")
    assert xp_of(bk.xp.zeros(3)) is bk.xp


@pytest.mark.parametrize("backend", BACKENDS)
def test_vmap_hook(backend):
    """The vmap hook maps a pytree-returning fn over a leading batch
    axis, with per-arg in_axes (None = broadcast) — the numpy
    Python-loop fallback must match jax.vmap semantics."""
    bk = get_backend(backend)
    xp = bk.xp
    A = np.arange(12.0).reshape(3, 4)
    b = np.array([1.0, 2.0, 3.0])

    def f(a, s, c):
        return {"sum": a.sum() + s, "prod": a * c}

    out = bk.vmap(f, in_axes=(0, 0, None))(
        xp.asarray(A), xp.asarray(b), xp.asarray(2.0)
    )
    assert np.allclose(np.asarray(out["sum"]), A.sum(axis=1) + b)
    assert np.allclose(np.asarray(out["prod"]), A * 2.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_vmap_hook_tuple_outputs(backend):
    bk = get_backend(backend)
    xp = bk.xp
    A = np.arange(6.0).reshape(2, 3)
    out = bk.vmap(lambda a: (a.min(), a + 1.0))(xp.asarray(A))
    assert np.allclose(np.asarray(out[0]), A.min(axis=1))
    assert np.allclose(np.asarray(out[1]), A + 1.0)


def test_unknown_repro_backend_raises_at_import():
    """``REPRO_BACKEND`` naming no backend is an error, not a silent
    fallback to numpy."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, REPRO_BACKEND="nope", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", "import repro.core.backend"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "unknown backend 'nope'" in proc.stderr
