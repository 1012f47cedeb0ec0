"""Differential tests for the Pallas ``gate_window`` kernels
(interpret mode on CPU): ``ops`` == ``ref`` == the numpy straggler
models, and the jax suffix/buffer dispatch in ``core.straggler``
routes through them at n >= 128 with unchanged verdicts."""

import jax
import numpy as np
import pytest

from repro.core.straggler import (
    PALLAS_WINDOW_MIN_N,
    ArbitraryModel,
    BurstyModel,
    PerRoundModel,
    _buffer_stats,
    _window_stats,
)
from repro.kernels.gate_window import ops, ref


def _rand_windows(shapes, p=0.25, seed=0):
    rng = np.random.default_rng(seed)
    for cells, W, n in shapes:
        yield rng.random((cells, W, n)) < p


SHAPES = [(3, 4, 200), (64, 3, 256), (7, 1, 130), (5, 2, 128), (17, 4, 384)]


@pytest.mark.parametrize("B", [1, 2, 3])
def test_window_stats_ops_vs_ref(B):
    import jax.numpy as jnp

    for win in _rand_windows(SHAPES, seed=B):
        w = jnp.asarray(win)
        got = ops.window_stats(w, B)
        want = ref.window_stats(w, B)
        for g, r in zip(got, want):
            assert g.shape == r.shape == (win.shape[0],)
            assert (np.asarray(g) == np.asarray(r)).all()
        # numpy cross-check of the verdict-level stats
        assert (np.asarray(got[0]) == win.any(axis=1).sum(axis=1)).all()
        assert (
            np.asarray(got[1])
            == win.sum(axis=1).max(axis=1, initial=0)
        ).all()
        assert (
            np.asarray(got[2])
            == win.sum(axis=2).max(axis=1, initial=0)
        ).all()


@pytest.mark.parametrize("B", [1, 2])
def test_buffer_stats_ops_vs_ref(B):
    import jax.numpy as jnp

    for buf in _rand_windows(SHAPES, seed=10 + B):
        b = jnp.asarray(buf)
        got = ops.buffer_stats(b, B)
        want = ref.buffer_stats(b, B)
        for g, r in zip(got, want):
            assert g.shape == r.shape
            assert (np.asarray(g) == np.asarray(r)).all()
        # numpy cross-check
        assert (np.asarray(got[0]) == buf.any(axis=1)).all()
        assert (np.asarray(got[1]) == buf.sum(axis=1)).all()


def test_suffix_dispatch_routes_through_kernel_and_matches_numpy():
    """At n >= PALLAS_WINDOW_MIN_N the jax suffix checks use the Pallas
    kernel; verdicts must equal the numpy models bit-for-bit."""
    import jax.numpy as jnp

    n = max(PALLAS_WINDOW_MIN_N, 128)
    rng = np.random.default_rng(3)
    win = rng.random((9, 3, n)) < 0.2
    for model in (
        BurstyModel(2, 3, n // 4),
        ArbitraryModel(2, 3, n // 4),
        PerRoundModel(n // 8),
    ):
        want = model.suffix_ok_batch(win)
        got = np.asarray(model.suffix_ok_batch(jnp.asarray(win)))
        assert (got == want).all(), type(model).__name__


def test_window_and_buffer_stats_jnp_fallback_below_threshold():
    """Small n stays on the plain jnp reduction — same results."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    win = rng.random((5, 3, 16)) < 0.3
    d, wm, rm, pb = _window_stats(jnp.asarray(win), 2)
    assert (np.asarray(d) == win.any(axis=1).sum(axis=1)).all()
    ba, bc, md, pr = _buffer_stats(jnp.asarray(win), 2)
    assert (np.asarray(ba) == win.any(axis=1)).all()
    assert (np.asarray(bc) == win.sum(axis=1)).all()
    assert (np.asarray(md) == win[:, :2].any(axis=1)).all()


def test_stats_inside_jit_and_scan():
    """The interpret-mode kernels must stage cleanly under jit + scan
    (how the lockstep engine consumes them)."""
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(5)
    wins = jnp.asarray(rng.random((4, 6, 2, 160)) < 0.2)

    @jax.jit
    def run(ws):
        def body(carry, w):
            d, _, _, _ = ops.window_stats(w, 1)
            return carry + d.sum(), d

        return lax.scan(body, jnp.int32(0), ws)

    tot, ds = run(wins)
    want = np.asarray(wins).any(axis=2).sum(axis=2)
    assert (np.asarray(ds) == want).all()
    assert int(tot) == int(want.sum())
