"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

Interpret-mode tests cannot see what Mosaic refuses: a refused kernel
aborts the whole process.  These tests lower the Pallas gate kernels
and the jitted lockstep runners for one chip of a described ``v5e:2x2``
topology at the paper's n=256, and check that the device program holds
the native kernel (``tpu_custom_call``).  The topology is described
inside a fixture, never at import, because only one process may load
the TPU library at a time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import get_backend, make_kernel, make_scheme
from repro.core.batch import _build_jax_grid_runner
from repro.kernels.gate_window import ops

N = 256

#: Every ``buffer_stats`` launch (cells, buffer rows, B) that App.-J
#: ``select_parameters_fast`` makes at n=256 over one probe trace with
#: the default sr-sgc, m-sgc and gc grids (recorded by wrapping the
#: kernel call during selection).  cells > 1 are grid-fused buckets
#: with the spec axis folded in.  The window kernel is not called on
#: this path.
SELECTION_BUFFER_LAUNCHES = [
    (1, 1, 1), (1, 1, 2), (1, 2, 1), (1, 2, 2), (1, 2, 3), (1, 3, 1),
    (1, 3, 3), (1, 3, 4), (1, 4, 2), (1, 6, 2), (1, 6, 3), (1, 9, 3),
    (20, 3, 1), (20, 6, 2), (20, 9, 3), (23, 2, 1), (23, 4, 2),
    (23, 6, 3), (24, 1, 1), (24, 2, 2), (24, 3, 3), (33, 1, 1),
    (33, 1, 2), (33, 2, 1), (33, 2, 2), (33, 2, 3), (33, 3, 1),
    (33, 3, 4),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    from repro.launch.cache import confine_tpu_logs

    confine_tpu_logs()      # before describing loads the TPU library
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """Described-chip executables cannot be read back without a chip;
    keep them out of any persistent compilation cache."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("which", ["window", "buffer"])
@pytest.mark.parametrize("shape,B", [
    ((8, 3, N), 1),        # B < W: the pair loop is compiled
    ((8, 4, N), 2),
    ((8, 1, N), 1),        # one round: no pair term
    ((64, 8, 3, N), 1),    # spec axis folded into 512 cells
])
def test_gate_kernel_compiles_natively(one_chip, which, shape, B):
    call = {"window": ops.window_stats, "buffer": ops.buffer_stats}[which]
    x = jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(lambda a: call(a, B), x)


@pytest.mark.parametrize("cells,kh,B", SELECTION_BUFFER_LAUNCHES)
def test_selection_buffer_launch_compiles_natively(one_chip, cells, kh, B):
    """A Mosaic refusal aborts the process, so every buffer shape the
    App.-J grids launch at the paper's n is compiled here first."""
    x = jax.ShapeDtypeStruct((cells, kh, N), jnp.bool_, sharding=one_chip)
    text = _compiled_text(lambda a: ops.buffer_stats(a, B), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("which", ["window", "buffer"])
def test_gate_kernel_vmapped_spec_axis_compiles_natively(one_chip, which):
    """The grid-fused engine's ``vmap`` over stacked specs lowers
    through the fold rule to one native launch."""
    call = {"window": ops.window_stats, "buffer": ops.buffer_stats}[which]
    x = jax.ShapeDtypeStruct((64, 8, 3, N), jnp.bool_, sharding=one_chip)
    text = _compiled_text(jax.vmap(lambda a: call(a, 1)), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,params,windowed", [
    # GC's per-round gate is a count with an analytic drop solver: no
    # window pass, so no kernel in its program
    ("gc", {"s": 15}, False),
    ("sr-sgc", {"B": 2, "W": 3, "lam": 23}, True),
    ("m-sgc", {"B": 2, "W": 3, "lam": 27}, True),
])
def test_lockstep_runner_compiles_with_native_gate(one_chip, name, params,
                                                   windowed):
    """The Table-1 specs' jitted scans at n=256, each a one-lane bucket
    as ``simulate_lockstep(backend="jax")`` runs it, x64 timing math
    included, compile for the chip; windowed gates call the native
    Pallas kernel."""
    J, cells = 32, 8
    sch = make_scheme(name, N, J, **params)
    kern = make_kernel(sch, get_backend("numpy"))
    fused_names = tuple(kern.fused_params)
    runner, _ = _build_jax_grid_runner(sch, J, "selective", fused_names)
    with jax.enable_x64(True):
        def lane(value):
            return jax.ShapeDtypeStruct((1,), np.asarray(value).dtype,
                                        sharding=one_chip)

        traces = jax.ShapeDtypeStruct((cells, J + sch.T, N), jnp.float64,
                                      sharding=one_chip)
        scalars = kern.fused_scalars(sch)
        text = runner.lower(
            lane(1.0), lane(8.0), lane(sch.normalized_load),
            {k: lane(scalars[k]) for k in fused_names}, traces,
        ).compile().as_text()
    assert ("tpu_custom_call" in text) == windowed


def test_grid_fused_runner_compiles_with_native_gate(one_chip):
    """An M-SGC lam sweep fused into one bucket (64 specs x 8 traces):
    the vmapped scan folds the spec axis into one native kernel launch
    per gate call."""
    from repro.core.batch import _build_jax_grid_runner

    J, cells, S = 32, 8, 64
    sch = make_scheme("m-sgc", N, J, B=2, W=3, lam=27)
    runner, _ = _build_jax_grid_runner(sch, J, "selective", ("lam",))
    with jax.enable_x64(True):
        def stacked(dtype):
            return jax.ShapeDtypeStruct((S,), dtype, sharding=one_chip)

        traces = jax.ShapeDtypeStruct((cells, J + sch.T, N), jnp.float64,
                                      sharding=one_chip)
        text = runner.lower(
            stacked(jnp.float64), stacked(jnp.float64),
            stacked(jnp.float64), {"lam": stacked(jnp.int64)}, traces,
        ).compile().as_text()
    assert "tpu_custom_call" in text


def test_pallas_attention_layer_compiles_natively(one_chip):
    """``models.layers.multihead_attention(use_pallas=True)`` picks the
    native flash kernel when lowered for the chip (qwen2-0.5b heads:
    GQA 14/2, head_dim 64)."""
    from repro.models.layers import multihead_attention

    q = jax.ShapeDtypeStruct((1, 14, 256, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: multihead_attention(q, k, v, causal=True,
                                            use_pallas=True), q, kv, kv)
    assert "tpu_custom_call" in text
