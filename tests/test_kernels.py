"""Per-kernel allclose sweeps against the pure-jnp oracles.

All Pallas kernels run in ``interpret=True`` (this container is CPU;
TPU v5e is the compilation target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gc_coding import ops as gc_ops
from repro.kernels.gc_coding import ref as gc_ref
from repro.kernels.rmsnorm import ops as rn_ops
from repro.kernels.rmsnorm import ref as rn_ref

RNG = np.random.default_rng(42)


def randn(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# -- gc_coding --------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3, 16, 28])
@pytest.mark.parametrize("d", [128, 1000, 16384, 40000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_combine_sweep(k, d, dtype):
    parts = randn((k, d), dtype)
    w = randn((k,), jnp.float32)
    out = gc_ops.coded_combine(parts, w, interpret=True)
    ref = gc_ref.coded_combine(parts, w)
    assert out.shape == (d,) and out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_coded_combine_tree_matches_pytree_oracle():
    tree = {
        "wte": randn((5, 64, 32), jnp.float32),
        "bias": randn((5, 17), jnp.float32),
        "scalar": randn((5,), jnp.float32),
    }
    w = randn((5,), jnp.float32)
    out = gc_ops.coded_combine_tree(tree, w, interpret=True)
    ref = gc_ref.coded_combine_tree(tree, w)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        out,
        ref,
    )


def test_coded_combine_is_gc_decode():
    """End-to-end: kernel decodes a real (n,s)-GC encode."""
    from repro.core import GradientCode

    code = GradientCode(8, 3, seed=0)
    g = randn((8, 512), jnp.float32)  # chunk gradients
    ell = jnp.asarray(code.encode_matrix, jnp.float32) @ g
    surv = [0, 2, 3, 5, 7]
    beta = jnp.asarray(code.decode_vector(surv), jnp.float32)
    out = gc_ops.coded_combine(ell, beta, interpret=True)
    np.testing.assert_allclose(out, g.sum(0), rtol=1e-4, atol=1e-4)


# -- rmsnorm ----------------------------------------------------------------


@pytest.mark.parametrize(
    "shape", [(8, 256), (512, 1024), (2, 3, 896), (1, 8192), (130, 640)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = randn(shape, dtype)
    g = randn((shape[-1],), jnp.float32)
    out = rn_ops.rmsnorm(x, g, interpret=True)
    ref = rn_ref.rmsnorm(x, g)
    assert out.shape == x.shape and out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


# -- flash attention ----------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,dh",
    [
        (1, 4, 2, 256, 256, 64),
        (2, 8, 8, 128, 128, 32),
        (1, 8, 1, 128, 256, 64),   # MQA, cross lengths
        (1, 4, 4, 384, 384, 128),
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, dh, causal):
    q = randn((b, hq, sq, dh), jnp.float32)
    k = randn((b, hkv, sk, dh), jnp.float32)
    v = randn((b, hkv, sk, dh), jnp.float32)
    out = fa_ops.attention(
        q, k, v, causal=causal, interpret=True, force_kernel=True
    )
    ref = fa_ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_attention_layer_pallas_path_matches_jnp_off_tpu():
    """``multihead_attention(use_pallas=True)`` with no explicit
    ``interpret`` runs the kernel interpreted off the TPU and agrees
    with the layer's jnp path."""
    from repro.models.layers import multihead_attention

    q = randn((1, 4, 128, 64), jnp.float32)
    k = randn((1, 2, 128, 64), jnp.float32)
    v = randn((1, 2, 128, 64), jnp.float32)
    got = jax.jit(lambda q, k, v: multihead_attention(
        q, k, v, causal=True, use_pallas=True))(q, k, v)
    want = multihead_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [32, 96, 200])
def test_flash_attention_sliding_window(window):
    q = randn((1, 4, 256, 64), jnp.float32)
    k = randn((1, 2, 256, 64), jnp.float32)
    v = randn((1, 2, 256, 64), jnp.float32)
    out = fa_ops.attention(
        q, k, v, causal=True, window=window, interpret=True, force_kernel=True
    )
    ref = fa_ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_ragged_padding():
    q = randn((1, 2, 200, 64), jnp.float32)
    k = randn((1, 2, 200, 64), jnp.float32)
    v = randn((1, 2, 200, 64), jnp.float32)
    out = fa_ops.attention(
        q, k, v, causal=False, interpret=True, force_kernel=True
    )
    ref = fa_ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    q = randn((1, 4, 128, 64), dtype)
    k = randn((1, 2, 128, 64), dtype)
    v = randn((1, 2, 128, 64), dtype)
    out = fa_ops.attention(q, k, v, causal=True, interpret=True, force_kernel=True)
    ref = fa_ref.attention(q, k, v, causal=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=3e-2, atol=3e-2
    )
