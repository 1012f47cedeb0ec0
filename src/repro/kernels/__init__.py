"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel package ships three files:
  * ``<name>.py`` — the ``pl.pallas_call`` kernel with explicit
    BlockSpec VMEM tiling (TPU is the target; ``interpret=True``
    validates on CPU),
  * ``ops.py``    — jit'd public wrapper with shape/dtype plumbing,
  * ``ref.py``    — pure-jnp oracle used by the allclose test sweeps.

Kernels:
  * ``gc_coding``       — coded combine: the (s+1)-way coefficient
    reduction of chunk gradients (GC encode) and survivor-weighted
    reduction (decode).  The paper's only added compute vs uncoded SGD.
  * ``rmsnorm``         — fused RMSNorm (bandwidth-bound).
  * ``flash_attention`` — blocked GQA attention w/ causal + sliding
    window masks (dominates every assigned arch's FLOPs).
  * ``ssd_scan``        — Mamba2 SSD intra-chunk block (the ssm/hybrid
    archs' compute hot-spot).
"""

import functools

import jax


def native_on_tpu(call, *args):
    """``call(*args, interpret=...)``, compiled by Mosaic when the
    enclosing program is lowered for a TPU and interpreted on any other
    platform.  The choice follows the lowering target rather than
    ``jax.default_backend()``, so an ahead-of-time compile for a
    described TPU takes the native kernel as well."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True),
    )


from . import flash_attention, gc_coding, rmsnorm, ssd_scan  # noqa: E402,F401
