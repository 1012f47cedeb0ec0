"""Shared tier-1 architecture selection for per-arch test matrices.

Every architecture stays covered, but the default (tier-1) run compiles
only one representative per family; the rest carry the ``slow`` marker
(run them with ``pytest -m slow`` / ``pytest -m ""``).

Families -> representative:
  dense attention (GQA, qkv-bias)  qwen2-0.5b
  pure SSM (Mamba2)                mamba2-1.3b
  MoE (+ shared experts)           qwen2-moe-a2.7b
  latent attention, held experts   deepseek-v2-lite
  audio frontend, non-causal       hubert-xlarge
  vision-prefix                    paligemma-3b
Slow set: llama3.2-1b, zamba2-2.7b (hybrid), mixtral-8x22b,
qwen2-72b, deepseek-67b — larger smoke configs of already-covered
families.
"""

import pytest

FAST_ARCHS = {
    "qwen2-0.5b",
    "mamba2-1.3b",
    "qwen2-moe-a2.7b",
    "deepseek-v2-lite",
    "hubert-xlarge",
    "paligemma-3b",
}


def arch_params(archs, fast=FAST_ARCHS):
    """Parametrize ids, slow-marking architectures outside ``fast``.

    Pass a narrower ``fast`` set for matrices too expensive to run one
    representative per family (e.g. prefill/decode parity).
    """
    return [
        a if a in fast else pytest.param(a, marks=pytest.mark.slow)
        for a in archs
    ]
