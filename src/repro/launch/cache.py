"""Where the jax runtime keeps its files: paths that stay put, inside
the checkout unless the environment names others.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``launch.train``)
call :func:`enable_compile_cache` and :func:`confine_tpu_logs` once at
start-up, before jax brings up a backend; importing this module changes
nothing.

- Persistent compilation cache.  The cache directory is part of every
  entry's key, so it must not move between runs:
  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (jax reads it
  itself, and no other directory is set in code); otherwise the cache
  lives at ``<checkout>/.jax_cache``.
- TPU library logs.  libtpu writes its logs to ``/tmp`` unless
  ``TPU_LOG_DIR`` names a directory; ``TPU_LOG_DIR`` wins when it is
  set, otherwise they go to ``<checkout>/.tpu_logs``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>`` — this file sits at ``<checkout>/src/repro/launch/cache.py``.
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"
DEFAULT_TPU_LOG_DIR = CHECKOUT / ".tpu_logs"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def confine_tpu_logs() -> str:
    """Point libtpu's logs at ``TPU_LOG_DIR`` or, unset, the checkout's
    ``.tpu_logs``; return the directory.  Takes effect only when called
    before libtpu loads (the first TPU backend or topology query)."""
    log_dir = os.environ.setdefault("TPU_LOG_DIR", str(DEFAULT_TPU_LOG_DIR))
    os.makedirs(log_dir, exist_ok=True)
    return log_dir
