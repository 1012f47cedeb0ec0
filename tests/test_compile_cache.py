"""The runtime's directory rules (``repro.launch.cache``): for the
persistent compilation cache ``JAX_COMPILATION_CACHE_DIR`` wins and no
other directory is set in code, for libtpu's logs ``TPU_LOG_DIR`` wins;
otherwise each is a fixed path in the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch.cache import (
    DEFAULT_CACHE_DIR,
    DEFAULT_TPU_LOG_DIR,
    confine_tpu_logs,
    enable_compile_cache,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first       # stable across calls
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_tpu_log_env_dir_wins(monkeypatch, tmp_path):
    log_dir = tmp_path / "tpu_logs"
    monkeypatch.setenv("TPU_LOG_DIR", str(log_dir))
    assert confine_tpu_logs() == str(log_dir)
    assert log_dir.is_dir()


def test_tpu_log_default_is_fixed_path_in_checkout(monkeypatch):
    # set first, so that teardown also removes what the call sets
    monkeypatch.setenv("TPU_LOG_DIR", "")
    monkeypatch.delenv("TPU_LOG_DIR")
    first = confine_tpu_logs()
    assert first == str(REPO / ".tpu_logs") == str(DEFAULT_TPU_LOG_DIR)
    assert confine_tpu_logs() == first           # stable across calls
    assert Path(first).is_dir()
    assert ".tpu_logs/" in (REPO / ".gitignore").read_text().split()
