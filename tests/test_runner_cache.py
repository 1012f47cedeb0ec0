"""Host-side regression tests for the compiled-runner cache and its
``REPRO_RUNNER_CACHE_CAP`` parser (no jax required).

The runner cache is one FIFO of compiled bucket runners, capped by
``REPRO_RUNNER_CACHE_CAP``.
"""

import pytest

from repro.core import batch
from repro.core.batch import (
    _runner_cache_cap,
    _runner_cache_lookup,
    cache_stats,
    clear_runner_cache,
)


@pytest.fixture
def _clean_cache():
    clear_runner_cache()
    yield
    clear_runner_cache()


def _fail():  # pragma: no cover - called only on a cache-miss bug
    raise AssertionError("cache miss: build() re-ran for a cached key")


def test_compiled_runner_fifo_still_capped(_clean_cache, monkeypatch):
    """The cap still governs compiled runners themselves."""
    monkeypatch.setenv("REPRO_RUNNER_CACHE_CAP", "2")
    for i in range(4):
        _runner_cache_lookup(("grid", i), lambda i=i: (f"runner-{i}", ""))
    st = cache_stats()
    assert st["size"] == 2
    assert st["evictions"] == 2
    # FIFO: the two oldest runners were evicted
    rebuilt = []
    _runner_cache_lookup(("grid", 0), lambda: rebuilt.append(0) or ("r", ""))
    assert rebuilt == [0]


def test_clear_runner_cache_drops_verdicts(_clean_cache):
    """Clearing drops every compiled runner and zeroes the counters."""
    _runner_cache_lookup(("grid", "a"), lambda: ("runner-a", "a"))
    _runner_cache_lookup(("grid", "a"), _fail)
    assert cache_stats()["size"] == 1
    clear_runner_cache()
    st = cache_stats()
    assert st["size"] == 0
    assert st["hits"] == st["misses"] == st["compiles"] == 0
    assert st["evictions"] == 0
    rebuilt = []
    _runner_cache_lookup(("grid", "a"),
                         lambda: rebuilt.append(1) or ("runner-a", "a"))
    assert rebuilt == [1]


def test_runner_cache_cap_env_parser(monkeypatch):
    monkeypatch.delenv("REPRO_RUNNER_CACHE_CAP", raising=False)
    assert _runner_cache_cap() == batch._RUNNER_CACHE_CAP_DEFAULT
    monkeypatch.setenv("REPRO_RUNNER_CACHE_CAP", "7")
    assert _runner_cache_cap() == 7
    monkeypatch.setenv("REPRO_RUNNER_CACHE_CAP", "0")
    assert _runner_cache_cap() == 1          # clamped to >= 1
    monkeypatch.setenv("REPRO_RUNNER_CACHE_CAP", "not-an-int")
    with pytest.warns(UserWarning, match="REPRO_RUNNER_CACHE_CAP"):
        assert _runner_cache_cap() == batch._RUNNER_CACHE_CAP_DEFAULT
