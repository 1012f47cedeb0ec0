"""Gilbert-Elliott straggler traffic: the delay traces the simulator
cells replay, made from the seed.

Each worker is a two-state chain (App. C of the paper): from normal it
turns straggler with probability ``p_ns`` per round, and back with
``p_sn``.  A normal worker takes ``base * (1 + jitter * z**2)``
seconds at load 1/n, a straggler that times a uniform slow-down in
``[1, slow]``.  All traces of a batch are drawn together, one round at
a time.
"""

from __future__ import annotations

import numpy as np


def delays(rng: np.random.Generator, traces: int, rounds: int, n: int, *,
           p_ns: float, p_sn: float, slow_factor: float, jitter: float,
           base_time: float = 1.0) -> np.ndarray:
    """(traces, rounds, n) float64 seconds."""
    state = rng.random((traces, n)) < p_ns / (p_ns + p_sn)
    flips = rng.random((traces, rounds, n))
    pattern = np.empty((traces, rounds, n), dtype=bool)
    for t in range(rounds):
        pattern[:, t] = state
        state = np.where(state, flips[:, t] >= p_sn, flips[:, t] < p_ns)
    base = base_time * (1.0 + jitter
                        * rng.standard_normal((traces, rounds, n)) ** 2)
    slow = 1.0 + (slow_factor - 1.0) * rng.random((traces, rounds, n))
    return np.where(pattern, base * slow, base)


def seed_words(seed: int, *words: int) -> list[int]:
    """A numpy seed sequence entropy for ``seed`` (any size) and a
    stream label."""
    return [seed % 2**63, seed // 2**63, *words]
