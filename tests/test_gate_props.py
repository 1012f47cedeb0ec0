"""ConformanceGate properties: under ANY candidate straggler stream the
effective history stays inside the design envelope (Remark 2.3), and
selective wait-outs never wait more workers than the all-workers rule."""

import copy

import numpy as np
from _prop import HealthCheck, given, settings, st

from repro.core.straggler import (
    ArbitraryModel,
    BurstyModel,
    ConformanceGate,
    MixtureModel,
    PerRoundModel,
    WindowwiseOr,
)

COMMON = dict(
    deadline=None,
    max_examples=30,
    suppress_health_check=[HealthCheck.too_slow],
)


def _models(n, B, W, lam, s):
    return [
        PerRoundModel(s),
        BurstyModel(B, W, lam),
        ArbitraryModel(B, W + B - 1, lam),
        MixtureModel((BurstyModel(B, W, lam), ArbitraryModel(B, W + B - 1, lam))),
        WindowwiseOr((BurstyModel(B, W, lam), PerRoundModel(s)), W),
    ]


@given(
    n=st.integers(4, 12),
    B=st.integers(1, 3),
    dW=st.integers(1, 3),
    lam=st.integers(1, 6),
    s=st.integers(0, 4),
    seed=st.integers(0, 10_000),
    density=st.floats(0.1, 0.7),
    rounds=st.integers(5, 25),
)
@settings(**COMMON)
def test_gate_always_conforms(n, B, dW, lam, s, seed, density, rounds):
    lam = min(lam, n)
    s = min(s, n - 1)
    W = B + dW
    rng = np.random.default_rng(seed)
    for model in _models(n, B, W, lam, s):
        gate = ConformanceGate(model, n)
        for _ in range(rounds):
            cand = rng.random(n) < density
            cost = rng.random(n)
            if not cand.any():
                gate.force(cand)
                continue
            eff, waited = gate.admit_partial(cand, cost)
            # waited workers are exactly the dropped stragglers
            assert set(waited) == set(np.flatnonzero(cand & ~eff).tolist())
        assert model.conforms(gate.history), type(model).__name__


@given(
    seed=st.integers(0, 5000),
    density=st.floats(0.2, 0.8),
)
@settings(**COMMON)
def test_selective_waits_no_more_than_all(seed, density):
    """Each round, from the same committed history, the selective gate
    waits out no more workers than the all-workers rule would, and none
    when that rule admits the round.  Summed over a run the two rules'
    histories diverge and the sums are not ordered: seed=4836,
    density=0.2 waits 7 workers selectively against 6 for the
    all-workers rule."""
    n, rounds = 10, 15
    model = BurstyModel(1, 2, 3)
    rng = np.random.default_rng(seed)
    cands = rng.random((rounds, n)) < density
    costs = rng.random((rounds, n))

    sel = ConformanceGate(model, n)
    for t in range(rounds):
        if not cands[t].any():
            sel.force(cands[t])
            continue
        all_rule = copy.deepcopy(sel)
        waits_all = 0 if all_rule.admit(cands[t]) else int(cands[t].sum())
        _, waited = sel.admit_partial(cands[t], costs[t])
        assert len(waited) <= waits_all
        assert set(waited) <= set(np.flatnonzero(cands[t]).tolist())
    assert model.conforms(sel.history)
