"""Decode consistency, two layers:

* model — stepping a sequence token-by-token through ``decode_step``
  must reproduce the full-sequence ``forward`` logits (validates the
  KV cache, the repeat-free GQA decode einsum, RoPE positions, and the
  SSM recurrence);
* scheme — the clustered baselines' coefficient-bearing descriptor
  ``collect`` must report exactly the same ``(job, round_done)`` set
  (and decode weights) as the load-only ``collect_jobs`` fast path and
  the batched lockstep kernels, across all 6 ``trace_library()``
  scenarios on both backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _arch import arch_params
from repro.configs import ARCHS, get_smoke
from repro.models import decode_step, forward, init_cache, init_params

DECODE_ARCHS = [
    a for a in ARCHS
    if get_smoke(a).has_decode and get_smoke(a).frontend == "none"
]


@pytest.mark.parametrize("arch", arch_params(DECODE_ARCHS))
def test_decode_matches_forward(arch):
    cfg = get_smoke(arch)
    b, s = 2, 12
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    full_logits, _ = forward(params, cfg, {"tokens": toks})

    cache = init_cache(cfg, b, s)
    step = jax.jit(lambda c, t, p: decode_step(params, cfg, c, t, p))
    outs = []
    for t in range(s):
        logits, cache = step(cache, toks[:, t : t + 1], jnp.int32(t))
        outs.append(logits)
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits), np.asarray(full_logits),
        rtol=2e-3, atol=2e-3,
    )


# ---------------------------------------------------------------------------
# Scheme decode consistency: descriptor collect vs fast path vs kernels
# ---------------------------------------------------------------------------

from repro.core import (  # noqa: E402
    make_scheme,
    simulate,
    simulate_fast,
    simulate_lockstep,
    trace_library,
)

SCHEME_N, SCHEME_J = 16, 16
CLUSTER_SPECS = [("dc-gc", dict(C=4, s=1)), ("sb-gc", dict(C=4, s=1))]


def _scenarios():
    return trace_library(n=SCHEME_N, rounds=20, num_traces=1, seed=0)


def _jd_key(jd):
    return (
        jd.job,
        jd.round_done,
        tuple(sorted((i, round(w, 9)) for i, w in jd.ell_weights.items())),
    )


@pytest.mark.parametrize("spec", CLUSTER_SPECS, ids=lambda s: s[0])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_clustered_paths_agree_on_trace_library(spec, backend):
    """Legacy simulate (descriptor collect) == simulate_fast
    (step/collect_jobs) == simulate_lockstep (batched kernel) on every
    scenario, for both clustered baselines, on both backends."""
    name, kw = spec
    for sc in _scenarios():
        delays = sc.delays[0]
        legacy = simulate(
            make_scheme(name, SCHEME_N, SCHEME_J, **kw), delays,
            mu=1.0, alpha=sc.alpha, J=SCHEME_J,
        )
        fast = simulate_fast(
            make_scheme(name, SCHEME_N, SCHEME_J, **kw), delays,
            mu=1.0, alpha=sc.alpha, J=SCHEME_J,
        )
        lock = simulate_lockstep(
            name, kw, delays[None], mu=1.0, alpha=sc.alpha, J=SCHEME_J,
            backend=backend,
        )[0]
        assert legacy.job_done_round == fast.job_done_round, sc.name
        assert legacy.job_done_round == lock.job_done_round, sc.name
        np.testing.assert_array_equal(
            legacy.effective_pattern, fast.effective_pattern, err_msg=sc.name
        )
        np.testing.assert_array_equal(
            legacy.effective_pattern, lock.effective_pattern,
            err_msg=sc.name,
        )
        assert lock.total_time == pytest.approx(legacy.total_time)


@pytest.mark.parametrize("spec", CLUSTER_SPECS, ids=lambda s: s[0])
def test_clustered_collect_decodes_match_descriptor_collect(spec):
    """Replaying a scenario's admitted pattern through both protocols
    must yield identical JobDecode contents: same (job, round_done)
    set AND the same solved decode weights."""
    name, kw = spec
    for sc in _scenarios():
        pattern = simulate(
            make_scheme(name, SCHEME_N, SCHEME_J, **kw), sc.delays[0],
            mu=1.0, alpha=sc.alpha, J=SCHEME_J,
        ).effective_pattern
        desc = make_scheme(name, SCHEME_N, SCHEME_J, **kw)
        fast = make_scheme(name, SCHEME_N, SCHEME_J, **kw)
        for t in range(1, pattern.shape[0] + 1):
            desc.assign(t)
            desc.observe(t, pattern[t - 1])
            fast.step(t, pattern[t - 1])
            a = sorted(_jd_key(jd) for jd in desc.collect(t))
            b = sorted(_jd_key(jd) for jd in fast.collect_decodes(t))
            assert a == b, (sc.name, t)
