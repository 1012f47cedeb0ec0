"""The training cells' comparison on a CPU-sized Qwen2 (float32, so
the program and the reference agree to rounding): a sound run is
correct; runs with a fault planted in the timed path are not."""

import pytest

from bench.tests import _tiny


def test_sound_run_is_correct():
    line = _tiny.run(_tiny.train_cell())
    assert line["correct"], line
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["checks"]["optimizer_steps_differ"]["value"] == 0


def _plant(monkeypatch, fault):
    import jax.numpy as jnp

    import repro.data
    import repro.train.coded as coded

    if fault == "state":
        make = coded.make_coded_loss

        def frozen(cfg, n, s, *, lr=1e-4, weight_decay=0.0,
                   num_chunks=None):
            loss = make(cfg, n if num_chunks is None else num_chunks)
            return lambda p, o, b, w: (p, o, {"loss": loss(p, b, w)})

        monkeypatch.setattr(coded, "make_coded_train_step", frozen)
    elif fault == "half":
        full = repro.data.token_batch

        def half(seed, job, batch, seq, vocab):
            out = full(seed, job, batch, seq, vocab)
            return {k: jnp.concatenate([v[: batch // 2]] * 2)
                    for k, v in out.items()}

        monkeypatch.setattr(repro.data, "token_batch", half)


@pytest.mark.parametrize("fault", ["state", "half"])
def test_run_with_a_planted_fault_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    line = _tiny.run(_tiny.train_cell())
    assert line["correct"] is False, line


#: model 0's steps in set-up: two calls of two jobs
SETUP_STEPS = 4


def _plant_after_setup(monkeypatch, fault):
    """Break the timed path only after set-up's steps, so that only the
    steady-state check after the window can see it."""
    import jax
    import jax.numpy as jnp

    import repro.data
    from repro.train import driver

    if fault == "state":
        post = driver.VectorizedCodedTrainer.__post_init__

        def stale_after_setup(self):
            post(self)
            step, calls = self._step, []

            def stale(p, o, b, w):
                calls.append(1)
                if len(calls) <= SETUP_STEPS:
                    return step(p, o, b, w)
                copy = jax.tree.map(jnp.copy, (p, o))
                return p, o, step(*copy, b, w)[2]

            self._step = stale

        monkeypatch.setattr(driver.VectorizedCodedTrainer, "__post_init__",
                            stale_after_setup)
    elif fault == "half":
        full, calls = repro.data.token_batch, []

        def half_after_setup(seed, job, batch, seq, vocab):
            calls.append(1)
            out = full(seed, job, batch, seq, vocab)
            if len(calls) <= SETUP_STEPS:
                return out
            return {k: jnp.concatenate([v[: batch // 2]] * 2)
                    for k, v in out.items()}

        monkeypatch.setattr(repro.data, "token_batch", half_after_setup)


@pytest.mark.parametrize("fault", ["state", "half"])
def test_fault_after_setup_fails_the_steady_state_check(monkeypatch, fault):
    _plant_after_setup(monkeypatch, fault)
    line = _tiny.run(_tiny.train_cell())
    assert line["correct"] is False, line
    checks = line["checks"]
    # the first steps are sound; the step after the window is not
    assert checks["loss_rel_gap"]["value"] <= checks["loss_rel_gap"]["limit"]
    assert any(checks[k]["value"] > checks[k]["limit"]
               for k in ("late_grad_norm_gap", "late_update_norm_gap")), \
        checks


def test_fp8_control_fails_the_limits():
    from bench.drivers.train import compare, reference_readings

    cell = _tiny.train_cell()
    cfg, tr = cell.config, cell.traffic
    steps = [(3, 1), (3, 2), (4, 1)]
    want = reference_readings(cfg, tr, 5, steps)
    low = reference_readings(cfg, tr, 5, steps, quant="fp8")
    nums = compare(low, want)
    assert any(nums[k] > v for k, v in tr["limits"].items()), nums
