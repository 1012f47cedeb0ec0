"""The expert-model training cell's comparison on a CPU-sized
DeepSeek-V2 (float32, so the program and the reference agree to
rounding): a sound run is correct; runs with a fault planted in the
timed path, and the fp8 control, are not."""

import pytest

from bench.tests import _tiny_moe
from bench.tests.faults_moe import FAULTS


def test_sound_run_is_correct():
    line = _tiny_moe.run(_tiny_moe.moe_cell())
    assert line["correct"], line
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["checks"]["optimizer_steps_differ"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_run_with_a_planted_fault_is_not_correct(fault):
    with FAULTS[fault]():
        line = _tiny_moe.run(_tiny_moe.moe_cell())
    assert line["correct"] is False, line


def test_fp8_control_fails_the_limits():
    from bench.drivers.train_moe import _bound, grad_rel_error

    cell = _tiny_moe.moe_cell()
    cfg, tr = cell.config, cell.traffic
    base = _bound(cfg["reference"])
    steps = [(3, 1), (3, 2), (4, 1)]
    want = base.reference_readings(cfg, tr, 5, steps)
    low = base.reference_readings(cfg, tr, 5, steps, quant="fp8")
    nums = base.compare(low, want)
    toks = base.ref.tokens(*steps[0], tr["batch"], tr["seq_len"],
                           cfg["vocab_size"])
    params = base.ref.init_weights(cfg, 5)
    grads = [base.ref.Trainer(cfg, params, blocks=tr["ref_blocks"],
                              quant=quant, m=0, v=0).loss_and_grad(toks)[1]
             for quant in ("fp8", None)]
    nums["grad_rel_error"] = grad_rel_error(*grads)
    assert any(nums[k] > v for k, v in tr["limits"].items()), nums
    assert nums["grad_rel_error"] > tr["limits"]["grad_rel_error"], nums


def test_grad_rel_error_counts_every_leaf():
    import numpy as np

    from bench.drivers.train_moe import grad_rel_error

    want = {"a": np.array([3.0, 0.0]), "b": {"c": np.array([4.0])}}
    same = {"a": np.array([6.0, 0.0]), "b": {"c": np.array([8.0])}}
    assert grad_rel_error(same, want, 0.5) == 0.0
    off = {"a": np.array([3.0, 5.0]), "b": {"c": np.array([4.0])}}
    assert grad_rel_error(off, want) == pytest.approx(1.0)
    assert grad_rel_error({"a": want["a"]}, want) == float("inf")


def test_routing_disagreement_counts_selections():
    import jax.numpy as jnp

    from bench.drivers.train_moe import routing_disagreement

    a = jnp.array([[[0, 1, 2], [3, 4, 5]]])
    b = jnp.array([[[2, 1, 0], [3, 4, 6]]])
    assert routing_disagreement(a, a) == 0.0
    assert routing_disagreement(a, b) == pytest.approx(1 / 6)
