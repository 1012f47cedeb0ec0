"""Cells cut to CPU size for the tests: same drivers, same limits."""

import time

from bench import harness

TABLE1_SMALL = [
    {"scheme": "m-sgc", "params": {"B": 2, "W": 3, "lam": 3}},
    {"scheme": "sr-sgc", "params": {"B": 2, "W": 3, "lam": 3}},
    {"scheme": "gc", "params": {"s": 3}},
    {"scheme": "uncoded", "params": {}},
]

QWEN_SMALL = dict(num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
                  num_key_value_heads=1, head_dim=16, intermediate_size=64,
                  vocab_size=256, torch_dtype="float32")


def sweep_cell():
    cell = harness.resolve("sweep-table1")
    cell.config = dict(cell.config, workers=16, jobs=40,
                       table1=TABLE1_SMALL)
    cell.traffic = dict(cell.traffic, traces_per_call=4, pool=2, compare=4)
    return cell


def train_cell(name="train-gc"):
    cell = harness.resolve(name)
    cell.config = dict(cell.config, **QWEN_SMALL)
    cell.traffic = dict(cell.traffic, seq_len=8, jobs_per_call=2)
    return cell


def run(cell, seconds=0.3, seed=2**33 + 11):
    """A whole run past the look for a chip, on jax's default device."""
    import jax

    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices())
