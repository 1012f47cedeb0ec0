"""Operation and byte counts of the benchmark's cost files."""

import json
from pathlib import Path

import pytest

from bench.harness import cost

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_qwen2_matches_a_hand_count():
    cfg = json.loads((CONFIGS / "qwen2-0.5b.json").read_text())
    q = cost("qwen2")
    # per layer: q and o 896x896, k and v 896x128, three 896x4864 MLP
    # matrices; the tied head 151,936 x 896
    layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert q.matmul_weights(cfg) == 24 * layer + 151936 * 896 == 493961216
    per_token = 6 * 493961216 + 12 * 24 * 14 * 64 * 64
    assert q.train_flops_per_token(cfg, 64) == per_token
    assert per_token == pytest.approx(2.98e9, rel=1e-3)
    head = 6 * 151936 * 896 / per_token
    assert head == pytest.approx(0.274, abs=1e-3)
    assert q.train_flops(cfg, 24, 64) == 24 * 64 * per_token
