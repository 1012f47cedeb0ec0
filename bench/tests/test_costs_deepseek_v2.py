"""Operation and byte counts of ``bench/costs/deepseek_v2.py`` against a
hand count at the cell's widths."""

import json
from pathlib import Path

import pytest

from bench.harness import cost

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "deepseek-v2-lite.json").read_text())


def test_token_operations_match_a_hand_count():
    c = cost("deepseek_v2")
    d = 2048
    # q 2048 x 16*192, kv_a 2048 x 576, kv_b 512 x 16*256, o 16*128 x 2048
    attn = d * 3072 + d * 576 + 512 * 4096 + 2048 * d
    assert attn == 13_762_560
    dense = 3 * d * 10944
    per_expert_layer = d * 64 + 3 * d * 2816
    head = d * 12800
    assert c.token_weights(CFG) == 6 * attn + dense + 5 * per_expert_layer \
        + head
    scores = 6 * 6 * 16 * 1024 * (192 + 128)
    per_token = c.token_flops(CFG, 1024)
    assert per_token == 6 * c.token_weights(CFG) + scores
    # the split quoted for the cell, GFLOP a token
    assert 6 * 6 * attn / 1e9 == pytest.approx(0.50, abs=0.01)
    assert scores / 1e9 == pytest.approx(0.19, abs=0.01)
    assert 6 * dense / 1e9 == pytest.approx(0.40, abs=0.01)
    assert 6 * head / 1e9 == pytest.approx(0.16, abs=0.01)
    routed = c.routed_row_flops(CFG)
    assert routed == 6 * 3 * d * 1408
    rows = c.expected_rows(CFG, 1)          # 6 of 64 picks, 8 held, 5 layers
    assert rows == pytest.approx(5 * 6 * 8 / 64)
    experts = (rows * routed + 6 * 5 * 3 * d * 2816) / 1e9
    assert experts == pytest.approx(0.72, abs=0.01)
    total = (per_token + rows * routed) / 1e9
    assert total == pytest.approx(1.96, abs=0.01)
    assert c.train_flops(CFG, 8, 1024, 8 * 1024 * rows) == pytest.approx(
        8 * 1024 * total * 1e9)


def test_gmm_work_matches_a_hand_count():
    c = cost("deepseek_v2")
    d, eff, held = 2048, 1408, 8
    layer_steps = 5
    rows = layer_steps * 12_288     # the cell's rows a layer call, all calls
    flops, nbytes = c.gmm_work(CFG, rows, layer_steps)
    # 3 matrices x (forward, recomputation, two backward products)
    assert flops == 12 * 2 * rows * d * eff
    assert nbytes == 12 * 2 * (rows * (d + eff) + layer_steps * held * d * eff)
    # compute-bound at these rows on a v5e (197 TFLOP/s, 819 GB/s)
    assert flops / 197e12 > nbytes / 819e9


def _reading_ctx(rows, by_scope, jobs=2, window_s=1.0):
    """A context whose span reading is given, as the readers see it."""
    from types import SimpleNamespace

    from bench.spans import Reading, ScopeTime, SpanStat

    st = ScopeTime(total_s=sum(by_scope.values()), by_scope=dict(by_scope))
    reading = Reading(window_s=window_s, spans={"train.sync": SpanStat(
        count=jobs, attrs={"held_rows": rows})}, scopes={"jit_step": st})
    drv = SimpleNamespace(cfg=CFG, batch=8, seq=1024, replication=2.0)
    return SimpleNamespace(spans_reading=reading, driver=drv, devices=[0],
                           peaks={"bf16_flops": 197e12,
                                  "hbm_bytes_per_s": 819e9})


GMM = "jit(step)/transpose(jvp(coded_loss))/layers/experts/gmm/mul"
MLA = "jit(step)/transpose(jvp(coded_loss))/layers/mla/dot_general"
ROUTER = "jit(step)/jvp(coded_loss)/layers/experts/router/dot_general"


def test_expert_readers_count_the_unscoped_ragged_dot_kernels():
    """The TPU compiler's ragged-dot kernels carry no scope: the gmm
    roofline and the expert share count them with ``experts``."""
    from bench.harness import ROOT, load_module

    def reader(name):
        return load_module(ROOT / "bench" / "metrics" / f"{name}.py", name)

    rows = 2 * 5 * 12_288
    ctx = _reading_ctx(rows, {"ragged-dot-none": 0.06, GMM: 0.02,
                              MLA: 0.05, ROUTER: 0.01,
                              "ragged-dot-metadata": 0.0})
    flops, _ = cost("deepseek_v2").gmm_work(CFG, rows, 5 * 2)
    roofline = reader("expert_gmm_roofline.moe").read(ctx)
    assert roofline == pytest.approx(100 * flops / 197e12 / 0.08)
    assert 0 < roofline <= 100
    share = reader("expert_share_of_step.moe").read(ctx)
    assert share == pytest.approx(0.09 / 0.14)
    assert reader("mla_share_of_step.moe").read(ctx) == pytest.approx(
        0.05 / 0.14)
    mfu = reader("train_mfu.moe").read(ctx)
    c = cost("deepseek_v2")
    useful = 2 * c.train_flops(CFG, 8, 1024, 0) \
        + rows / 2 * c.routed_row_flops(CFG)
    assert mfu == pytest.approx(100 * useful / 197e12)
    # the parent's program has no counters: nothing to read
    assert reader("train_mfu.moe").read(_reading_ctx(0, {MLA: 1.0})) is None
