"""Useful training operations of the jobs decoded in the traced window
(``bench/costs/qwen2.py``) over the window's length times the chips'
bf16 peak, in percent."""

from bench.harness import cost


def read(ctx):
    drv = ctx.driver
    if not drv.window_jobs:
        return None
    flops = drv.window_jobs * cost("qwen2").train_flops(
        drv.cfg, drv.batch, drv.seq)
    peak = ctx.peaks["bf16_flops"] * len(ctx.devices)
    return 100.0 * flops / (ctx.reduction.window_s * peak)
