"""Useful training operations of the jobs decoded in the span window
(``bench/costs/deepseek_v2.py``: every token's share, plus the routed
expert rows the step's ``held_rows`` counters report, less the coded
step's chunk replication) over the window's length times the chips'
bf16 peak, in percent; None without the counters."""

from bench.harness import cost
from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None or "train.sync" not in r.spans:
        return None
    rows = r.attr(("train.sync",), "held_rows")
    if not rows:
        return None
    drv = ctx.driver
    c = cost("deepseek_v2")
    jobs = r.spans["train.sync"].count
    flops = jobs * c.train_flops(drv.cfg, drv.batch, drv.seq, 0) \
        + rows / drv.replication * c.routed_row_flops(drv.cfg)
    peak = ctx.peaks["bf16_flops"] * len(ctx.devices)
    return 100.0 * flops / (r.window_s * peak)
