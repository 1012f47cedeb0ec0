"""The grouped expert matmuls' share of their roofline, in percent: the
least time the chip needs for the operations and bytes they execute
for the routed rows (``bench/costs/deepseek_v2.py``, the span window's
``held_rows``), the larger of operations over the bf16 peak and bytes
over HBM bandwidth, over the device self time of the grouped matmuls:
the ops under the ``experts/gmm`` named scope and the grouped-matmul
kernels the TPU compiler emits itself, which carry no scope
(``ragged-dot-*``).  Padding rows count nothing.  None without the
counters or the ops."""

from bench.harness import cost
from bench.spans import in_scope, reading


def read(ctx):
    r = reading(ctx)
    if r is None or "train.sync" not in r.spans or "jit_step" not in r.scopes:
        return None
    rows = r.attr(("train.sync",), "held_rows")
    secs = sum(s for p, s in r.scopes["jit_step"].by_scope.items()
               if (in_scope(p, "experts") and in_scope(p, "gmm"))
               or p.startswith("ragged-dot"))
    if not rows or secs <= 0:
        return None
    drv = ctx.driver
    layers = drv.cfg["num_hidden_layers"] - drv.cfg["first_k_dense_replace"]
    flops, nbytes = cost("deepseek_v2").gmm_work(
        drv.cfg, rows, layers * r.spans["train.sync"].count)
    least = max(flops / (ctx.peaks["bf16_flops"] * len(ctx.devices)),
                nbytes / (ctx.peaks["hbm_bytes_per_s"] * len(ctx.devices)))
    return 100.0 * least / secs
