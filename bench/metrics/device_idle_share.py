"""Share of the traced window in which no operation ran on the chip
(mean over the chips used), from the trace reduction."""


def read(ctx):
    return ctx.reduction.idle_share
