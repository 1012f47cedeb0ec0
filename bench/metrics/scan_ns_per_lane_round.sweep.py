"""Device nanoseconds of the simulator's runner programs (the jitted
``lax.scan`` of ``core.batch``, program name ``jit_run``) per
lane-round completed in the traced window."""


def read(ctx):
    secs = ctx.reduction.module_seconds("jit_run")
    lane_rounds = ctx.driver.lane_rounds
    if secs <= 0 or not lane_rounds:
        return None
    return secs / lane_rounds * 1e9
