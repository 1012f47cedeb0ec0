"""Host milliseconds of the simulator's planning and result assembly
(self time of the ``sim.plan`` and ``sim.assemble`` spans) per
``simulate_batch`` call (``sim.batch`` span) in the traced window."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None:
        return None
    ms = r.per(("sim.plan", "sim.assemble"), "sim.batch")
    return None if ms is None else ms * 1e3
