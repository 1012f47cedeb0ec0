"""Milliseconds of the simulator's transfers per ``simulate_batch`` call
(``sim.batch`` span) in the traced window: the time inside the
``sim.upload`` spans (host to device) and the ``sim.fetch`` spans
(device to host) in which no op runs on the chip.  The fetch also
waits for the bucket's program; the chip is busy while that runs, so
the scan's own time is left out without a sync between the steps."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None:
        return None
    ms = r.idle_per(("sim.upload", "sim.fetch"), "sim.batch")
    return None if ms is None else ms * 1e3
