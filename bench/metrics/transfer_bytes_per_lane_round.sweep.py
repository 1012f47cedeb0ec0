"""Bytes the simulator moves between host and device (the ``bytes``
attributes of the ``sim.upload`` and ``sim.fetch`` spans) per
lane-round it runs (the ``lane_rounds`` attributes of the
``sim.batch`` spans) in the traced window: a count, fixed by the
bucket shapes."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None:
        return None
    lane_rounds = r.attr(("sim.batch",), "lane_rounds")
    if not lane_rounds:
        return None
    return r.attr(("sim.upload", "sim.fetch"), "bytes") / lane_rounds
