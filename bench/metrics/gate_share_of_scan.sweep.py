"""Share of the simulator programs' device self time (``jit_run``) spent
in ops under the ``gate`` named scope (the wait-out gate and its
Pallas kernels) in the traced window; None where over 5% of that
time cannot be attributed to a scope."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None or "jit_run" not in r.scopes:
        return None
    return r.scopes["jit_run"].share("gate")
