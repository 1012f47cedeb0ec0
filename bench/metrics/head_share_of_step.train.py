"""Share of the coded train step's device self time (``jit_step``) spent
in ops under the ``head`` named scope (final norm, tied-head matmul,
log-softmax and loss, forward and backward) in the traced window;
None where over 5% of that time cannot be attributed to a scope."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None or "jit_step" not in r.scopes:
        return None
    return r.scopes["jit_step"].share("head")
