"""Share of the coded train step's device self time (``jit_step``) spent
in ops under the ``mla`` named scope (latent attention's projections,
rope and attention core; forward, recomputation and backward) in the
span window; None where over 5% of that time cannot be attributed."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None or "jit_step" not in r.scopes:
        return None
    return r.scopes["jit_step"].share("mla")
