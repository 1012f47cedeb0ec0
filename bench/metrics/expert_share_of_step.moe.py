"""Share of the coded train step's device self time (``jit_step``) spent
in the expert layers in the span window: ops under the ``experts`` named
scope (router, dispatch, grouped matmuls, combine; forward,
recomputation and backward) and the grouped-matmul kernels the TPU
compiler emits itself, which carry no scope (``ragged-dot-*``); None
where over 5% of the step's time cannot be attributed."""

from bench.spans import NULL_SHARE, in_scope, reading


def read(ctx):
    r = reading(ctx)
    if r is None or "jit_step" not in r.scopes:
        return None
    st = r.scopes["jit_step"]
    if st.total_s <= 0 or st.unattributed_s > NULL_SHARE * st.total_s:
        return None
    inside = sum(s for p, s in st.by_scope.items()
                 if in_scope(p, "experts") or p.startswith("ragged-dot"))
    return inside / st.total_s
