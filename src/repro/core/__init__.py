"""Core library: sequential gradient coding (the paper's contribution).

Three simulation paths cover every workload:

* **Legacy scalar path** — ``simulate`` + ``Scheme.assign/observe/
  collect``: materializes ``MiniTask`` descriptors and decode weights;
  what the coded trainer consumes, and the differential-testing oracle.
* **Fast scalar path** — ``simulate_fast`` is a bit-for-bit drop-in
  for ``simulate`` on the schemes' load-only fast path
  (``Scheme.step``/``collect_jobs``: single-cell wrappers over the
  functional kernels in ``core.kernel``).
* **Lockstep batch engine** (``core.batch`` + ``core.kernel``) —
  ``simulate_batch`` runs a whole (specs x seeds x traces) grid with
  every trace of a spec advancing through the batched struct-of-arrays
  kernels in lockstep (math behind the ``core.backend`` shim: numpy
  now, jax-swappable).  ``select_parameters`` (App. J) runs on this
  engine; ``select_parameters_legacy`` keeps the old per-candidate
  loop as the oracle.  See docs/scheme_kernels.md for the kernel
  protocol and how to add a scheme.

Typical sweep::

    from repro.core import simulate_batch
    results = simulate_batch(
        [("m-sgc", {"B": 2, "W": 3, "lam": 27}), ("gc", {"s": 15})],
        traces,                   # (num_traces, rounds, n) delays
        seeds=(0, 1), alpha=8.0,
    )                             # object array (specs, seeds, traces)
    total = results[0, 0, 0].total_time
"""

from .backend import (
    available_backends,
    get_backend,
    set_backend,
    use_backend,
    xp_of,
)
from .batch import (
    cache_stats,
    clear_runner_cache,
    grid_plan,
    select_parameters_fast,
    simulate_batch,
    simulate_fast,
    simulate_lockstep,
)
from .kernel import (
    GateKernel,
    SchemeKernel,
    SchemeState,
    has_kernel,
    make_kernel,
    register_kernel,
    state_flatten,
    state_unflatten,
)
from .bounds import (
    load_gc,
    load_m_sgc,
    load_sr_sgc,
    lower_bound_arbitrary,
    lower_bound_bursty,
    sr_sgc_s,
)
from .gc import GradientCode, RepGradientCode, cyclic_support, make_gradient_code
from .schemes import (
    DCGCScheme,
    GCScheme,
    JobDecode,
    MSGCScheme,
    MiniTask,
    NoCodingScheme,
    SBGCScheme,
    SRSGCScheme,
    make_scheme,
    register_scheme,
)
from .simulator import (
    SimResult,
    estimate_alpha,
    reference_profile,
    select_parameters,
    select_parameters_legacy,
    simulate,
)
from .straggler import (
    ArbitraryModel,
    BurstyModel,
    ConformanceGate,
    DynamicClusterModel,
    GilbertElliotSource,
    LambdaTraceGenerator,
    MixtureModel,
    PerRoundModel,
    RepCoverageModel,
    Scenario,
    StochasticBlockModel,
    TraceModel,
    TraceSource,
    WindowwiseOr,
    fit_gilbert_elliot,
    load_recorded_harness,
    suggest_parameters,
    trace_library,
)

__all__ = [
    "GradientCode",
    "RepGradientCode",
    "cyclic_support",
    "make_gradient_code",
    "GCScheme",
    "SRSGCScheme",
    "MSGCScheme",
    "DCGCScheme",
    "SBGCScheme",
    "NoCodingScheme",
    "MiniTask",
    "JobDecode",
    "make_scheme",
    "BurstyModel",
    "ArbitraryModel",
    "PerRoundModel",
    "MixtureModel",
    "WindowwiseOr",
    "RepCoverageModel",
    "DynamicClusterModel",
    "StochasticBlockModel",
    "ConformanceGate",
    "GilbertElliotSource",
    "TraceSource",
    "TraceModel",
    "LambdaTraceGenerator",
    "Scenario",
    "trace_library",
    "load_recorded_harness",
    "fit_gilbert_elliot",
    "suggest_parameters",
    "load_gc",
    "load_sr_sgc",
    "load_m_sgc",
    "lower_bound_bursty",
    "lower_bound_arbitrary",
    "sr_sgc_s",
    "simulate",
    "SimResult",
    "select_parameters",
    "select_parameters_legacy",
    "estimate_alpha",
    "reference_profile",
    "simulate_fast",
    "simulate_batch",
    "simulate_lockstep",
    "select_parameters_fast",
    "grid_plan",
    "cache_stats",
    "clear_runner_cache",
    "register_scheme",
    "SchemeKernel",
    "SchemeState",
    "GateKernel",
    "make_kernel",
    "register_kernel",
    "has_kernel",
    "get_backend",
    "set_backend",
    "use_backend",
    "available_backends",
]
