"""Grid sweep on the lockstep batch engine.

Sweeps (scheme parameters x GE traces) through ``simulate_batch`` —
every trace of a spec advances through the functional scheme kernels
in lockstep (struct-of-arrays state, math behind the ``core.backend``
shim) — then reports the fastest parameterization per scheme: the
Monte-Carlo version of the paper's App.-J probe procedure (what
Table 1 / Figs. 15-18 aggregate).

    PYTHONPATH=src python examples/parameter_sweep.py [n] [rounds] \
        [--backend jax]

``--backend jax`` runs on the device-resident lockstep path (see
docs/scheme_kernels.md, "Running on jax"): the planner buckets specs
by static shape key and each bucket compiles as ONE vmapped
``lax.scan`` — the per-scheme lines below report how many shape
buckets each sweep folded into and how many runners were actually
compiled.
"""

import sys
import time

import numpy as np

from repro.core import (
    GilbertElliotSource,
    available_backends,
    cache_stats,
    estimate_alpha,
    get_backend,
    grid_plan,
    simulate_batch,
)

args = sys.argv[1:]
backend = None
if "--backend" in args:
    i = args.index("--backend")
    if i + 1 >= len(args):
        sys.exit("usage: parameter_sweep.py [n] [rounds] [--backend NAME]")
    backend = args[i + 1]
    del args[i : i + 2]
    if backend not in available_backends():
        sys.exit(f"backend {backend!r} unavailable; have "
                 f"{available_backends()}")
n = int(args[0]) if len(args) > 0 else 64
rounds = int(args[1]) if len(args) > 1 else 60

eff_backend = backend or get_backend().name
print(f"kernel backend: {eff_backend} "
      f"(array namespace {get_backend(eff_backend).xp.__name__}, "
      f"grid fusion {'on' if eff_backend == 'jax' else 'off'})")

# several independent GE traces of the Fig.-1-calibrated cluster
# (traces are the Monte-Carlo axis: load-only sim results are
# seed-invariant and the engine broadcasts across the seed axis,
# see simulate_batch's docstring)
sources = [
    GilbertElliotSource(n=n, seed=100 + k, p_ns=0.035, p_sn=0.85,
                        slow_factor=6.0, jitter=0.05)
    for k in range(5)
]
traces = np.stack([src.sample_delays(rounds) for src in sources])
alpha = estimate_alpha(sources[0])

grids = {
    "gc": [("gc", {"s": s}) for s in (4, 8, 12, 15, 20)],
    "sr-sgc": [("sr-sgc", {"B": B, "W": B + 1, "lam": lam})
               for B in (1, 2) for lam in (4, 8, 16, 23)],
    "m-sgc": [("m-sgc", {"B": B, "W": B + 1, "lam": lam})
              for B in (1, 2) for lam in (4, 8, 16, 27)],
}

t0 = time.perf_counter()
for scheme, specs in grids.items():
    compiles0 = cache_stats()["compiles"]
    results = simulate_batch(specs, traces, alpha=alpha, strict=False,
                             backend=backend)
    compiled = cache_stats()["compiles"] - compiles0
    best_params, best_t = None, float("inf")
    for i, (_, params) in enumerate(specs):
        runs = [r for r in results[i].ravel() if r is not None]
        if not runs:
            continue
        per_job = float(np.mean([r.total_time / len(r.job_done_round)
                                 for r in runs]))
        if per_job < best_t:
            best_params, best_t = params, per_job
    print(f"{scheme:8s} best={best_params} per_job={best_t:.3f}s "
          f"({len(specs) * traces.shape[0]} sims)")
    if eff_backend == "jax":
        plan = grid_plan(specs, traces)
        sizes = sorted((len(b["specs"]) for b in plan["buckets"]),
                       reverse=True)
        print(f"         {len(specs)} specs -> {len(plan['buckets'])} "
              f"shape buckets {sizes} "
              f"(+{len(plan['fallback'])} on the numpy engine, "
              f"{len(plan['infeasible'])} infeasible), "
              f"{compiled} runner compile(s) this sweep")
elapsed = time.perf_counter() - t0
total = sum(len(g) for g in grids.values()) * traces.shape[0]
print(f"swept {total} simulations (n={n}, {rounds} rounds) in {elapsed:.2f}s")
