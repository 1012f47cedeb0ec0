"""Training launcher.

Two modes:
  * ``--demo``: CPU-scale multi-model coded training (the paper's §4.2
    experiment): M models trained interleaved under GC / SR-SGC / M-SGC
    with a Gilbert-Elliott straggler source, reporting per-scheme
    simulated runtimes and real training losses.
  * ``--arch/--shape``: single-model uncoded or GC-coded training steps
    on the local mesh (CPU devices; on a real pod, the same code path
    with ``make_production_mesh`` shards over 256/512 chips).

Example:
  PYTHONPATH=src python -m repro.launch.train --demo --scheme m-sgc --jobs 60
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 3 --coded
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.configs import ARCHS, get_smoke
from repro.core import GilbertElliotSource, make_scheme
from repro.core.gc import make_gradient_code
from repro.data import gc_chunked_batch, token_batch
from repro.launch.cache import confine_tpu_logs, enable_compile_cache
from repro.train import CodedTrainingDriver
from repro.train.coded import (
    gc_round_weights,
    init_train_state,
    make_coded_train_step,
    make_train_step,
)


def run_demo(scheme_name: str, jobs: int, n: int, models: int, seed: int):
    kw = {
        "gc": dict(s=max(1, n // 8)),
        "sr-sgc": dict(B=1, W=2, lam=max(2, n // 4)),
        "m-sgc": dict(B=1, W=2, lam=max(2, n // 4)),
        "uncoded": {},
    }[scheme_name]
    sch = make_scheme(scheme_name, n, jobs, **kw)
    drv = CodedTrainingDriver(
        scheme=sch, num_models=models, batch_size=256, lr=5e-3, seed=seed
    )
    delays = GilbertElliotSource(n=n, seed=seed).sample_delays(jobs + sch.T + 1)
    t0 = time.perf_counter()
    clock = drv.run(jobs, delays)
    wall = time.perf_counter() - t0
    final = [drv.losses[m][-1] for m in range(models)]
    print(
        f"scheme={scheme_name:8s} load={sch.normalized_load:.4f} T={sch.T} "
        f"simulated_runtime={clock:8.1f}s wall={wall:5.1f}s "
        f"final_losses={[f'{l:.3f}' for l in final]}"
    )
    return clock


def run_arch(arch: str, steps: int, coded: bool, seed: int):
    cfg = get_smoke(arch)
    params, opt = init_train_state(cfg, jax.random.PRNGKey(seed))
    if coded:
        n, s = 4, 1
        code = make_gradient_code(n, s)
        step = jax.jit(make_coded_train_step(cfg, n, s))
        rng = np.random.default_rng(seed)
        for i in range(steps):
            batch = token_batch(seed, i, 8, 64, cfg.vocab_size)
            coded_batch = gc_chunked_batch(batch, n, s)
            # random straggler each round (tolerates s=1)
            surv = sorted(
                rng.choice(n, size=n - 1, replace=False).tolist()
            )
            w = gc_round_weights(code, surv)
            params, opt, m = step(params, opt, coded_batch, w)
            print(f"step {i}: loss={float(m['loss']):.4f} survivors={surv}")
    else:
        step = jax.jit(make_train_step(cfg))
        for i in range(steps):
            batch = token_batch(seed, i, 8, 64, cfg.vocab_size)
            params, opt, m = step(params, opt, batch)
            print(f"step {i}: loss={float(m['loss']):.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--scheme", default="m-sgc",
                    choices=["gc", "sr-sgc", "m-sgc", "uncoded"])
    ap.add_argument("--jobs", type=int, default=40)
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--models", type=int, default=4)
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--coded", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    confine_tpu_logs()
    enable_compile_cache()

    if args.demo:
        run_demo(args.scheme, args.jobs, args.workers, args.models, args.seed)
    elif args.arch:
        run_arch(args.arch, args.steps, args.coded, args.seed)
    else:
        raise SystemExit("pass --demo or --arch")


if __name__ == "__main__":
    main()
