#!/usr/bin/env python3
"""Readings that set the limits of a cell's output comparison.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...]

For each seed, the cell's traffic is drawn as a run with that seed
draws it, and the plain reference is compared with itself computed
otherwise, by the cell's own comparison:

- the control: the reference in the nearest precision below the one
  the configuration states (float32 timing math for the simulator
  cells, fp8 matmul operands for bfloat16 training);
- for training cells, faults of the timed path planted in the
  reference put in the program's place: half of the batch left out,
  the mean taken over the rest.  (A step that returns its state
  unchanged reads 1 on the gradient and the change without a run.)
  The steady-state step that training cells check after the window is
  read as one step from the reference's own state after its first
  steps.

The benchmark's own runs never run this; each reading is printed as
one JSON line.  The program's own readings are the ``checks`` of the
cell's runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


class _Sim:
    """A reference simulation in the shape of the program's result."""

    def __init__(self, res: dict):
        import numpy as np

        self.round_times = res["rt"].astype(np.float64)
        self.effective_pattern = res["history"]
        self.waitouts = int(res["waited"].sum())
        self.job_done_round = {j: int(r) for j, r in
                               enumerate(res["done_round"]) if j}


def sweep_readings(cell, seed: int) -> dict:
    import numpy as np

    from bench import ge
    from bench.drivers.sweep import sim_checks
    from bench.refs import sgc_sim

    cfg, tr = cell.config, cell.traffic
    n, J = cfg["workers"], cfg["jobs"]
    specs = [(s["scheme"], s["params"]) for s in cfg["table1"]]
    T = max(sgc_sim.scheme_shape(nm, n, p)["T"] for nm, p in specs)
    rng = np.random.default_rng(ge.seed_words(seed, 1))
    traces = ge.delays(rng, tr["traces_per_call"], J + T, n, **cfg["ge"])
    pick = np.random.default_rng(ge.seed_words(seed, 2))
    pairs = []
    for _ in range(tr["compare"]):
        si = int(pick.integers(len(specs)))
        ti = int(pick.integers(traces.shape[0]))
        name, params = specs[si]
        low = sgc_sim.simulate(name, params, traces[ti], mu=cfg["mu"],
                               alpha=cfg["alpha"], J=J, dtype=np.float32)
        pairs.append((_Sim(low), traces[ti], name, params, J))
    return {"control_f32": {c["name"]: c["value"] for c in
                            sim_checks(pairs, cfg, tr["limits"])}}


def train_readings(cell, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.drivers import train
    from bench.refs import qwen2 as ref

    cfg, tr = cell.config, cell.traffic
    steps = train.first_steps(seed, tr["jobs_per_call"], tr["models"])
    wseed = train.data_seed(seed, 1000)
    want = train.reference_readings(cfg, tr, wseed, steps)
    out = {"reference": {"losses": want["losses"]}}
    low = train.reference_readings(cfg, tr, wseed, steps, quant="fp8")
    out["control_fp8"] = train.compare(low, want)
    orig = ref.tokens

    def half(seed_, job, batch, seq, vocab):
        toks = orig(seed_, job, batch, seq, vocab)
        return jnp.concatenate([toks[: batch // 2]] * 2)

    ref.tokens = half
    try:
        out["half_batch"] = train.compare(
            train.reference_readings(cfg, tr, wseed, steps), want)
    finally:
        ref.tokens = orig

    # the steady-state step, from the reference's own state after its
    # first steps (the program's state is not at hand here)
    trainer = ref.Trainer(cfg, ref.init_weights(cfg, wseed),
                          blocks=tr["ref_blocks"])
    for dseed, job in steps:
        trainer.step(ref.tokens(dseed, job, tr["batch"], tr["seq_len"],
                                cfg["vocab_size"]))
    state = jax.device_get(dict(params=trainer.params, m=trainer.m,
                                v=trainer.v, step=trainer.t))
    del trainer
    at = (train.data_seed(seed, 99), 1)
    want = train.late_reference(cfg, tr, state, at)
    for name, kw in (("control_fp8", dict(quant="fp8")),
                     ("half_batch", dict(toks=half(
                         *at, tr["batch"], tr["seq_len"],
                         cfg["vocab_size"])))):
        low = train.late_reference(cfg, tr, state, at, **kw)
        out[name].update(train.compare_late(low, want))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.workload)
    harness.setup_runtime()
    kind = cell.traffic["driver"]
    fn = {"sweep": sweep_readings, "train": train_readings}[kind]
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **fn(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
