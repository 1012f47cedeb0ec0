"""Monte-Carlo scheme sweep: the Table-1 specs over fresh GE traces,
one ``simulate_batch(backend="jax", fuse=True)`` call at a time.

A call replays ``traces_per_call`` traces under every spec; its work
is specs x traces x (J + T) lane-rounds.  The traces come from a pool
drawn from the seed in set-up, cycled call by call.  ``keep_per_call``
(spec, trace) results of each call, drawn from the seed, are kept;
after the window up to ``compare`` of them, drawn from the seed, are
compared with the plain reference
(``bench/refs/sgc_sim.py``), as is the precision the configuration
states.
"""

from __future__ import annotations

import numpy as np

from bench import ge
from bench.harness import finite
from bench.refs import sgc_sim


def sim_checks(pairs, cfg, limits, dtype=np.float64) -> list[dict]:
    """Compare kept program results ``(result, trace, name, params, J)``
    with the reference run at ``dtype``: per-round durations (largest
    relative gap), effective straggler pattern, wait-out count and the
    round each job decoded in (counts of differences)."""
    gap = 0.0
    missing = pattern = waitouts = done = 0
    for res, trace, name, params, J in pairs:
        if res is None:
            missing += 1
            continue
        ref = sgc_sim.simulate(name, params, trace, mu=cfg["mu"],
                               alpha=cfg["alpha"], J=J, dtype=dtype)
        rt = np.asarray(res.round_times, dtype=np.float64)
        want = ref["rt"].astype(np.float64)
        if rt.shape != want.shape:
            missing += 1
            continue
        gap = max(gap, float(np.max(np.abs(rt - want) / want)))
        pattern += int((np.asarray(res.effective_pattern)
                        != ref["history"]).sum())
        waitouts += int(res.waitouts != int(ref["waited"].sum()))
        got = np.array([res.job_done_round.get(j, 0)
                        for j in range(1, J + 1)])
        done += int((got != ref["done_round"][1:]).sum())
    nums = {"round_time_rel_gap": gap, "results_missing": missing,
            "pattern_bits_differ": pattern, "waitouts_differ": waitouts,
            "done_rounds_differ": done, "compared": len(pairs)}
    return [{"name": k, "value": finite(v), "limit": limits[k]}
            for k, v in nums.items() if k in limits]


class Driver:
    def __init__(self, ctx):
        from repro.core import simulate_batch

        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.tr, self.seed = cfg, tr, ctx.seed
        self.simulate_batch = simulate_batch
        self.n, self.J = cfg["workers"], cfg["jobs"]
        self.specs = [(s["scheme"], dict(s["params"])) for s in cfg["table1"]]
        self.T = [sgc_sim.scheme_shape(nm, self.n, p)["T"]
                  for nm, p in self.specs]
        rounds = self.J + max(self.T)
        rng = np.random.default_rng(ge.seed_words(ctx.seed, 1))
        self.pool = [ge.delays(rng, tr["traces_per_call"], rounds, self.n,
                               **cfg["ge"])
                     for _ in range(tr["pool"])]
        self.pick = np.random.default_rng(ge.seed_words(ctx.seed, 2))
        self.kept: list = []
        self.calls = 0
        self.lane_rounds = 0
        self._run(self.pool[0])            # compiles every bucket

    def _run(self, traces):
        c = self.cfg
        return self.simulate_batch(self.specs, traces, mu=c["mu"],
                                   alpha=c["alpha"], J=self.J,
                                   waitout=c["waitout"], backend="jax",
                                   fuse=True)

    def call(self) -> None:
        traces = self.pool[self.calls % len(self.pool)]
        lanes = len(self.specs) * traces.shape[0]
        picks = self.pick.choice(lanes, size=self.tr["keep_per_call"],
                                 replace=False)
        self.calls += 1
        res = self._run(traces)
        for lane in picks:
            si, ti = divmod(int(lane), traces.shape[0])
            name, params = self.specs[si]
            self.kept.append((res[si, 0, ti], traces[ti], name, params,
                              self.J))
        self.lane_rounds += traces.shape[0] * sum(self.J + t for t in self.T)

    def end_to_end(self, window_s: float) -> dict:
        return {"sweep_rounds_per_s": self.lane_rounds / window_s}

    def release(self) -> None:
        self.pool = None

    def check(self) -> list[dict]:
        k = min(self.tr["compare"], len(self.kept))
        idx = np.random.default_rng(ge.seed_words(self.seed, 3)).choice(
            len(self.kept), size=k, replace=False)
        return sim_checks([self.kept[i] for i in sorted(idx)], self.cfg,
                          self.tr["limits"])
