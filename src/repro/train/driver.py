"""Multi-model interleaved coded-training driver (paper §4.2 / App. I).

Trains M models concurrently: job ``M*i + j`` is step-i of model-j
(Remark 2.1), so a scheme with delay T <= M-1 never stalls an update.
The driver runs the full master protocol with real numerics:

  round-t:  tasks = scheme.assign(t)
            stragglers <- delay profile + mu-rule + Remark-2.3 wait-out
            non-straggler tasks execute REAL chunk gradients (at the
            parameter snapshot of the job's issue round)
            scheme.collect(t) -> decoded gradient -> ADAM update

Decode exactness (decoded == full-batch gradient at the snapshot) is
asserted on demand in tests; the wall clock is simulated from the delay
profile exactly like ``core.simulator`` so runtimes are comparable
across schemes while the training itself is genuine.

Two drivers live here:

* :class:`CodedTrainingDriver` — the descriptor-path reference: it
  materializes per-round ``MiniTask`` lists, executes each mini-task's
  chunk gradients eagerly, and decodes via ``scheme.collect``.
* :class:`VectorizedCodedTrainer` — the kernel-path production loop:
  rounds advance the lockstep kernels' 1-cell ``SchemeState``
  (``scheme.step``), decodable jobs come back with solved coefficients
  from ``scheme.collect_decodes``, and each decode is ONE jitted
  ``make_coded_train_step`` call on the (n, slots) replicated batch
  view — no descriptors, no per-chunk python loop, no parameter
  snapshots (Remark 2.1: T <= M-1 serializes each model's jobs, so
  decode-time params equal issue-time params by construction).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.schemes import MSGCScheme, Scheme
from repro.data import chunk_boundaries, classification_batch
from repro.optim import adamw_init, adamw_update


# ---------------------------------------------------------------------------
# A small model abstraction for the driver (the paper trains CNNs; we use
# an MLP classifier so CPU rounds stay fast — the protocol is identical).
# ---------------------------------------------------------------------------


@dataclass
class MLPModel:
    dim: int = 64
    hidden: int = 128
    classes: int = 10

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (self.dim, self.hidden)) * self.dim ** -0.5,
            "b1": jnp.zeros((self.hidden,)),
            "w2": jax.random.normal(k2, (self.hidden, self.classes))
            * self.hidden ** -0.5,
            "b2": jnp.zeros((self.classes,)),
        }

    def loss_sum(self, params, x, y):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()

    def loss_mean(self, params, x, y):
        return self.loss_sum(params, x, y) / x.shape[0]


@dataclass
class CodedTrainingDriver:
    scheme: Scheme
    num_models: int
    model: MLPModel = field(default_factory=MLPModel)
    batch_size: int = 256
    lr: float = 1e-2
    mu: float = 1.0
    alpha: float = 8.0
    seed: int = 0
    data_fn: Callable | None = None

    def __post_init__(self):
        if self.scheme.T > self.num_models - 1:
            raise ValueError(
                f"delay T={self.scheme.T} needs at least T+1="
                f"{self.scheme.T + 1} interleaved models (Remark 2.1)"
            )
        key = jax.random.PRNGKey(self.seed)
        keys = jax.random.split(key, self.num_models)
        self.params = [self.model.init(k) for k in keys]
        self.opt = [adamw_init(p) for p in self.params]
        self._grad_sum = jax.jit(jax.grad(self.model.loss_sum))
        self._loss = jax.jit(self.model.loss_mean)
        self._snapshots: dict[int, list] = {}     # job -> params snapshot
        self._chunk_grads: dict[tuple, object] = {}
        self._results: dict[tuple, object] = {}
        self.losses: dict[int, list] = {m: [] for m in range(self.num_models)}
        self.job_done_time: dict[int, float] = {}
        self.compute_units = 0.0                  # normalized-load ledger

    # -- data ------------------------------------------------------------
    def _job_batch(self, job: int):
        fn = self.data_fn or classification_batch
        return fn(self.seed, job, self.batch_size, self.model.dim,
                  self.model.classes)

    def _chunks(self):
        if isinstance(self.scheme, MSGCScheme):
            fr = [
                self.scheme.chunk_fraction(c)
                for c in range(self.scheme.num_chunks)
            ]
            return chunk_boundaries(self.batch_size, fr)
        n = self.scheme.n
        return chunk_boundaries(self.batch_size, [1.0 / n] * n)

    def _chunk_grad(self, job: int, chunk: int):
        key = (job, chunk)
        if key not in self._chunk_grads:
            x, y = self._job_batch(job)
            lo, hi = self._chunks()[chunk]
            snap = self._snapshots[job]
            self._chunk_grads[key] = self._grad_sum(snap, x[lo:hi], y[lo:hi])
        return self._chunk_grads[key]

    def _task_load(self, mt) -> float:
        """Normalized data fraction a mini-task costs its worker."""
        bounds = self._chunks()
        if mt.kind == "ell":
            sup = np.flatnonzero(self.scheme.code.encode_matrix[mt.worker])
            return sum(bounds[c][1] - bounds[c][0] for c in sup) / self.batch_size
        if mt.kind in ("d1", "all"):
            lo, hi = bounds[mt.chunk]
            return (hi - lo) / self.batch_size
        if mt.kind == "d2":
            sch = self.scheme
            base = (sch.W - 1) * sch.n + mt.chunk * sch.n
            loc = np.flatnonzero(sch.code.encode_matrix[mt.worker])
            return sum(
                bounds[base + c][1] - bounds[base + c][0] for c in loc
            ) / self.batch_size
        return 0.0

    # -- protocol ----------------------------------------------------------
    def run(self, J: int, delays: np.ndarray):
        """Run J jobs; delays: (>= J+T rounds, n) reference profile."""
        from repro.core.straggler import ConformanceGate

        sch = self.scheme
        n = sch.n
        rounds = J + sch.T
        extra = (sch.normalized_load - 1.0 / n) * self.alpha
        gate = ConformanceGate(sch.design_model, n)
        clock = 0.0

        for t in range(1, rounds + 1):
            # snapshot params for the job issued this round
            if 1 <= t <= J:
                midx = (t - 1) % self.num_models
                self._snapshots[t] = jax.tree.map(jnp.copy, self.params[midx])

            tasks = sch.assign(t)
            times = delays[t - 1] + extra
            kappa = float(times.min())
            cutoff = (1.0 + self.mu) * kappa
            cand = times > cutoff
            if not cand.any():
                gate.force(cand)
                clock += float(min(cutoff, times.max()))
            else:
                cand, waited = gate.admit_partial(cand, times)  # Remark 2.3
                base = float(min(cutoff, times.max())) if cand.any() else cutoff
                clock += float(max(times[waited].max(), base)) if waited else base

            self._execute(tasks, cand)
            sch.observe(t, cand)
            for jd in sch.collect(t):
                self._apply_update(jd)
                self.job_done_time[jd.job] = clock
        missing = [j for j in range(1, J + 1) if j not in self.job_done_time]
        assert not missing, f"jobs unfinished: {missing[:4]}"
        return clock

    # -- numeric task execution ------------------------------------------
    def _execute(self, tasks, stragglers):
        for mt in tasks:
            if mt.trivial:
                continue
            # assigned work costs compute whether or not the worker
            # straggles (cancelled tasks still burned the cycles)
            self.compute_units += self._task_load(mt)
            if stragglers[mt.worker]:
                continue
            if mt.kind == "ell":
                row = self.scheme.code.encode_matrix[mt.worker]
                sup = np.flatnonzero(row)
                val = _tree_weighted_sum(
                    [self._chunk_grad(mt.job, int(c)) for c in sup],
                    row[sup],
                )
                self._results[("ell", mt.job, mt.worker)] = val
            elif mt.kind == "d1":
                self._results[("d1", mt.job, mt.chunk)] = self._chunk_grad(
                    mt.job, mt.chunk
                )
            elif mt.kind == "d2":
                sch = self.scheme
                m = mt.chunk
                base = (sch.W - 1) * sch.n + m * sch.n
                coeffs = sch.code.encode_matrix[mt.worker]
                loc = np.flatnonzero(coeffs)
                val = _tree_weighted_sum(
                    [self._chunk_grad(mt.job, int(base + c)) for c in loc],
                    coeffs[loc],
                )
                self._results[("d2", mt.job, m, mt.worker)] = val
            elif mt.kind == "all":
                self._results[("d1", mt.job, mt.chunk)] = self._chunk_grad(
                    mt.job, mt.chunk
                )

    def decode_gradient(self, jd):
        sch = self.scheme
        if jd.ell_weights:
            parts = [self._results[("ell", jd.job, i)] for i in jd.ell_weights]
            return _tree_weighted_sum(parts, list(jd.ell_weights.values()))
        if isinstance(sch, MSGCScheme):
            parts = [
                self._results[("d1", jd.job, sch.d1_chunk(i, l))]
                for i in range(sch.n)
                for l in range(sch.W - 1)
            ]
            weights = [1.0] * len(parts)
            for m, ws in jd.group_weights.items():
                for i, w in ws.items():
                    parts.append(self._results[("d2", jd.job, m, i)])
                    weights.append(w)
            return _tree_weighted_sum(parts, weights)
        parts = [self._results[("d1", jd.job, c)] for c in range(sch.n)]
        return _tree_weighted_sum(parts, [1.0] * sch.n)

    def _apply_update(self, jd):
        g_sum = self.decode_gradient(jd)
        g = jax.tree.map(lambda x: x / self.batch_size, g_sum)
        midx = (jd.job - 1) % self.num_models
        self.params[midx], self.opt[midx] = adamw_update(
            self.params[midx], g, self.opt[midx], lr=self.lr
        )
        x, y = self._job_batch(jd.job)
        self.losses[midx].append(float(self._loss(self.params[midx], x, y)))

    # -- validation hook ----------------------------------------------------
    def full_gradient(self, job: int):
        """Direct full-batch gradient at the job's snapshot (oracle)."""
        x, y = self._job_batch(job)
        return self._grad_sum(self._snapshots[job], x, y)


def run_adaptive(
    num_models: int,
    J: int,
    delays: np.ndarray,
    *,
    scheme_name: str = "m-sgc",
    t_probe: int = 20,
    batch_size: int = 256,
    lr: float = 1e-2,
    mu: float = 1.0,
    alpha: float = 8.0,
    seed: int = 0,
    grid=None,
):
    """App. K.2 / Fig. 18: start training UNCODED, after ``t_probe``
    rounds select coding parameters from the observed delay profile and
    switch to the coded scheme for the remaining jobs.

    Returns (total_clock, probe_clock, selected_params, driver) — model
    parameters carry over across the switch, so no training progress is
    lost to the probe phase.
    """
    from repro.core.schemes import make_scheme
    from repro.core.simulator import select_parameters

    n = delays.shape[1]
    # phase 1: uncoded probe (records the reference delay profile)
    probe_sch = make_scheme("uncoded", n, t_probe)
    drv = CodedTrainingDriver(
        scheme=probe_sch, num_models=num_models, batch_size=batch_size,
        lr=lr, mu=mu, alpha=alpha, seed=seed,
    )
    probe_clock = drv.run(t_probe, delays[:t_probe])

    # phase 2: App-J selection on the probe profile
    cand = select_parameters(
        scheme_name, n, delays[:t_probe], mu=mu, alpha=alpha, grid=grid,
    )

    # phase 3: coded training continues with the SAME model states
    rest = J - t_probe
    coded_sch = make_scheme(scheme_name, n, rest, **cand.params)
    drv2 = CodedTrainingDriver(
        scheme=coded_sch, num_models=num_models, batch_size=batch_size,
        lr=lr, mu=mu, alpha=alpha, seed=seed + 1,
    )
    drv2.params = drv.params          # carry over model states
    drv2.opt = drv.opt
    coded_clock = drv2.run(rest, delays[t_probe : t_probe + rest + coded_sch.T])
    return probe_clock + coded_clock, probe_clock, cand.params, drv2


@dataclass
class VectorizedCodedTrainer:
    """Kernel-path multi-model coded trainer (module docstring).

    Trains ``num_models`` transformer LMs (``cfg``) concurrently on
    deterministic ``token_batch`` streams; job-t belongs to model
    ``(t-1) % num_models``.  The straggler gate (mu-rule + Remark-2.3
    wait-out) and the simulated wall clock match ``core.simulator`` /
    :class:`CodedTrainingDriver` expression-for-expression, so clocks
    are comparable across all three.  ``batch_size`` must be divisible
    by ``scheme.chunk_grid()[0]``.
    """

    scheme: Scheme
    cfg: object                       # models.config.ModelConfig
    num_models: int
    batch_size: int = 32
    seq_len: int = 16
    lr: float = 1e-4
    mu: float = 1.0
    alpha: float = 8.0
    seed: int = 0

    def __post_init__(self):
        from .coded import init_train_state, make_coded_train_step

        sch = self.scheme
        if sch.T > self.num_models - 1:
            raise ValueError(
                f"delay T={sch.T} needs at least T+1={sch.T + 1} "
                "interleaved models (Remark 2.1)"
            )
        self.num_chunks, self.slots = sch.chunk_grid()
        if self.batch_size % self.num_chunks:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"num_chunks {self.num_chunks} ({sch.name})"
            )
        keys = jax.random.split(
            jax.random.PRNGKey(self.seed), self.num_models
        )
        states = [init_train_state(self.cfg, k) for k in keys]
        self.params = [p for p, _ in states]
        self.opt = [o for _, o in states]
        # params and optimizer state are donated: the update then reuses
        # their buffers, so a model costs one copy of its state on the
        # device instead of two at the step's peak
        self._step = jax.jit(
            make_coded_train_step(
                self.cfg, sch.n, getattr(sch, "s", 0),
                lr=self.lr, num_chunks=self.num_chunks,
            ),
            donate_argnums=(0, 1),
        )
        self.losses: dict[int, list] = {m: [] for m in range(self.num_models)}
        self.job_done_time: dict[int, float] = {}

    def _job_batch(self, job: int):
        from repro.data import token_batch

        return token_batch(
            self.seed, job, self.batch_size, self.seq_len,
            self.cfg.vocab_size,
        )

    def _apply(self, jd) -> None:
        """Decode job ``jd`` as one jitted coded step: gather the job's
        batch into the (n, slots) view, feed the scheme's solved decode
        weights, update that model in place."""
        from repro.data import coded_slot_batch

        sch = self.scheme
        coded = coded_slot_batch(
            self._job_batch(jd.job), sch.chunk_slots(jd.job),
            self.num_chunks,
        )
        w = jnp.asarray(sch.decode_weights(jd))
        midx = (jd.job - 1) % self.num_models
        self.params[midx], self.opt[midx], metrics = self._step(
            self.params[midx], self.opt[midx], coded, w
        )
        self.losses[midx].append(float(metrics["loss"]))

    def run(self, J: int, delays: np.ndarray) -> float:
        """Run J jobs against the (>= J+T rounds, n) delay profile;
        returns the simulated wall clock."""
        from repro.core.straggler import ConformanceGate

        sch = self.scheme
        n = sch.n
        rounds = J + sch.T
        extra = (sch.normalized_load - 1.0 / n) * self.alpha
        gate = ConformanceGate(sch.design_model, n)
        clock = 0.0

        for t in range(1, rounds + 1):
            times = delays[t - 1] + extra
            kappa = float(times.min())
            cutoff = (1.0 + self.mu) * kappa
            cand = times > cutoff
            if not cand.any():
                gate.force(cand)
                clock += float(min(cutoff, times.max()))
            else:
                cand, waited = gate.admit_partial(cand, times)  # Remark 2.3
                base = float(min(cutoff, times.max())) if cand.any() else cutoff
                clock += float(max(times[waited].max(), base)) if waited else base

            sch.step(t, cand)
            for jd in sch.collect_decodes(t):
                self._apply(jd)
                self.job_done_time[jd.job] = clock
        missing = [j for j in range(1, J + 1) if j not in self.job_done_time]
        assert not missing, f"jobs unfinished: {missing[:4]}"
        return clock


def _tree_weighted_sum(trees, weights):
    out = jax.tree.map(lambda x: x * float(weights[0]), trees[0])
    for tr, w in zip(trees[1:], weights[1:]):
        out = jax.tree.map(lambda a, b: a + float(w) * b, out, tr)
    return out
