"""Coded training: ``VectorizedCodedTrainer.run`` over GE straggler
delays, one call per ``jobs_per_call`` jobs.

Set-up builds one trainer (its compiled coded step and the models'
state), gives it weights made on the device from the seed, and drives
it through its first call, the window's own call on the window's own
feed.  Model 0's first three steps in that call are what the
comparison checks against the plain reference (``bench/refs/qwen2.py``):
each step's loss, the first gradient as the optimizer holds it after
one step, and the change of the parameters after three.  The same
trainer then runs the window.  Each call gives the trainer a fresh
scheme of the same parameters, a fresh data seed (so every job's rows
differ) and fresh GE delays.

After the window, one more call of the same kind runs untimed, and its
first step of model 0 is compared too: the reference takes one step
from the state the program held before it (copied to the host), so a
fault that shows only after many steps or across calls is caught.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import numpy as np

from bench import ge
from bench.harness import finite
from bench.refs import qwen2 as ref


def program_config(cfg: dict):
    """The program's model configuration at the widths of ``cfg``."""
    from repro.configs import get_config

    return get_config(cfg["program_arch"]).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def data_seed(seed: int, call: int) -> int:
    """A 31-bit data seed per (run seed, call)."""
    return int(np.random.default_rng(ge.seed_words(seed, 4, call))
               .integers(2**31 - 1))


def first_steps(seed: int, jobs_per_call: int, models: int) -> list:
    """(data seed, job) of model 0's first three steps: jobs 1, 1 + M,
    ... of the first calls."""
    out, call = [], 0
    while len(out) < 3:
        out += [(data_seed(seed, call), j)
                for j in range(1, jobs_per_call + 1, models)]
        call += 1
    return out[:3]


def _diff_norms(new, old, keep: float = 1.0, scale: float = 1.0) -> dict:
    """Leaf norms of ``(new - keep * old) * scale``, in float32."""
    return ref.leaf_norms(jax.tree.map(
        lambda a, b: (a.astype("float32") - keep * b.astype("float32"))
        * scale, new, old))


class _Watch:
    """Stands in for the trainer's compiled step and passes every call
    through.  At model 0's steps named in ``at`` it calls the hook with
    the state before the step (still on the device, not yet donated)
    and the state after."""

    def __init__(self, step, trainer):
        self.step, self.trainer = step, trainer
        self.count = 0
        self.at: dict = {}

    def __call__(self, params, opt, coded, w):
        if params is not self.trainer.params[0]:
            return self.step(params, opt, coded, w)
        self.count += 1
        hook = self.at.pop(self.count, None)
        before = hook.before(params, opt) if hook else None
        out = self.step(params, opt, coded, w)
        if hook:
            # the step's temporaries fill the chip until it ends
            hook.after(before, *jax.block_until_ready(out[:2]))
        return out


class Driver:
    def __init__(self, ctx):
        from repro.core import make_scheme
        from repro.train import VectorizedCodedTrainer

        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.tr, self.seed = cfg, tr, ctx.seed
        self.make_scheme = make_scheme
        self.n, self.M = cfg["workers"], tr["models"]
        self.batch, self.seq = tr["batch"], tr["seq_len"]
        sch = make_scheme(tr["scheme"], self.n, self.M, **tr["params"])
        self.T = sch.T
        self.pcfg = program_config(cfg)
        opt = cfg["optimizer"]
        self.b1 = opt["b1"]
        if opt["weight_decay"] or (opt["b1"], opt["b2"], opt["eps"]) != (
                0.9, 0.999, 1e-8):
            raise ValueError("the trainer's AdamW has b1 0.9, b2 0.999, "
                             "eps 1e-8 and no weight decay")
        self.trainer = VectorizedCodedTrainer(
            scheme=sch, cfg=self.pcfg, num_models=self.M,
            batch_size=self.batch, seq_len=self.seq, lr=opt["lr"],
            mu=cfg["mu"], alpha=cfg["alpha"], seed=0)
        want = jax.tree.map(lambda x: (x.shape, x.dtype),
                            self.trainer.params[0])
        self.weight_seeds = [data_seed(ctx.seed, 1000 + m)
                             for m in range(self.M)]
        for m in range(self.M):
            self.trainer.params[m] = None
            self.trainer.params[m] = ref.init_weights(
                cfg, self.weight_seeds[m])
        got = jax.tree.map(lambda x: (x.shape, x.dtype),
                           self.trainer.params[0])
        if got != want:
            raise ValueError("benchmark weights do not match the "
                             "program's parameter layout")
        self.watch = _Watch(self.trainer._step, self.trainer)
        self.trainer._step = self.watch
        self.rng = np.random.default_rng(ge.seed_words(ctx.seed, 5))
        self.calls = 0
        self.jobs = 0
        self.steps = first_steps(ctx.seed, tr["jobs_per_call"], self.M)
        self.first = _FirstSteps(self)
        while self.watch.count < 3:
            self.call()
        self.prog_losses = [float(x) for x in self.trainer.losses[0][:3]]
        self.window_jobs0 = self.jobs
        self.errors: list[str] = []

    def call(self) -> None:
        """One call of the trainer: ``jobs_per_call`` jobs on a fresh
        scheme, data seed and GE delays."""
        tr, jobs = self.trainer, self.tr["jobs_per_call"]
        tr.scheme = self.make_scheme(self.tr["scheme"], self.n, jobs,
                                     **self.tr["params"])
        tr.seed = data_seed(self.seed, self.calls)
        before = sum(len(v) for v in tr.losses.values())
        self.calls += 1
        tr.run(jobs, ge.delays(self.rng, 1, jobs + self.T, self.n,
                               **self.cfg["ge"])[0])
        self.jobs += sum(len(v) for v in tr.losses.values()) - before

    @property
    def window_jobs(self) -> int:
        return self.jobs - self.window_jobs0

    def end_to_end(self, window_s: float) -> dict:
        return {"train_tokens_per_s":
                self.window_jobs * self.batch * self.seq / window_s}

    def release(self) -> None:
        """One more call, untimed, for the steady-state check; then the
        program's state is freed."""
        self.late = _LateStep(self)
        try:
            self.call()
            if self.late.read is None:
                self.errors.append("the check's call took no step of "
                                   "model 0")
            else:
                self.late.read["loss"] = self.late.loss()
        except Exception as e:  # the check then reads the step as missing
            self.errors.append(f"{type(e).__name__}: {e}")
        # the trainer and the watch refer to each other: break the
        # cycle, so that the state leaves the chip before the reference
        self.watch.trainer = None
        self.trainer = self.watch = None
        gc.collect()
        jax.clear_caches()

    def check(self) -> list[dict]:
        readings = dict(losses=self.prog_losses, grad=self.first.grad,
                        change=self.first.change, steps=self.first.steps)
        checks = train_checks(self.cfg, self.tr, self.weight_seeds[0],
                              self.steps, readings)
        late = self.late
        if not self.errors:
            want = late_reference(self.cfg, self.tr, late.state,
                                  late.tokens_at)
            nums = compare_late(late.read, want)
        else:
            nums = {k: float("inf") for k in LATE_NUMBERS}
        return checks + [{"name": k, "value": finite(v),
                          "limit": self.tr["limits"][k]}
                         for k, v in nums.items() if k in self.tr["limits"]]


class _FirstSteps:
    """Model 0's first gradient (from the optimizer's first moment
    after step 1, which holds ``(1 - b1)`` times it) and the change of
    its parameters from the initial weights after step 3."""

    def __init__(self, drv):
        self.drv = drv
        self.grad = self.change = self.steps = None
        drv.watch.at[1] = self
        drv.watch.at[3] = self

    def before(self, params, opt):
        return None

    def after(self, _, params, opt):
        drv = self.drv
        if self.grad is None:
            self.grad = {k: v / (1.0 - drv.b1)
                         for k, v in ref.leaf_norms(opt.m).items()}
            return
        p0 = ref.init_weights(drv.cfg, drv.weight_seeds[0])
        self.change = _diff_norms(params, p0)
        self.steps = int(opt.step)


LATE_NUMBERS = ("late_loss_rel_gap", "late_grad_norm_gap",
                "late_update_norm_gap")


class _LateStep:
    """Model 0's first step of the call after the window: the state
    before it goes to the host; the loss, the gradient (from the
    change of the first moment) and the change of the parameters are
    read after it."""

    def __init__(self, drv):
        self.drv = drv
        self.read = self.state = None
        self.losses_before = len(drv.trainer.losses[0])
        self.tokens_at = (data_seed(drv.seed, drv.calls), 1)
        drv.watch.at[drv.watch.count + 1] = self

    def before(self, params, opt):
        t0 = time.perf_counter()
        self.state = jax.device_get(dict(params=params, m=opt.m, v=opt.v,
                                         step=opt.step))
        print(f"bench: state copied to the host in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
        return self.state

    def after(self, old, params, opt):
        b1 = self.drv.b1
        old_dev = jax.device_put(dict(params=old["params"], m=old["m"]))
        grad = _diff_norms(opt.m, old_dev["m"], keep=b1,
                           scale=1.0 / (1.0 - b1))
        change = _diff_norms(params, old_dev["params"])
        del old_dev
        self.read = dict(grad=grad, change=change)

    def loss(self) -> float:
        return float(self.drv.trainer.losses[0][self.losses_before])


def reference_readings(cfg: dict, tr: dict, weight_seed: int, steps,
                       quant=None) -> dict:
    """The reference's readings over model 0's first steps: each step's
    loss, the first gradient's leaf norms, the leaf norms of the change
    after the last step."""
    trainer = ref.Trainer(cfg, ref.init_weights(cfg, weight_seed),
                          blocks=tr["ref_blocks"], quant=quant)
    losses, grads = [], []
    for dseed, job in steps:
        toks = ref.tokens(dseed, job, tr["batch"], tr["seq_len"],
                          cfg["vocab_size"])
        loss, norms = trainer.step(toks)
        losses.append(loss)
        grads.append(norms)
    p0 = ref.init_weights(cfg, weight_seed)
    change = ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        trainer.params, p0))
    return dict(losses=losses, grad=grads[0], change=change, steps=trainer.t)


def late_reference(cfg: dict, tr: dict, state: dict, tokens_at,
                   quant=None, toks=None) -> dict:
    """One reference step from ``state`` (params, m, v, step on the
    host): its loss, its gradient's leaf norms and the leaf norms of
    the change of the parameters."""
    trainer = ref.Trainer(cfg, jax.device_put(state["params"]),
                          blocks=tr["ref_blocks"], quant=quant,
                          m=jax.device_put(state["m"]),
                          v=jax.device_put(state["v"]),
                          t=int(state["step"]))
    if toks is None:
        toks = ref.tokens(*tokens_at, tr["batch"], tr["seq_len"],
                          cfg["vocab_size"])
    loss, grad = trainer.step(toks)
    change = ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        trainer.params, jax.device_put(state["params"])))
    return dict(loss=loss, grad=grad, change=change)


def compare(prog: dict, want: dict) -> dict:
    """The numbers compared: largest relative loss gap over the steps;
    worst leaf gap of the first gradient's norms; worst leaf gap of the
    change's norms, leaving out leaves whose reference gradient is under
    a thousandth of the median leaf's (they move by round-off alone);
    difference in optimizer steps taken."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], want["losses"]))
    if len(prog["losses"]) != len(want["losses"]):
        loss_gap = float("inf")
    keep = _moved(want["grad"])
    grad_gap, grad_at = ref.worst_gap(prog["grad"], want["grad"])
    change_gap, change_at = ref.worst_gap(prog["change"], want["change"],
                                          keep)
    return {"loss_rel_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": change_gap,
            "optimizer_steps_differ": abs(prog["steps"] - want["steps"]),
            "grad_worst_leaf": grad_at, "update_worst_leaf": change_at}


def compare_late(prog: dict, want: dict) -> dict:
    """The steady-state step's numbers, measured as :func:`compare`
    measures the first steps."""
    keep = _moved(want["grad"])
    return {"late_loss_rel_gap": abs(prog["loss"] - want["loss"])
            / abs(want["loss"]),
            "late_grad_norm_gap": ref.worst_gap(prog["grad"],
                                                want["grad"])[0],
            "late_update_norm_gap": ref.worst_gap(prog["change"],
                                                  want["change"], keep)[0]}


def _moved(grad: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    med = float(np.median(list(grad.values())))
    return {k for k, v in grad.items() if v >= 1e-3 * med}


def train_checks(cfg: dict, tr: dict, weight_seed: int, steps,
                 readings: dict) -> list[dict]:
    nums = compare(readings, reference_readings(cfg, tr, weight_seed,
                                                steps))
    return [{"name": k, "value": finite(v), "limit": tr["limits"][k]}
            for k, v in nums.items() if k in tr["limits"]]
