"""Worker process: real per-chunk partial gradients + enacted faults.

``worker_main`` is the spawn target.  Each round message carries
executor-style mini-task items ``{"key", "chunks", "coeffs"}``; the
worker computes every referenced chunk gradient for real and returns
the coefficient-weighted combinations — exactly the quantities the
master's ``JobDecode`` weights reconstruct the full gradient from, for
all registered schemes (GC/SR-SGC ``ell`` rows, M-SGC ``d1``/``d2``
parts, clustered per-cluster codes, uncoded chunks).

Two compute modes, shared with the master through
:class:`TaskComputer` (the master instantiates the same class to form
the full-gradient truth its decode certificate checks against):

* ``linear`` (default) — closed-form least-squares chunk gradients
  ``g_c = X_c^T (X_c theta - y_c)`` on a deterministic per-job dataset;
  exact decode, no heavyweight imports in the child, fast enough that
  the *injected* delay dominates the measured round time.
* ``grad`` — the coded trainer's per-slot gradient path:
  ``jax.grad(train.coded.chunk_loss_sum)`` on deterministic
  ``data.token_batch`` chunks of a real (tiny) transformer LM, raveled
  to a flat vector.  Heavier (each child compiles its own jit), kept
  for the slow suite / example.  Grad mode always computes on the host
  CPU device, in the workers and in the master's truth alike, so both
  come from one backend.

Workers model the paper's CPU workers: ``worker_main`` (and the TCP
shim ``net.tcp_child_main``) pin the child's jax to the CPU before it
touches a backend, so a master holding an accelerator keeps it to
itself — a chip serves one process at a time.

Fault enactment (``injection.FaultSpec``): the per-round delay from the
master's trace is burned before reporting; ``drop_rounds`` suppresses
first-attempt sends (the master's resend recovers the cached result);
``kill_after`` exits the process for good.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from .injection import FaultSpec, enact_delay


def pin_to_cpu() -> None:
    """Restrict this process's jax to the host CPU.  Must run before
    jax initialises a backend (the first array op or device query);
    spawned workers call it first thing."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def linear_job_data(seed: int, job: int, num_rows: int, dim: int):
    """Deterministic per-job least-squares problem (X, y, theta)."""
    rng = np.random.default_rng([seed, 7919, job])
    X = rng.standard_normal((num_rows, dim))
    y = rng.standard_normal(num_rows)
    theta = rng.standard_normal(dim)
    return X, y, theta


class TaskComputer:
    """Chunk-gradient oracle shared by workers (per-task values) and
    the master (full-gradient decode certificate)."""

    def __init__(self, seed: int, compute: str, dim: int, num_rows: int,
                 bounds, model_cfg=None, batch_size: int = 0,
                 seq_len: int = 0):
        self.seed = seed
        self.compute = compute
        self.dim = dim
        self.num_rows = num_rows
        self.bounds = [tuple(b) for b in bounds]
        self._jobs: dict[int, tuple] = {}
        if compute == "grad":
            self._init_grad(model_cfg, batch_size, seq_len)
        elif compute != "linear":
            raise ValueError(f"unknown compute mode {compute!r}")

    # -- linear mode -----------------------------------------------------
    def _linear_data(self, job: int):
        if job not in self._jobs:
            if len(self._jobs) > 64:
                self._jobs.clear()
            self._jobs[job] = linear_job_data(
                self.seed, job, self.num_rows, self.dim
            )
        return self._jobs[job]

    # -- grad mode (train/coded.py per-slot gradient path) ---------------
    def _init_grad(self, model_cfg, batch_size: int, seq_len: int):
        import jax
        from jax.flatten_util import ravel_pytree

        from repro.train.coded import chunk_loss_sum, init_train_state

        if model_cfg is None or batch_size <= 0:
            raise ValueError("grad mode needs model_cfg and batch_size")
        self.cfg = model_cfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self._cpu = jax.devices("cpu")[0]
        self._ravel = lambda tree: np.asarray(ravel_pytree(tree)[0])
        self._init_state = init_train_state
        self._grad_fn = jax.jit(
            jax.grad(lambda p, b: chunk_loss_sum(p, self.cfg, b))
        )

    def _grad_data(self, job: int):
        import jax

        from repro.data import token_batch

        if job not in self._jobs:
            if len(self._jobs) > 16:
                self._jobs.clear()
            with jax.default_device(self._cpu):
                params, _ = self._init_state(
                    self.cfg, jax.random.PRNGKey(self.seed * 100003 + job)
                )
                batch = token_batch(
                    self.seed, job, self.batch_size, self.seq_len,
                    self.cfg.vocab_size,
                )
            self._jobs[job] = (params, batch)
        return self._jobs[job]

    # -- shared surface --------------------------------------------------
    def set_bounds(self, bounds) -> None:
        """Adopt a new chunk partition (master ``reconfig`` after the
        fleet shrank): per-job data is partition-independent, so only
        the slice table changes."""
        self.bounds = [tuple(b) for b in bounds]

    def chunk_grad(self, job: int, chunk: int) -> np.ndarray:
        lo, hi = self.bounds[chunk]
        if self.compute == "linear":
            X, y, theta = self._linear_data(job)
            Xc = X[lo:hi]
            return Xc.T @ (Xc @ theta - y[lo:hi])
        import jax

        params, batch = self._grad_data(job)
        with jax.default_device(self._cpu):
            cb = jax.tree.map(lambda a: a[lo:hi], batch)
            return self._ravel(self._grad_fn(params, cb))

    def value(self, item: dict) -> np.ndarray:
        """Coefficient-weighted combination of the item's chunk grads."""
        chunks = item["chunks"]
        coeffs = item["coeffs"]
        out = coeffs[0] * self.chunk_grad(item["job"], chunks[0])
        for c, w in zip(chunks[1:], coeffs[1:]):
            out = out + w * self.chunk_grad(item["job"], c)
        return out

    def warmup(self) -> None:
        """Pre-compile the grad-mode jit for every distinct chunk shape
        (workers call this before reporting ready, so compile cost never
        counts against round timeouts or round measurement)."""
        if self.compute != "grad":
            return
        seen = set()
        for c, (lo, hi) in enumerate(self.bounds):
            if hi - lo not in seen:
                seen.add(hi - lo)
                self.chunk_grad(1, c)

    def full_grad(self, job: int) -> np.ndarray:
        """Full-batch gradient (the master's decode truth)."""
        if self.compute == "linear":
            X, y, theta = self._linear_data(job)
            return X.T @ (X @ theta - y)
        import jax

        params, batch = self._grad_data(job)
        with jax.default_device(self._cpu):
            return self._ravel(self._grad_fn(params, batch))


@dataclass(frozen=True)
class WorkerSetup:
    """Everything a spawned worker needs (must stay picklable)."""

    worker_id: int
    seed: int
    compute: str = "linear"
    dim: int = 8
    num_rows: int = 64
    bounds: tuple = ()
    fault: FaultSpec = field(default_factory=FaultSpec)
    model_cfg: object = None
    batch_size: int = 0
    seq_len: int = 0

    def computer(self) -> TaskComputer:
        return TaskComputer(
            self.seed, self.compute, self.dim, self.num_rows, self.bounds,
            model_cfg=self.model_cfg, batch_size=self.batch_size,
            seq_len=self.seq_len,
        )


def _pong(conn, worker_id: int, msg: dict) -> bool:
    """Answer a liveness ping (piggybacked on the round protocol);
    returns False when the pipe is gone."""
    try:
        conn.send({"kind": "pong", "worker": worker_id,
                   "seq": msg.get("seq")})
        return True
    except (BrokenPipeError, EOFError, OSError):
        return False


def _enact_cancellable(conn, worker_id: int, t: int, seconds: float,
                       mode: str):
    """Burn the injected delay, but abandon it if the master has moved
    on to a later round — the protocol's task cancellation: a straggler
    whose result was not admitted stops wasting time on it.  Returns the
    interrupting message (later round / stop) or ``None`` when the full
    delay elapsed.  Same-round resends arriving mid-delay are absorbed
    (the single reply after the delay answers them), and liveness pings
    are answered inline so a slow worker is never mistaken for a dead
    one."""
    deadline = time.perf_counter() + seconds
    while True:
        remaining = deadline - time.perf_counter()
        if remaining <= 0:
            return None
        enact_delay(min(remaining, 0.005), mode)
        try:
            if conn.poll(0):
                nxt = conn.recv()
                if nxt.get("kind") == "ping":
                    if not _pong(conn, worker_id, nxt):
                        return {"kind": "stop"}
                    continue
                if nxt.get("kind") == "round" and int(nxt["t"]) <= t:
                    continue
                return nxt
        except (EOFError, OSError):
            return {"kind": "stop"}


def worker_main(conn, setup: WorkerSetup) -> None:
    """Spawn target: serve round messages until stopped or killed."""
    pin_to_cpu()
    fault = setup.fault
    computer = setup.computer()
    computer.warmup()
    if fault.ready_delay > 0:
        time.sleep(fault.ready_delay)   # slow (re)join
    # readiness handshake: the master must not start round timeouts
    # while children are still paying interpreter/import/compile
    # start-up cost
    try:
        conn.send({"kind": "ready", "worker": setup.worker_id})
    except (BrokenPipeError, OSError):
        return
    cache: dict[int, tuple] = {}      # t -> (values, compute_s, delay_s)
    pending = None                    # message that cancelled a delay
    while True:
        if pending is not None:
            msg, pending = pending, None
        else:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                return
        kind = msg.get("kind")
        if kind == "stop":
            return
        if kind == "ping":
            if not _pong(conn, setup.worker_id, msg):
                return
            continue
        if kind == "reconfig":
            # the fleet shrank: adopt the survivors' chunk partition and
            # forget results keyed on the old one
            computer.set_bounds(msg["bounds"])
            computer.warmup()
            cache.clear()
            continue
        if kind != "round":
            continue
        t, attempt = int(msg["t"]), int(msg["attempt"])
        t_recv = time.perf_counter()
        # master->worker wire time: the transport stamps "_sent" on the
        # master clock at send (same perf_counter base on one host)
        wire_s = (t_recv - float(msg["_sent"])
                  if msg.get("_sent") is not None else None)
        if t in cache:
            # resend path: the result was computed on the first attempt
            # and only the message was lost — answer from the cache
            values, compute_s, delay_s = cache[t]
        else:
            t0 = time.perf_counter()
            values = [(it["key"], computer.value(it))
                      for it in msg["items"]]
            compute_s = time.perf_counter() - t0
            delay_s = float(msg["delay_s"])
            pending = _enact_cancellable(
                conn, setup.worker_id, t, delay_s, fault.delay_mode
            )
            if pending is not None:
                if pending.get("kind") == "stop":
                    return
                continue              # round cancelled by a newer one
            cache[t] = (values, compute_s, delay_s)
            for old in [k for k in cache if k < t - 4]:
                del cache[old]
        if not fault.drops(t, attempt):
            reply = {
                "kind": "result",
                "t": t,
                "attempt": attempt,
                "worker": setup.worker_id,
                "values": values,
                "telemetry": {
                    "recv": t_recv,
                    "delay_s": delay_s,
                    "compute_s": compute_s,
                    "wire_s": wire_s,
                    "sent": time.perf_counter(),
                },
            }
            try:
                conn.send(reply)
            except (BrokenPipeError, OSError):
                return
        if fault.dies_after(t):
            try:
                conn.close()
            finally:
                return
