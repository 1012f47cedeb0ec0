"""Plain reference of the round-based SGC runtime simulation.

Written from the paper (Krishnan, Ebadifar, Khisti, ICLR 2023: §2 the
mu-rule and Remark 2.3's wait-out, §3.1 GC, §3.2 SR-SGC with
Algorithm 1 and App. G's replication code, §3.3 M-SGC, App. J's load
adjustment) and imports nothing of the program.
One (scheme, trace) pair at a time, one round at a time, in numpy.

``dtype`` is the precision of the timing math: float64 is what the
configuration states; the benchmark's control runs this same code in
float32.
"""

from __future__ import annotations

import math

import numpy as np

# -- schemes: delay, load, gate members and decodability ---------------------


def scheme_shape(name: str, n: int, params: dict) -> dict:
    """Delay ``T``, normalized load and the straggler models of one
    scheme, or raise ``ValueError`` for parameters the scheme rejects."""
    if name == "uncoded":
        return dict(T=0, load=1.0 / n, members=[("perround", 0)], s=0)
    if name == "gc":
        s = int(params["s"])
        if not 0 <= s < n:
            raise ValueError("gc needs 0 <= s < n")
        rep = s > 0 and n % (s + 1) == 0
        members = ([("repcover", s), ("perround", s)] if rep
                   else [("perround", s)])
        return dict(T=0, load=(s + 1) / n, members=members, s=s)
    B, W, lam = int(params["B"]), int(params["W"]), int(params["lam"])
    if name == "sr-sgc":
        if B <= 0 or (W - 1) % B:
            raise ValueError("sr-sgc needs B > 0 and B | W - 1")
        if not 0 < lam <= n:
            raise ValueError("sr-sgc needs 0 < lam <= n")
        s = math.ceil(B * lam / (W - 1 + B))
        # every window of W rounds is bursty OR <= s per round
        member = ("or", W, [("bursty", B, lam), ("perround", s)])
        return dict(T=B, load=(s + 1) / n, members=[member], s=s, B=B,
                    rep=(s == 0 or n % (s + 1) == 0))
    if name == "m-sgc":
        if not 0 < B < W:
            raise ValueError("m-sgc needs 0 < B < W")
        if not 0 <= lam <= n:
            raise ValueError("m-sgc needs 0 <= lam <= n")
        if lam < n:
            load = (lam + 1) * (W - 1 + B) / (n * (B + (W - 1) * (lam + 1)))
        else:
            load = (W - 1 + B) / (n * (W - 1))
        members = [("bursty", B, lam, W), ("arbitrary", B, lam, W + B - 1)]
        return dict(T=W - 2 + B, load=load, members=members)
    raise ValueError(f"unknown scheme {name!r}")


def _window(member) -> int:
    kind = member[0]
    if kind in ("perround", "repcover"):
        return 1
    if kind == "or":
        return member[1]
    return member[3]


def _conforms(member, win: np.ndarray) -> bool:
    """Does the window ``win`` (rows <= the member's window) satisfy the
    member's straggler model?"""
    kind = member[0]
    if kind == "perround":
        return bool((win.sum(axis=1) <= member[1]).all())
    if kind == "repcover":
        g = member[1] + 1
        groups = win.reshape(win.shape[0], -1, g)
        return bool((~groups.all(axis=2)).all())
    if kind == "bursty":
        B, lam = member[1], member[2]
        if int(win.any(axis=0).sum()) > lam:
            return False
        rows = np.arange(win.shape[0])[:, None]
        first = np.where(win, rows, win.shape[0]).min(axis=0)
        last = np.where(win, rows, -1).max(axis=0)
        return bool((last - first < B).all())
    if kind == "arbitrary":
        N, lam = member[1], member[2]
        if int(win.any(axis=0).sum()) > lam:
            return False
        return int(win.sum(axis=0).max(initial=0)) <= N
    if kind == "or":
        return any(_conforms(m, win) for m in member[2])
    raise ValueError(kind)


class _Gate:
    """Remark 2.3's selective wait-out: while the candidate straggler
    set would take the pattern outside every still-valid model, wait
    out the fastest candidate."""

    def __init__(self, members):
        self.members = members
        self.alive = [True] * len(members)
        self.rows: list[np.ndarray] = []

    def _admits(self, member, cand) -> bool:
        w = _window(member)
        tail = self.rows[max(0, len(self.rows) - (w - 1)):] if w > 1 else []
        return _conforms(member, np.array(tail + [cand], dtype=bool))

    def admit_selective(self, cand, cost):
        cand = cand.copy()
        waited = []
        while cand.any():
            ok = [i for i, m in enumerate(self.members)
                  if self.alive[i] and self._admits(m, cand)]
            if ok:
                self.alive = [i in ok for i in range(len(self.members))]
                break
            on = np.flatnonzero(cand)
            drop = int(on[np.argmin(cost[on])])
            cand[drop] = False
            waited.append(drop)
        self.rows.append(cand)
        return cand, waited


class _SRDecoder:
    """Algorithm 1 (with App. G's group rule for the replication code):
    round t serves job t, and job t - B's reattempts on workers whose
    first attempt did not come back."""

    def __init__(self, n, J, s, B, rep):
        self.n, self.J, self.s, self.B, self.rep = n, J, s, B, rep
        self.assigned: dict[int, np.ndarray] = {}
        self.returned: dict[int, np.ndarray] = {}
        self.fresh: dict[int, int] = {}

    def _decodable(self, surv) -> bool:
        if self.rep:
            return bool(surv.reshape(-1, self.s + 1).any(axis=1).all())
        return int(surv.sum()) >= self.n - self.s

    def round(self, t: int, stragglers: np.ndarray, done: dict) -> None:
        n, B, J = self.n, self.B, self.J
        jobs = np.full(n, t)
        tb = t - B
        if 1 <= tb <= J:
            eligible = ~((self.assigned[tb] == tb) & self.returned[tb])
            if self.rep:
                groups = np.arange(n) // (self.s + 1)
                covered = np.zeros(n // (self.s + 1), dtype=bool)
                covered[groups[self.returned[tb]]] = True
                eligible &= ~covered[groups]
            budget = n - self.s - self.fresh[tb]
            retry = eligible & (np.cumsum(eligible) - eligible < budget)
            jobs[retry] = tb
        self.assigned[t] = jobs
        ok = ~stragglers
        for job in (t, tb):
            if not 1 <= job <= J:
                continue
            got = self.returned.setdefault(job, np.zeros(n, dtype=bool))
            got |= ok & (jobs == job)
            if job == t:
                self.fresh[t] = int((ok & (jobs == job)).sum())
        for job in (t, tb):
            if 1 <= job <= J and job not in done:
                if self._decodable(self.returned[job]):
                    done[job] = t
                elif job == tb:
                    raise AssertionError(f"sr-sgc job {job} undecodable")


# -- one simulation ------------------------------------------------------------


def simulate(name: str, params: dict, delays: np.ndarray, *, mu: float,
             alpha: float, J: int, dtype=np.float64) -> dict:
    """Run ``J`` jobs of one scheme against one (rounds, n) delay trace.

    Returns the per-round durations ``rt`` (J + T,), the effective
    straggler pattern ``history`` (J + T, n), the rounds with a
    wait-out ``waited`` (J + T,) and ``done_round`` (J + 1,), where
    ``done_round[j]`` is the round job j decoded in (index 0 unused).
    """
    n = delays.shape[1]
    shape = scheme_shape(name, n, params)
    T = shape["T"]
    rounds = J + T
    if delays.shape[0] < rounds:
        raise ValueError("trace too short")
    one = dtype(1.0)
    extra = (dtype(shape["load"]) - one / dtype(n)) * dtype(alpha)
    gate = _Gate(shape["members"])
    sr = (_SRDecoder(n, J, shape["s"], shape["B"], shape["rep"])
          if name == "sr-sgc" else None)
    rt = np.zeros(rounds, dtype=dtype)
    waited_rounds = np.zeros(rounds, dtype=bool)
    done: dict[int, int] = {}
    for t in range(1, rounds + 1):
        times = delays[t - 1].astype(dtype) + extra
        kappa = times.min()
        cutoff = (one + dtype(mu)) * kappa
        tmax = times.max()
        cand = times > cutoff
        if not cand.any():
            gate.rows.append(cand)
            eff, waited = cand, []
            rt[t - 1] = min(cutoff, tmax)
        else:
            eff, waited = gate.admit_selective(cand, times)
            base = min(cutoff, tmax) if eff.any() else cutoff
            rt[t - 1] = (max(times[waited].max(), base) if waited
                         else min(cutoff, tmax))
        waited_rounds[t - 1] = bool(waited)
        if sr is not None:
            sr.round(t, eff, done)
    if sr is None:
        for j in range(1, J + 1):
            done[j] = j + T
    done_round = np.zeros(J + 1, dtype=np.int64)
    for j, r in done.items():
        done_round[j] = r
    return dict(rt=rt, history=np.array(gate.rows, dtype=bool),
                waited=waited_rounds, done_round=done_round, T=T,
                load=shape["load"])
