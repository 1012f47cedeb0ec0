"""Faults planted in the expert model's timed path, for the tests and
for the chip runs that set the cell's limits.  Each returns a context
manager that patches the program while it is open."""

import contextlib

import jax
import jax.numpy as jnp


def _patched_ragged_dot(rows_kept):
    """``lax.ragged_dot`` with the lhs rows that ``rows_kept(position in
    group, group id, group sizes)`` rejects zeroed: those rows then add
    nothing to the expert layer's output."""
    real = jax.lax.ragged_dot

    def ragged_dot(lhs, rhs, group_sizes, *args, **kw):
        ends = jnp.cumsum(group_sizes)
        row = jnp.arange(lhs.shape[0])
        group = jnp.searchsorted(ends, row, side="right")
        start = ends - group_sizes
        pos = row - start[jnp.minimum(group, len(ends) - 1)]
        keep = rows_kept(pos, group, group_sizes)
        return real(jnp.where(keep[:, None], lhs, 0), rhs, group_sizes,
                    *args, **kw)

    return ragged_dot


@contextlib.contextmanager
def _patch(target, name, value):
    old = getattr(target, name)
    setattr(target, name, value)
    try:
        yield
    finally:
        setattr(target, name, old)


def capacity_dropped(factor: float = 1.0):
    """Each held expert computes at most ``factor`` times its even share
    of the layer call's routed rows; the rest are dropped, as
    DeepSeek-V2's device-level token dropping (capacity factor 1.0)
    drops them in training."""

    def kept(pos, group, sizes):
        cap = jnp.ceil(factor * sizes.sum() / sizes.shape[0])
        return pos < cap

    return _patch(jax.lax, "ragged_dot", _patched_ragged_dot(kept))


def expert_left_out(expert: int = 0):
    """The rows routed to one held expert add nothing."""
    return _patch(jax.lax, "ragged_dot", _patched_ragged_dot(
        lambda pos, group, sizes: group != expert))


def balance_left_out():
    """The coded loss leaves the balance term out."""
    import repro.train.coded as coded

    return _patch(coded, "_balance_weight", lambda cfg: 0.0)


def half_batch():
    """Every job's second half of sequences is a copy of its first."""
    import repro.data

    full = repro.data.token_batch

    def half(seed, job, batch, seq, vocab):
        out = full(seed, job, batch, seq, vocab)
        return {k: jnp.concatenate([v[: batch // 2]] * 2)
                for k, v in out.items()}

    return _patch(repro.data, "token_batch", half)


FAULTS = {"capacity": capacity_dropped, "expert": expert_left_out,
          "balance": balance_left_out, "half": half_batch}
