"""Jitted training / serving steps, coded and uncoded.

``make_coded_train_step`` is the TPU-native form of the paper's GC
round (DESIGN.md §2): the batch arrives as the cyclic replicated view
(n, s+1, chunk_bs, ...) with per-(worker, chunk) weights

    w[i, j] = beta_i * (1 - straggler_i) * alpha_{i, c(i,j)}

so the decoded gradient is grad of the weighted scalar loss

    L = sum_ij w[i, j] * loss_sum(chunk_ij)

When the survivor decode vector beta solves the GC system,
``sum_i w[i, j(c)] == 1`` for every data chunk c and the gradient is
*exactly* the full-batch gradient — the weighted all-reduce XLA inserts
for the batch axis IS the GC decoder.  Stragglers enter as zeroed
weights: their shard's compute is dead weight exactly like a cancelled
Lambda worker's.

The ``n`` axis is sharded over ("pod", "data") on the production mesh;
chunk replication (the factor s+1) is the paper's computational load,
and shows up 1:1 in the dry-run roofline compute term.

**Vectorized-state master loop.**  The step generalizes past plain GC:
any registered scheme maps its decode onto a (n, slots) weight grid via
``scheme.chunk_grid()`` / ``chunk_slots(job)`` / ``decode_weights(jd)``
(see ``core.schemes``), and ``num_chunks`` here overrides the
normalization when the grid covers more than ``n`` chunks (M-SGC's
subchunk expansion, uncoded's single column).  The end-to-end loop is
``train.driver.VectorizedCodedTrainer``: it advances every scheme on
the lockstep kernels' ``SchemeState`` (``scheme.step`` — no per-round
``MiniTask`` descriptor lists), reads decodable jobs with their solved
coefficients off ``scheme.collect_decodes``, gathers the job's batch
into the slot view with ``data.coded_slot_batch``, and feeds one jitted
``make_coded_train_step`` per scheme — the weighted all-reduce is the
exact decoder for all 7 registered schemes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import decode_step, loss_fn
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update


def make_train_step(cfg: ModelConfig, *, lr: float = 1e-4,
                    weight_decay: float = 0.0):
    """Plain (uncoded) data-parallel train step: (params, opt, batch)."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch)
        )(params)
        params, opt_state = adamw_update(
            params, grads, opt_state, lr=lr, weight_decay=weight_decay
        )
        return params, opt_state, {"loss": loss}

    return step


def chunk_loss_sum(params, cfg: ModelConfig, chunk_batch) -> jax.Array:
    """SUM-reduced loss over one chunk (partial gradients must add up to
    the full-batch gradient, so per-chunk reduction is a sum)."""
    logits_loss = loss_fn(params, cfg, chunk_batch, aux_weight=0.0)
    # loss_fn returns a mean over chunk tokens; rescale to a sum over
    # examples so sum over chunks == batch total (uniform seq lengths).
    n_ex = jax.tree.leaves(chunk_batch)[0].shape[0]
    return logits_loss * n_ex


def make_coded_loss(cfg: ModelConfig, num_chunks: int):
    """The coded step's scalar loss ``(params, coded_batch, weights)``:
    the decode-weighted sum of every (worker, slot) chunk loss over the
    job's ``num_chunks * chunk_bs`` examples.  Its gradient is the
    decoded gradient, which equals the full-batch gradient when the
    weights solve the scheme's decode."""

    def coded_loss(params, coded_batch, weights):
        def worker_chunks(wchunks, w_i):
            def one(chunk, w):
                return w * chunk_loss_sum(params, cfg, chunk)
            return jax.vmap(one)(wchunks, w_i).sum()

        per_worker = jax.vmap(worker_chunks, in_axes=(0, 0))(
            coded_batch, weights
        )  # (n,)
        total_examples = (
            num_chunks * jax.tree.leaves(coded_batch)[0].shape[2]
        )
        return per_worker.sum() / total_examples

    return coded_loss


def make_coded_train_step(cfg: ModelConfig, n: int, s: int, *,
                          lr: float = 1e-4, weight_decay: float = 0.0,
                          num_chunks: int | None = None):
    """GC-coded train step.

    Inputs:
      coded_batch — pytree with leaves (n, s+1, chunk_bs, ...), the
        cyclic replicated chunk view (``data.gc_chunked_batch``), or
        the scheme-generic (n, slots, chunk_bs, ...) view
        (``data.coded_slot_batch``) — ``s+1``/``slots`` is just the
        leaves' second axis, the step never reads ``s``;
      weights     — (n, s+1) f32, folding alpha, beta and the straggler
        mask (see module docstring; ``gc_round_weights`` builds them,
        ``scheme.decode_weights`` in the general case).

    ``num_chunks`` (default ``n``) is how many equal chunks the job's
    batch was split into — the loss normalizer ``num_chunks * chunk_bs``
    must equal the job's true batch size.
    """
    coded_loss = make_coded_loss(cfg, n if num_chunks is None else num_chunks)

    def step(params, opt_state, coded_batch, weights):
        loss, grads = jax.value_and_grad(coded_loss)(
            params, coded_batch, weights
        )
        params, opt_state = adamw_update(
            params, grads, opt_state, lr=lr, weight_decay=weight_decay
        )
        return params, opt_state, {"loss": loss}

    return step


def gc_round_weights(code, survivors) -> jnp.ndarray:
    """(n, s+1) weights for one steady-state GC round.

    code: GradientCode/RepGradientCode; survivors: worker ids that
    returned results.  w[i, j] = beta_i * alpha_{i, chunk(i, j)}.
    """
    import numpy as np

    n = code.n
    beta = code.decode_vector(sorted(survivors))
    w = np.zeros((n, code.s + 1), dtype=np.float32)
    for i in range(n):
        chunks = code.chunks_of_worker(i)
        w[i] = beta[i] * code.encode_matrix[i, chunks]
    return jnp.asarray(w)


def make_serve_step(cfg: ModelConfig):
    def step(params, cache, token, pos):
        return decode_step(params, cfg, cache, token, pos)

    return step


def init_train_state(cfg: ModelConfig, key):
    from repro.models import init_params

    params = init_params(cfg, key)
    return params, adamw_init(params)
