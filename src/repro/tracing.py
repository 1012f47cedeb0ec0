"""Host spans for the profiler's trace.

:func:`span` is a ``jax.profiler.TraceAnnotation``: while a profiler
collects, it writes one host event into the same ``.xplane.pb`` as the
device planes, on the same clock, with its keyword attributes as the
event's stats; otherwise it costs one construction.  Counters are span
attributes (bytes moved, lane-rounds, jobs), so a reader sums them over
exactly the spans it looks at and no counter state lives in the
program.  Spans of one call share an identifier attribute (``call`` on
``sim.*``, ``job`` on ``train.*``).

The spans, outermost first:

- simulator (``core.batch``): ``sim.batch`` (``call``,
  ``lane_rounds``) around one ``simulate_batch``; inside it
  ``sim.plan``, one ``sim.upload`` (``bytes``; ``buckets``, the bucket
  programs that read this one copy of the traces), then per shape
  bucket ``sim.dispatch`` (the launch of its trace slice and its
  program), ``sim.fetch`` (``bytes``; it waits for the upload and the
  program) and ``sim.assemble`` (``cells``); ``sim.runner_build`` on a
  runner-cache miss.  ``simulate_lockstep(backend="jax")`` opens the
  same spans, ``sim.batch`` aside, for its one-member bucket;
- trainer (``train.driver.VectorizedCodedTrainer``): ``train.run``
  (``jobs``), ``train.round`` (``t``) per round, and per decoded job
  ``train.job`` (``job``, ``model``) holding ``train.batch``,
  ``train.dispatch`` and ``train.sync``.  For an expert model
  ``train.sync`` also carries the step's routing counts, fetched with
  the loss: ``held_rows`` (routed rows that landed on the experts this
  chip holds, over all expert layers and chunk passes) and
  ``max_expert_rows`` (the most rows one held expert got in one layer).

Device ops are named by ``jax.named_scope`` inside the jitted programs
(``round``, ``gate``, ``scheme_step`` in the simulator's scan;
``coded_loss``, ``layers``, ``head``, ``adamw`` in the coded train
step; inside ``layers`` for DeepSeek-V2-style models ``mla``,
``dense_mlp``, ``experts`` holding ``router``, ``dispatch``, ``gmm`` and
``combine``, and ``shared_expert``).  The trace holds each program's optimised HLO, whose
instructions carry those scope paths, so nothing here records them.
"""

from __future__ import annotations

import itertools

import jax

_CALL_IDS = itertools.count(1)


def span(name: str, **attrs):
    """A host span named ``name`` with integer or short string
    attributes; use as a context manager (``set_metadata(**attrs)`` on
    it adds attributes known only at the end)."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def collecting() -> bool:
    """Whether a profiler is recording host spans now: attributes that
    cost a pass over the results are computed only then."""
    return jax.profiler.TraceAnnotation.is_enabled()


def next_call_id() -> int:
    """A fresh identifier for the spans of one call."""
    return next(_CALL_IDS)
