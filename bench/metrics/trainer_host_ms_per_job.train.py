"""Host milliseconds of the trainer's own work (self time of the
``train.round`` spans: gate, scheme step, decode collection; and of
``train.batch`` and ``train.dispatch``) per decoded job (``train.sync``
span) in the traced window."""

from bench.spans import reading


def read(ctx):
    r = reading(ctx)
    if r is None:
        return None
    ms = r.per(("train.round", "train.batch", "train.dispatch"),
               "train.sync")
    return None if ms is None else ms * 1e3
