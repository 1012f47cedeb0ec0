"""Share of the expert model's coded train step's device self time
(``jit_step``) spent in ops under the ``head`` named scope (final norm,
the untied head's matmul, log-softmax and loss, forward and backward),
read as ``head_share_of_step.train`` reads it."""

from bench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "head_share_of_step.train.py",
                   "bench_metric_head_share_of_step_train").read
