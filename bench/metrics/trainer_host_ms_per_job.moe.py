"""Host milliseconds of the trainer's own work per decoded job in the
expert model's cell, read as ``trainer_host_ms_per_job.train`` reads it
(self time of ``train.round``, ``train.batch`` and ``train.dispatch``
per ``train.sync`` span in the traced window)."""

from bench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "trainer_host_ms_per_job.train.py",
                   "bench_metric_trainer_host_ms_per_job_train").read
