#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``.  The run sets up
(inputs and weights from ``--seed``, every shape the cell uses
compiled or loaded from the compile cache in ``<checkout>/.jax_cache``),
measures for ``--seconds``, then compares what the timed path produced
with the plain reference.  ``--trace 1`` profiles the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    harness.setup_runtime()
    try:
        devices = harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run on the CPU", file=sys.stderr)
        return 3
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START, devices=devices)
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
