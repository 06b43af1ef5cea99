#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, chips, metrics) comes from
``BENCHMARK.json`` at the checkout's root.  The run builds the oracle (and
the proxy, where the mix filters with a cascade) at the configuration's
published widths with random weights from ``--seed``, warms up every shape
the traffic reaches, drives the gateway for ``--seconds``, waits for every
query sent, checks the answers against the plain reference, and prints one
JSON line last: the end-to-end metrics with ``--trace 0``, the per-layer
metrics from a profiler trace with ``--trace 1``.  It needs a TPU: without
one, or with fewer chips than the cell asks for, it exits non-zero and
prints no result."""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    return harness.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                       t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
