#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload fig2b-grid --seed 7 --seconds 10 --trace 0

The cells are listed in ``BENCHMARK.json``.  The last line of standard
output is the result as one JSON object; the numbers the correctness
check compared, each with its limit, are the last lines of standard
error.  Exits non-zero, with no result line, unless JAX's first device
is a TPU and there are as many chips as the cell asks for.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    return harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
