#!/usr/bin/env python3
"""Chip benchmark of the served statistics path: one cell, one run.

    python3 benchmarks/chip/bench.py --workload devops-ingest --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout on a machine with the chips the cell asks
for (``BENCHMARK.json``).  Without a TPU, or with fewer chips, it exits
with an error and prints no result.  ``--trace 1`` profiles the window and
reports the cell's per-layer metrics instead of its end-to-end ones.
``--control 1`` puts the reference, computed one precision lower, in the
program's place for the check; its run must come out not correct.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each number compared with its limit.
The same checks are the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    import repro  # noqa: F401  (no program, no run)
    from chipbench import run, spec

    cell = spec.load(args.workload)
    result = run.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS, control=bool(args.control))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
