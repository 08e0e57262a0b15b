#!/usr/bin/env python3
"""perfbench: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints a `details: {...}` line and then, as the last line of standard
output, one JSON object with the keys `correct`, `attempted`, `failed`,
`metrics` and `device` (with `--trace 1` also `breakdown`). Exits
non-zero with no result line where JAX finds no TPU or too few chips.
See perfbench/README.md.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # For the sweep that finds a cell's knee, by hand: the driver never
    # passes it, and the cell's own file holds the rate that counts.
    ap.add_argument("--knee-per-s", type=float, default=None)
    return ap.parse_args(argv)


def main(argv=None, *, root=ROOT, devices=None, out=print) -> int:
    args = parse(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import harness

    return harness.run_cell(
        args, root=root, t_start=T_START, devices=devices, out=out
    )


if __name__ == "__main__":
    sys.exit(main())
