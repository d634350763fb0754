#!/usr/bin/env python3
"""Runs one cell of the benchmark once, on the chips of this machine:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result; the numbers compared
with the reference, each beside its limit, are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits nonzero before any work and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    bench = harness.Bench(ROOT)
    cell, _, _ = bench.cell(args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        sys.stderr.write(f"perfbench: {e}\n")
        return 2
    harness.peaks(devices[0].device_kind)
    harness.import_program(ROOT)
    line, checks = harness.run(bench, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START, devices)
    harness.emit(line, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
