#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process: the
program's compared numbers on each seed, one line per release, through
the window's own job at the cell's size; then the control's (the plain
reference in bfloat16 put in the program's place) on other seeds. The
benchmark's own runs do not run it.

    python3 perfbench/readings.py --workload <name> --seeds 1 2 3 \
        --releases 3 --control-seeds 4 5 6

The last line gives, for each number, the lower reading (the largest
of the program's) and the upper one (the smallest of the control's)
beside the configuration's limit.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import harness  # noqa: E402


def program(bench, workload: str, seeds, releases: int, mesh=None):
    """{seed: [numbers of each release]} of the program."""
    _, config, traffic = bench.cell(workload)
    out = {}
    for seed in seeds:
        job = bench.module("jobs", traffic["job"]).Job(config, traffic, seed,
                                                       mesh=mesh)
        rows = [job.release(i)[0] for i in range(releases)]
        job.free()
        out[seed] = [job.check([r]) for r in rows]
        harness.log(json.dumps({"workload": workload, "seed": seed,
                                "program": out[seed]}))
    return out


def control(bench, workload: str, seeds):
    """{seed: numbers} of the control."""
    _, config, traffic = bench.cell(workload)
    out = {}
    for seed in seeds:
        job = bench.module("jobs", traffic["job"]).Job(config, traffic, seed)
        out[seed] = job.control()
        harness.log(json.dumps({"workload": workload, "seed": seed,
                                "control": out[seed]}))
    return out


def summary(limits: dict, prog: dict, ctrl: dict) -> dict:
    """{number: {lower, upper, limit}}; None where there is no reading."""
    runs = [n for per in prog.values() for n in per]
    out = {}
    for k, lim in limits.items():
        lo = [n[k] for n in runs if k in n]
        hi = [n[k] for n in ctrl.values() if k in n]
        out[k] = {"lower": max(lo) if lo else None,
                  "upper": min(hi) if hi else None, "limit": lim}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--releases", type=int, default=3)
    parser.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = parser.parse_args(argv)
    bench = harness.Bench(ROOT)
    cell, config, _ = bench.cell(args.workload)
    mesh = None
    if args.seeds:
        try:
            harness.require_chips(cell["chips"])
        except harness.NoChip as e:
            harness.log(f"perfbench: {e}")
            return 2
        harness.import_program(ROOT)
        harness.enable_compile_cache(ROOT)
        if cell["chips"] > 1:
            from pipelinedp_tpu import parallel
            mesh = parallel.make_mesh(cell["chips"])
    else:
        harness.import_program(ROOT)
    prog = program(bench, args.workload, args.seeds, args.releases, mesh)
    ctrl = control(bench, args.workload, args.control_seeds)
    limits = config["limits"]
    over = {s: [k for k, lim in limits.items() if n.get(k, 0) > lim]
            for s, n in ctrl.items()}
    sound = all(n[k] <= lim for per in prog.values() for n in per
                for k, lim in limits.items() if k in n)
    print(json.dumps({"workload": args.workload,
                      "readings": summary(limits, prog, ctrl),
                      "program_within_limits": sound,
                      "control_fails": over}), flush=True)
    return 0 if sound and all(over.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
