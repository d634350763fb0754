"""Records the small profiler traces that ``test_tracing.py`` reads:
two releases of a tiny configuration inside a ``bench.window`` span,
on one chip and, with ``--chips 4``, on a 4-chip mesh. Run on the chip:

    chiprun [--chips 4] -- python3 perfbench/tests/record_trace.py --chips N

and copy ``chiprun_out/trace_<N>chip.xplane.pb`` into ``tests/data/``.
"""

import argparse
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402
import tracing  # noqa: E402

TINY = {
    "dataset": {
        "rows": 200_000, "privacy_units": 4_000, "partitions": 2_000,
        "values": {"kind": "grid", "lo": 0.5, "hi": 5.0, "step": 0.5},
        "rows_per_unit": {"min": 2, "median": 30, "max": 400},
        "partition_zipf_a": 1.3, "sizes_seed": 7},
    "query": {"metrics": ["COUNT", "SUM", "MEAN"]},
    "guarantees": {
        "release_epsilon": 1e8, "delta": 1e-6, "noise_kind": "LAPLACE",
        "max_partitions_contributed": 400,
        "max_contributions_per_partition": 1, "min_value": 0.5,
        "max_value": 5.0},
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--chips", type=int, default=1)
    args = parser.parse_args()
    harness.require_chips(args.chips)
    from jax.profiler import TraceAnnotation
    from pipelinedp_tpu import parallel
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "jobs"))
    import dp_aggregate
    mesh = parallel.make_mesh(args.chips) if args.chips > 1 else None
    job = dp_aggregate.Job(TINY, {"dataset": "fresh"}, 1, mesh=mesh)
    job.release(-1)
    d = tempfile.mkdtemp()
    tracing.start(d)
    with TraceAnnotation("bench.window"):
        for i in range(2):
            with TraceAnnotation("bench.release"):
                job.release(i)
    tracing.stop()
    out = os.path.join("chiprun_out", f"trace_{args.chips}chip.xplane.pb")
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)[0], out)
    print(out, os.path.getsize(out))
    print(tracing.reduce(tracing.load(d)))


if __name__ == "__main__":
    main()
