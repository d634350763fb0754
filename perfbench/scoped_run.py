#!/usr/bin/env python3
"""Runs one cell like ``run.py --trace 1``, and reads the trace also by
the program's own names:

    python3 perfbench/scoped_run.py --workload <name> --seed <n> --seconds <s> [--out <file.json>]

Besides ``run.py``'s result line, standard error (and ``--out``, as
JSON) gets the device seconds per named scope of the fused programs
over the whole trace (``scope_trace.scope_times``, with the share of
the engine programs' device time that maps to a scope), and the
longest idle gaps named by the program's ``engine.*``/``pdp.*`` spans
as well as the benchmark's own (``scope_trace.reduce_named``). The
result line and its metrics are ``run.py``'s, computed the same way.
"""

import json
import os
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import harness  # noqa: E402
import run  # noqa: E402
import scope_trace  # noqa: E402
import tracing  # noqa: E402


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    out = None
    if "--out" in args:
        i = args.index("--out")
        out = args[i + 1]
        del args[i:i + 2]
    extra = {}
    reduce = tracing.reduce

    def reduce_and_read(profile, window=tracing.WINDOW):
        from pipelinedp_tpu import obs
        extra["scopes"] = scope_trace.scope_times(
            profile, obs.program_scopes(), window)
        extra["idle_gaps"] = scope_trace.reduce_named(
            profile, window)["idle_gaps"]
        return reduce(profile, window)

    tracing.reduce = reduce_and_read
    run.T_START = T_START
    rc = run.main(args + ["--trace", "1"])
    if extra:
        harness.log("scopes: " + json.dumps(extra["scopes"]))
        harness.log("coverage of the engine programs' device time: %s" %
                    extra["scopes"]["coverage"])
        harness.log("idle gaps by program span: " +
                    json.dumps(extra["idle_gaps"]))
        if out:
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
            with open(out, "w") as f:
                json.dump(extra, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
