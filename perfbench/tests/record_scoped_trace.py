"""Records the small profiler traces that ``test_scope_trace.py``
reads: ``record_trace.py``'s two releases of its tiny configuration
inside a ``bench.window`` span, on one chip or a 4-chip mesh, with the
program's ``engine.*`` spans and ``pdp.*`` dispatch regions in the
trace, and the program's scope table (``obs.program_scopes()``) saved
beside it. Run from the root of a checkout on a host with N TPU chips:

    python3 perfbench/tests/record_scoped_trace.py --chips N

and copy ``<out>/trace_scoped_<N>chip.xplane.pb`` and
``<out>/trace_scoped_<N>chip.scopes.json`` into ``tests/data/``, where
``<out>`` is the output directory ``record_trace.py`` writes to.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402
import record_trace  # noqa: E402
import scope_trace  # noqa: E402
import tracing  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--chips", type=int, default=1)
    args = parser.parse_args()
    harness.require_chips(args.chips)
    from jax.profiler import TraceAnnotation
    from pipelinedp_tpu import obs, parallel
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "jobs"))
    import dp_aggregate
    mesh = parallel.make_mesh(args.chips) if args.chips > 1 else None
    job = dp_aggregate.Job(record_trace.TINY, {"dataset": "fresh"}, 1,
                           mesh=mesh)
    job.release(-1)
    d = tempfile.mkdtemp()
    tracing.start(d)
    with TraceAnnotation("bench.window"):
        for i in range(2):
            with TraceAnnotation("bench.release"):
                job.release(i)
    tracing.stop()
    out = "chiprun_out"  # record_trace.py's output directory
    base = os.path.join(out, f"trace_scoped_{args.chips}chip")
    os.makedirs(out, exist_ok=True)
    shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)[0], base + ".xplane.pb")
    scopes = obs.program_scopes()
    with open(base + ".scopes.json", "w") as f:
        json.dump(scopes, f, indent=0, sort_keys=True)
    profile = tracing.load(d)
    print(base, os.path.getsize(base + ".xplane.pb"))
    print(json.dumps(scope_trace.scope_times(profile, scopes)))
    print(json.dumps(scope_trace.reduce_named(profile)["idle_gaps"]))
    modules = sorted({ev.name for plane in profile.planes
                      for line in plane.lines if line.name == "XLA Modules"
                      for ev in line.events})
    print("trace modules:", modules)
    from pipelinedp_tpu.obs import costs
    print("executables:", sorted((module, fingerprint)
                                 for fn in list(costs._PROGRAMS)
                                 for module, fingerprint, _ in
                                 fn.scope_tables()))


if __name__ == "__main__":
    main()
