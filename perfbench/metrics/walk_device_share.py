"""Share of the traced window, in percent, the device spends in the
fused programs' ``walk`` scope: the percentile walk's counting (leaf
mapping, the mid histogram, the bottom walk's compaction or its
full-row scatters, the descent; node noise is ``noise``). Counted over
the trace's longest operations only, each mapped to its scope by the
program's own table (``scope_trace.top_op_scope_share``), the mean over
chips: a lower bound, since the walk's operations spread past the
longest few and a walk operation that leaves them is no longer counted.
None on a program that declares no ``walk`` scope, or has no scope
tables."""

import scope_trace


def read(run):
    from pipelinedp_tpu import obs
    if "walk" not in getattr(obs, "SCOPES", ()):
        return None
    return scope_trace.top_op_scope_share(run, "walk")
