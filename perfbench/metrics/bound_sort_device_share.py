"""Share of the traced window, in percent, the device spends in the
fused programs' ``bound_sort`` scope: the bounding sort (``jnp.lexsort``
by privacy id, hash and tie-break, with its key hashing). Counted over
the trace's longest operations, each mapped to its scope by the
program's own table (``scope_trace.top_op_scope_share``), the mean over
chips. None on a program without scope tables."""

import scope_trace


def read(run):
    return scope_trace.top_op_scope_share(run, "bound_sort")
