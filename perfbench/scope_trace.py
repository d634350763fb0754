"""Reads a profiler trace by the program's own names: device time per
named scope of the fused programs, and idle gaps named by the
program's spans.

The program names its fused programs' phases (``obs.SCOPES``:
``bound_sort``, ``bound_permute``, ``segsum``, ...) and maps each
compiled program's HLO instructions to them on request
(``obs.program_scopes()``: ``{program: {instruction: scope}}``). A
trace's ``XLA Ops`` events carry only the instruction, so that table
turns them into per-scope device seconds. The program's ``engine.*``
spans and ``pdp.*`` dispatch regions are profiler annotations too, so a
gap can be named by the innermost of them as well as by the
benchmark's ``bench.*`` spans.

Nothing here changes what ``tracing.reduce`` computes: window, busy,
collective and op times are its own.
"""

import bisect
import contextlib
import traceback

import tracing

#: The host annotations a gap may be named by: the benchmark's spans,
#: the program's spans and its dispatch regions.
NAMING_PREFIXES = ("bench.", "engine.", "pdp.")

_host_spans = tracing.host_spans
_reduce = tracing.reduce


def _naming_spans(profile, prefix: str = "bench."):
    return [s for p in NAMING_PREFIXES for s in _host_spans(profile, p)]


@contextlib.contextmanager
def _gaps_named_by_program():
    tracing.host_spans = _naming_spans
    try:
        yield
    finally:
        tracing.host_spans = _host_spans


def reduce_named(profile, window: str = tracing.WINDOW) -> dict:
    """``tracing.reduce`` with each idle gap named by the innermost
    overlapping span among ``bench.*``, ``engine.*`` and ``pdp.*``. The
    gaps, their seconds and every other number are ``reduce``'s."""
    with _gaps_named_by_program():
        return _reduce(profile, window)


def scope_of(scopes: dict, module: str, op: str):
    """(scope or None, whether ``scopes`` maps the program at all) of
    instruction ``op`` in the program an ``XLA Modules`` event names
    (``jit_fused_aggregate_kernel(1195...)``, or the bare name). A name
    with no table of its own takes the scope every table of the program
    agrees on (its several signatures)."""
    table = scopes.get(module)
    if table is not None:
        return table.get(op), True
    prog = tracing._program(module)
    tables = [t for k, t in scopes.items() if tracing._program(k) == prog]
    if not tables:
        return None, False
    found = {t.get(op) for t in tables}
    return (found.pop() if len(found) == 1 else None), True


def _window(profile, window: str):
    spans = [s for s in tracing.host_spans(profile) if s[2] == window]
    if not spans:
        raise ValueError(f"trace has no host span {window!r}")
    return max((e - s, s, e) for s, e, _ in spans)[1:]


def scope_times(profile, scopes: dict, window: str = tracing.WINDOW) -> dict:
    """Device seconds per scope inside the window, the mean over chips,
    for the programs ``scopes`` maps (``obs.program_scopes()``).

    Returns ``{"scopes": {scope: s}, "programs": {program: {"s": device
    s, "mapped_s": s under a scope}}, "coverage": mapped over device
    seconds of those programs}``; ``coverage`` is None where none of
    them ran in the window."""
    w0, w1 = _window(profile, window)
    per_scope, programs, chips = {}, {}, 0
    for plane in profile.planes:
        if not tracing.DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        ops, modules = tracing._device_events(plane)
        starts = [s for s, _, _ in modules]
        for s, e, name in ops:
            s, e = max(s, w0), min(e, w1)
            i = bisect.bisect_right(starts, s) - 1
            if e <= s or i < 0 or modules[i][1] < e:
                continue
            scope, known = scope_of(scopes, modules[i][2],
                                    tracing.op_name(name))
            if not known:
                continue
            prog = programs.setdefault(tracing._program(modules[i][2]),
                                       {"s": 0.0, "mapped_s": 0.0})
            prog["s"] += (e - s) * 1e-9
            if scope is not None:
                prog["mapped_s"] += (e - s) * 1e-9
                per_scope[scope] = per_scope.get(scope, 0.0) + (e - s) * 1e-9
    if not chips:
        raise ValueError("trace has no TPU device plane")
    for prog in programs.values():
        prog["s"] /= chips
        prog["mapped_s"] /= chips
    total = sum(p["s"] for p in programs.values())
    return {
        "scopes": {k: v / chips for k, v in sorted(per_scope.items())},
        "programs": programs,
        "coverage": (sum(p["mapped_s"] for p in programs.values()) / total
                     if total > 0 else None),
    }


def top_op_scope_share(run: dict, scope: str):
    """Percent of the traced window the device spent in ``scope``,
    counted over the trace's longest operations (``trace.device_ops``:
    ``tracing.TOP`` of them, each the mean over chips) that
    ``obs.program_scopes()`` maps to it: a lower bound, since time in
    shorter operations is not counted. None where the run has no trace
    or the program has no scope table."""
    t = run["trace"]
    if t is None:
        return None
    from pipelinedp_tpu import obs
    program_scopes = getattr(obs, "program_scopes", None)
    if program_scopes is None:
        return None
    try:
        scopes = program_scopes()
    except Exception:  # a reader must not take the run down
        traceback.print_exc()
        return None
    spent = sum(seconds for name, seconds in t["device_ops"]
                if scope_of(scopes, *name.partition(" ")[::2])[0] == scope)
    return 100.0 * spent / t["window_s"]
