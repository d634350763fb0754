"""The reduction from a profiler trace to the benchmark's device numbers.

``reduce`` reads one ``jax.profiler`` trace (an ``.xplane.pb``) and
returns, inside the host span that marks the measured window:

* each chip's busy seconds: the union of the intervals in which an
  operation of its ``XLA Ops`` line ran;
* each chip's seconds in collective operations;
* the operations that took most device time, named by their program
  (the ``XLA Modules`` event around them) and their HLO name, averaged
  over the chips;
* the longest idle gaps, each named by what the host was doing (the
  benchmark's own innermost host span, ``bench.*``, that overlaps it
  most) and by the device programs before and after it.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute",
    re.IGNORECASE)
WINDOW = "bench.window"
TOP = 10


def start(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def _union(intervals):
    """Sorted, merged [start, end, first, last] of (start, end, name)
    intervals: ``first`` names the interval that opens a merged one,
    ``last`` the one that closes it."""
    merged = []
    for s, e, name in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1], merged[-1][3] = e, name
        else:
            merged.append([s, e, name, name])
    return merged


def host_spans(profile, prefix: str = "bench."):
    """[(start_ns, end_ns, name)] of the host annotations whose name
    starts with ``prefix``."""
    return [(ev.start_ns, ev.end_ns, ev.name)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def op_name(hlo: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:80]


def _program(module: str) -> str:
    """``jit_fused_aggregate_kernel(123)`` -> ``jit_fused_aggregate_kernel``."""
    return module.split("(")[0]


def _device_events(plane):
    """(ops, modules): sorted [(start_ns, end_ns, name)] of the plane's
    ``XLA Ops`` and ``XLA Modules`` lines."""
    lines = {ln.name: ln for ln in plane.lines}

    def events(name):
        line = lines.get(name)
        return [] if line is None else sorted(
            (ev.start_ns, ev.end_ns, ev.name) for ev in line.events)

    return events("XLA Ops"), events("XLA Modules")


def _leaves(spans):
    """The spans that contain no other span."""
    return [a for a in spans if not any(
        b is not a and a[0] <= b[0] and b[1] <= a[1] for b in spans)]


def reduce(profile, window: str = WINDOW) -> dict:
    spans = host_spans(profile)
    windows = [s for s in spans if s[2] == window]
    if not windows:
        raise ValueError(f"trace has no host span {window!r}")
    w0, w1 = max((e - s, s, e) for s, e, _ in windows)[1:]
    inner = [s for s in spans if s[2] != window and s[1] > w0 and s[0] < w1]
    leaves = _leaves(inner)
    chips, gaps, op_total = [], [], {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        chip = int(m.group(1))
        ops, modules = _device_events(plane)
        if not ops:  # a trace without op detail: whole programs
            ops, modules = modules, []
        starts = [s for s, _, _ in modules]
        busy, collective = [], 0
        for s, e, name in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = (_program(modules[i][2])
                    if i >= 0 and modules[i][1] >= e else "")
            busy.append((s, e, prog or "-"))
            key = (prog + " " + op_name(name)).strip()
            op_total[key] = op_total.get(key, 0) + (e - s)
            if COLLECTIVE.search(name):
                collective += e - s
        merged = _union(busy)
        chips.append({"chip": chip,
                      "busy_s": sum(e - s for s, e, _, _ in merged) * 1e-9,
                      "collective_s": collective * 1e-9})
        edges = [(None, w0, "-", "-")] + merged + [(w1, None, "-", "-")]
        for a, b in zip(edges, edges[1:]):
            gs, ge = a[1], b[0]
            if ge > gs:
                gaps.append((ge - gs, gs, ge, chip, (a[3], b[2])))
    if not chips:
        raise ValueError("trace has no TPU device plane")
    n = len(chips)
    chips.sort(key=lambda c: c["chip"])

    def doing(gs, ge):
        """The innermost bench span that overlaps the gap most."""
        for pool in (leaves, inner):
            best = max(((min(e, ge) - max(s, gs), name)
                        for s, e, name in pool), default=(0, ""))
            if best[0] > 0:
                return best[1]
        return "no bench span"

    ops = sorted(op_total.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "chips": chips,
        "busy_s": sum(c["busy_s"] for c in chips) / n,
        "collective_s": sum(c["collective_s"] for c in chips) / n,
        "device_ops": [[name, t * 1e-9 / n] for name, t in ops],
        "idle_gaps": [
            [f"chip{chip} {doing(gs, ge)}, after {a} before {b}", g * 1e-9]
            for g, gs, ge, chip, (a, b) in sorted(gaps, reverse=True)[:TOP]],
    }
