"""The benchmark harness: runs one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in ``BENCHMARK.json``, and nothing here names one:

* ``configs/<config>.json`` (the file the ``configs`` entry names): the
  deployment, its sizes and guarantees, and the limits of the numbers
  its comparison reads;
* ``traffic/<traffic>.json``: the mix, and the ``job`` that runs one
  release of it, ``jobs/<job>.py``;
* ``metrics/<metric>.py``: one reader per metric, end-to-end and
  per-layer, which returns its number from the run's record, or None
  where it finds nothing to read.

A run: set-up (the chip, the compile cache, the job's data, one
warm-up release), then releases back to back for ``--seconds``, then
the reference over every release the window completed.
"""

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import tracing  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "perfbench_" + "_".join(path.split(os.sep)[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at the root of a checkout, and lookups by
    name into the files beside it."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, os.path.basename(HERE))
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py`` of this benchmark, loaded."""
        return load_module(os.path.join(self.dir, kind, name + ".py"))

    def cell(self, workload: str):
        """(cell, config, traffic) of the named workload."""
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        cell = cells[workload]
        entry = next(c for c in self.spec["configs"]
                     if c["name"] == cell["config"])
        config = load_json(os.path.join(self.root, entry["file"]))
        traffic = load_json(os.path.join(self.dir, "traffic",
                                         cell["traffic"] + ".json"))
        return cell, config, traffic

    def metrics(self, workload: str, trace: bool):
        """The metric entries a run of ``workload`` reports: the
        end-to-end ones without a trace, the per-layer ones with."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]


def require_chips(n: int):
    """The first step on the device: ``n`` TPU chips, or NoChip."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chips, jax found "
                     f"{len(devices)}")
    return devices[:n]


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[device_kind]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else one fixed directory inside the checkout (the
    path is part of the cache's key). Every program is cached."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or
            os.path.join(root, ".perfbench_cache", "jax"))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Compiles:
    """Backend compiles (a program loaded from the persistent cache
    counts too) and cache hits and misses, from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.count, self.hits, self.misses


def log(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def import_program(root: str):
    """The package of this checkout, never an installed copy."""
    if root not in sys.path:
        sys.path.insert(0, root)
    import pipelinedp_tpu
    where = os.path.dirname(os.path.dirname(os.path.abspath(
        pipelinedp_tpu.__file__)))
    if os.path.realpath(where) != os.path.realpath(root):
        raise ImportError(f"pipelinedp_tpu came from {where}, not from the "
                          f"checkout {root}")
    return pipelinedp_tpu


def run(bench: Bench, workload: str, seed: int, seconds: float,
        trace: bool, t_start: float, devices):
    """One run of the cell on ``devices``. Returns (result line without
    ``checks``, {name: (number, limit)})."""
    from jax.profiler import TraceAnnotation

    cell, config, traffic = bench.cell(workload)
    enable_compile_cache(bench.root)
    compiles = Compiles()
    mesh = None
    if cell["chips"] > 1:
        from pipelinedp_tpu import parallel
        mesh = parallel.make_mesh(cell["chips"])
    t_init = time.monotonic()
    job = bench.module("jobs", traffic["job"]).Job(config, traffic, seed,
                                                   mesh=mesh)
    t_synth = time.monotonic()
    with TraceAnnotation("bench.warmup"):
        job.release(-1)
    t_warm = time.monotonic()
    setup_compile = compiles.snapshot()
    setup_s = t_warm - t_start
    log(f"setup: {setup_s:.3f} s = init {t_init - t_start:.3f} + synthesis "
        f"{t_synth - t_init:.3f} + warm-up {t_warm - t_synth:.3f}; "
        f"compile {setup_compile[0]:.3f} s in {setup_compile[1]} programs, "
        f"cache hits {setup_compile[2]} misses {setup_compile[3]}")

    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if trace else None
    if trace:
        tracing.start(trace_dir)
    outputs, releases, failed, i = [], [], 0, 0
    t0 = time.monotonic()
    with TraceAnnotation("bench.window"):
        while time.monotonic() - t0 < seconds:
            r0 = time.monotonic()
            try:
                with TraceAnnotation("bench.release"):
                    rows, timings = job.release(i)
            except Exception:  # a failed release counts, the run goes on
                traceback.print_exc()
                failed += 1
            else:
                outputs.append(rows)
                releases.append({"rows": job.rows, "timings": timings,
                                 "seconds": time.monotonic() - r0})
            i += 1
    window_s = time.monotonic() - t0
    reduced = None
    if trace:
        tracing.stop()
        try:
            reduced = tracing.reduce(tracing.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    in_window = [a - b for a, b in zip(compiles.snapshot(), setup_compile)]
    each = ", ".join("%.3f" % r["seconds"] for r in releases)
    log(f"window: {window_s:.3f} s, {len(releases)} releases ({each} s), "
        f"{failed} failed; compiles inside it: {in_window[1]} "
        f"({in_window[0]:.3f} s), cache misses {in_window[3]}")
    spans = {}
    for r in releases:
        for k, v in r["timings"].items():
            if isinstance(v, (int, float)):
                spans[k] = spans.get(k, 0) + v
    log("release timings, summed over the window: " + json.dumps(spans))
    if reduced is not None:
        log("trace: window %.6f s, per chip %s" % (
            reduced["window_s"], json.dumps(reduced["chips"])))

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    job.free()
    gc.collect()

    record = {
        "releases": releases,
        "rows_released": sum(r["rows"] for r in releases),
        "window_s": window_s,
        "setup_s": setup_s,
        "setup_compile_s": setup_compile[0],
        "trace": reduced,
    }
    metrics = {}
    for m in bench.metrics(workload, trace):
        value = bench.module("metrics", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limits = config["limits"]
    try:
        numbers = job.check(outputs) if outputs else {}
    except Exception:
        traceback.print_exc()
        numbers = {}
    checks = {k: (numbers.get(k), limits[k]) for k in limits}
    correct = (bool(outputs) and failed == 0 and all(
        v is not None and v <= lim for v, lim in checks.values()))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": i, "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line, checks


def emit(line: dict, checks: dict) -> None:
    """Each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard
    output, with the same numbers under ``checks``, last."""
    for name, (value, limit) in checks.items():
        log(f"check {name} {value} limit {limit}")
    line = dict(line)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
