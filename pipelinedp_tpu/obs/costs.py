"""Device cost observatory: compile/cost/memory capture + rooflines.

The obs stack records everything the *host* does (spans, counters,
audit records, heartbeats) but — until this module — nothing the
*device* does: ``device_s`` dominates every bench record, yet no
artifact said what a jitted program cost to compile, how many
FLOPs/bytes it executes, or how much HBM it holds. PAPER.md §5.8
frames the TPU rebuild as a roofline problem; this module captures the
measurements that argument needs, per compiled program:

* :func:`instrumented_jit` — the ONE seam every hot jitted entry point
  (``jax_engine.py``, ``streaming.py``, ``analysis/jax_sweep.py``,
  ``parallel/sharded.py``) compiles through. Same signature as
  ``functools.partial(jax.jit, ...)`` plus a ``phase=`` label. With
  ``PIPELINEDP_TPU_COSTS`` unset it IS ``jax.jit`` (one env check and
  two reads of jax's dispatch-cache size per call; a call that grew the
  cache keeps its abstract signature). Enabled, the first call per
  (function, abstract-shape signature) compiles ahead-of-time via
  ``jitted.lower(...).compile()`` — the SAME program XLA would build
  for the traced call — records a ``compile.program`` span with the
  compile wall time and the persistent-compile-cache hit/miss verdict,
  captures ``compiled.cost_analysis()`` (flops, bytes accessed) and
  ``compiled.memory_analysis()`` (argument/output/temp bytes) into the
  process cost table, then dispatches THROUGH the captured executable.
  Subsequent same-signature calls reuse it, so cost capture never pays
  a second XLA compile for the same program (asserted by
  ``tests/test_costs.py`` via the trace counter). Backends that expose
  neither analysis record a ``cost.unavailable`` event instead of
  failing — capture must never take an aggregation down.
* :data:`DEVICE_PEAKS` — a static per-device-kind peak table (v5e /
  v4 nominal datasheet numbers; an order-of-magnitude CPU proxy) that
  turns each program's arithmetic intensity (flops per HBM byte) into
  a roofline verdict: ``compute_bound`` when the intensity clears the
  device's ridge point (peak FLOP/s over peak bytes/s),
  ``bandwidth_bound`` below it, ``unknown`` when the backend exposed
  no analysis or the device kind has no peak entry. Verdicts surface
  per program AND per phase (walk / pass_a / pass_b / ...) in the run
  report's ``device_costs`` section (schema v3).
* :func:`sample_live_bytes` — HBM watermark sampling: the monitor
  thread calls this each beat; it sums ``jax.live_arrays()`` bytes
  into the ``hbm.live_bytes`` gauge and the ``hbm.watermark`` running
  max (and a ledger time-series for the Chrome-trace counter track),
  so the heartbeat shows live device memory and leaks between sweeps
  become visible as a watermark that never comes back down.
* :data:`SCOPES`, :func:`scope` and :func:`program_scopes` — the
  fused programs name their phases with ``jax.named_scope`` (one of
  :data:`SCOPES` each). Every ``instrumented_jit`` program keeps the
  abstract signature of each call that compiled a new executable
  (static values, and a ``ShapeDtypeStruct`` with its sharding per
  array: no array stays referenced). ``program_scopes()`` compiles
  those signatures again on request — the persistent compile cache
  hands back the same executable — and maps every HLO instruction to
  the innermost declared scope in its ``op_name`` metadata, the names
  a profiler trace's device operations carry.

Bit-identity: the AOT executable is the same XLA program as the traced
call's, so DP outputs are bit-identical with the flag on vs off —
asserted as PARITY row 31, exactly like trace/audit/heartbeat. Named
scopes change HLO metadata only, never an operation.

This module imports only the stdlib at module level (``obs`` must stay
importable before jax platform selection settles); jax is imported
lazily at decoration/capture time, by which point the decorated module
has long since imported it.
"""

from __future__ import annotations

import collections
import functools
import inspect
import os
import re
import threading
import time as _time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

ENV_VAR = "PIPELINEDP_TPU_COSTS"

#: The phases of the fused aggregation programs, each a
#: ``jax.named_scope`` around the code that does it: the bounding sort
#: (and the total-cap sort), the gathers by its order that remain (rank-2
#: values, the total-cap keep bits), the per-row
#: rank/ordinal/clip masks, the per-partition segment sum, partition
#: selection, on-device noise draws, the cross-chip combine, the
#: compact fetch's output compaction, and the percentile walk's
#: counting (leaf mapping, histograms, the descent).
SCOPES = ("bound_sort", "bound_permute", "bound_caps", "segsum", "select",
          "noise", "combine", "compact", "walk")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES` (any other
    name is a ValueError, so a typo cannot silently leave a phase
    unnamed)."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a declared scope {SCOPES}")
    import jax
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator form of :func:`scope`: the function's whole body runs
    inside a fresh scope per call."""
    scope(name)  # a typo fails at import, not at the first trace

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


#: Every ``instrumented_jit`` program of the process, for
#: :func:`program_scopes`.
_PROGRAMS: "weakref.WeakSet[_InstrumentedFunction]" = weakref.WeakSet()

#: A compile option (at its default) that only GPU compiles read: it
#: keys a compile apart in jax's caches without changing a CPU or TPU
#: program (see ``_InstrumentedFunction.scope_tables``).
_FRESH_COMPILE = {"xla_gpu_enable_fast_min_max": False}

#: Nominal peak FLOP/s and HBM bytes/s per device kind — the roofline
#: ceilings arithmetic intensity is judged against. Matching is by
#: lowercase substring of ``jax.devices()[0].device_kind``. TPU rows
#: are datasheet numbers (dense bf16 FLOP/s; HBM bandwidth); the CPU
#: row is an order-of-magnitude PROXY (one desktop core's vector units
#: and DDR channel) — good enough to rank programs against each other,
#: NOT a calibrated machine model; verdicts carry ``proxy: true``.
DEVICE_PEAKS: Tuple[Dict[str, Any], ...] = (
    {"match": ("v5 lite", "v5lite", "v5e"),
     "kind": "tpu_v5e", "flops_per_s": 197e12,
     "hbm_bytes_per_s": 819e9, "proxy": False},
    {"match": ("v4",),
     "kind": "tpu_v4", "flops_per_s": 275e12,
     "hbm_bytes_per_s": 1228e9, "proxy": False},
    {"match": ("cpu",),
     "kind": "cpu_proxy", "flops_per_s": 1e11,
     "hbm_bytes_per_s": 5e10, "proxy": True},
    # Interpret-mode executors (JAX_PLATFORMS=interpreter, and hosts
    # whose CPU device kind spells it out): Pallas-path programs on
    # the CPU proxy run through the interpreter, and without this row
    # their roofline verdict degraded to "unknown" instead of an
    # order-of-magnitude proxy classification. ~100x below the CPU
    # proxy row — interpreters execute one op at a time.
    {"match": ("interpret", "host"),
     "kind": "cpu_interpret", "flops_per_s": 1e9,
     "hbm_bytes_per_s": 5e8, "proxy": True},
)


def costs_enabled() -> bool:
    """True when ``PIPELINEDP_TPU_COSTS`` requests device-cost capture
    (any value except empty/0/false/off)."""
    return os.environ.get(ENV_VAR, "").lower() not in ("", "0", "false",
                                                       "off")


def device_peaks(device_kind: Optional[str]) -> Optional[Dict[str, Any]]:
    """The peak-table row for a ``device_kind`` string. A TPU kind with
    no row is an error: a roofline share against a made-up ceiling, or
    an ``unknown`` verdict for the chip itself, would hide the gap in
    the table. Any other unmatched kind gets None (verdict
    ``unknown``)."""
    if not device_kind:
        return None
    kind = device_kind.lower()
    for row in DEVICE_PEAKS:
        if any(m in kind for m in row["match"]):
            return row
    if "tpu" in kind:
        raise ValueError(f"no peak row for TPU device kind "
                         f"{device_kind!r}: add it to DEVICE_PEAKS")
    return None


def roofline_verdict(flops: Optional[float],
                     bytes_accessed: Optional[float],
                     peaks: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Classify one program (or one phase's aggregate) against the
    device roofline: ``compute_bound`` when arithmetic intensity
    (flops/byte) is at or above the ridge point (peak FLOP/s over peak
    bytes/s), ``bandwidth_bound`` below it, ``unknown`` when the
    analysis or the peak row is missing."""
    out: Dict[str, Any] = {"verdict": "unknown", "intensity": None,
                           "ridge": None}
    if peaks is not None:
        out["ridge"] = round(peaks["flops_per_s"] /
                             peaks["hbm_bytes_per_s"], 3)
    if flops is None or bytes_accessed is None or bytes_accessed <= 0:
        return out
    # Arithmetic intensity is a property of the PROGRAM — report it
    # even without a peak row (the verdict stays unknown: intensity
    # alone can't place a program against an unknown ridge). Wide-D
    # matmul programs on an unmatched device kind used to lose their
    # intensity here, hiding the one number that shows they are
    # MXU-shaped.
    intensity = flops / bytes_accessed
    out["intensity"] = round(intensity, 4)
    if peaks is None:
        return out
    out["verdict"] = ("compute_bound" if intensity >= out["ridge"]
                      else "bandwidth_bound")
    return out


class CostTable:
    """Process-global per-program cost table (thread-safe). One entry
    per (program, abstract-shape signature) first compile; the run
    report's ``device_costs`` section and ``store --summarize``'s
    cost/roofline columns are views over :meth:`snapshot`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._device_kind: Optional[str] = None
        self._platform: Optional[str] = None

    def note_device(self, platform: Optional[str],
                    device_kind: Optional[str]) -> None:
        with self._lock:
            if device_kind:
                self._device_kind = device_kind
            if platform:
                self._platform = platform

    def record(self, key: str, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._programs[key] = entry

    def note_call(self, key: str) -> None:
        with self._lock:
            entry = self._programs.get(key)
            if entry is not None:
                entry["calls"] = entry.get("calls", 0) + 1

    def reset(self) -> None:
        with self._lock:
            self._programs = {}

    def snapshot(self) -> Dict[str, Any]:
        """The ``device_costs`` section: the peak row in force, every
        program entry, and per-phase aggregates (flops/bytes summed
        over the phase's programs, one roofline verdict per phase —
        ``unknown`` only where no program in the phase carried an
        analysis)."""
        with self._lock:
            programs = {k: dict(v) for k, v in self._programs.items()}
            device_kind = self._device_kind
            platform = self._platform
        peaks = device_peaks(device_kind)
        phases: Dict[str, Dict[str, Any]] = {}
        for entry in programs.values():
            ph = phases.setdefault(entry.get("phase") or "device", {
                "programs": 0, "calls": 0, "compile_s": 0.0,
                "flops": 0.0, "bytes_accessed": 0.0, "analyzed": 0})
            ph["programs"] += 1
            ph["calls"] += entry.get("calls", 0)
            ph["compile_s"] += entry.get("compile_s") or 0.0
            if entry.get("flops") is not None and (
                    entry.get("bytes_accessed") is not None):
                ph["analyzed"] += 1
                ph["flops"] += entry["flops"]
                ph["bytes_accessed"] += entry["bytes_accessed"]
        for ph in phases.values():
            ph["compile_s"] = round(ph["compile_s"], 6)
            verdict = roofline_verdict(
                ph["flops"] if ph["analyzed"] else None,
                ph["bytes_accessed"] if ph["analyzed"] else None, peaks)
            ph.update(verdict)
        return {
            "platform": platform,
            "device_kind": device_kind,
            "peaks": ({k: peaks[k] for k in ("kind", "flops_per_s",
                                             "hbm_bytes_per_s", "proxy")}
                      if peaks else None),
            "programs": programs,
            "phases": phases,
        }


#: The one process-global cost table (``pipelinedp_tpu.obs`` re-exports
#: it; ``obs.reset()`` clears it at run boundaries).
TABLE = CostTable()

#: One lock serializes every AOT capture in the process: compiles are
#: rare and seconds-long, and serializing them keeps the persistent-
#: cache hit/miss attribution (a before/after counter diff) honest.
_CAPTURE_LOCK = threading.Lock()

#: Persistent-compile-cache hit/miss evidence: jax emits monitoring
#: events on each cache probe; one listener (registered at first
#: capture) counts them and the capture diffs before/after.
_CACHE_EVENTS = {"hits": 0, "misses": 0}
_cache_listener_on = False


def _ensure_cache_listener() -> None:
    global _cache_listener_on
    if _cache_listener_on:
        return
    _cache_listener_on = True
    try:
        from jax import monitoring as _mon

        def _on_event(event: str, **kwargs) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                _CACHE_EVENTS["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                _CACHE_EVENTS["misses"] += 1

        _mon.register_event_listener(_on_event)
    except Exception:
        pass  # older jax: verdict stays "unknown"


def _persistent_cache_dir() -> Optional[str]:
    try:
        import jax
        return jax.config.jax_compilation_cache_dir or None
    except Exception:
        return None


def _cost_analysis(compiled) -> Tuple[Optional[Dict[str, float]],
                                      Optional[str]]:
    """(flops/bytes dict, error tag). Tolerates every known shape of
    ``cost_analysis()`` across jax versions: a dict, a one-element list
    of dicts, None, or a raise."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return None, f"cost_analysis: {type(e).__name__}"
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, "cost_analysis: empty"
    out = {}
    for field, key in (("flops", "flops"),
                       ("bytes_accessed", "bytes accessed")):
        v = ca.get(key)
        if isinstance(v, (int, float)):
            out[field] = float(v)
    return (out or None), (None if out else "cost_analysis: no fields")


def _memory_analysis(compiled) -> Tuple[Optional[Dict[str, int]],
                                        Optional[str]]:
    """(memory-stats dict, error tag). ``peak_bytes`` approximates the
    program's HBM high-water mark as arguments + outputs + temps +
    generated code — the components XLA's ``CompiledMemoryStats``
    exposes (aliased pairs are counted once via ``alias_bytes``)."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        return None, f"memory_analysis: {type(e).__name__}"
    if ma is None:
        return None, "memory_analysis: empty"
    out: Dict[str, int] = {}
    for field, attr in (("argument_bytes", "argument_size_in_bytes"),
                        ("output_bytes", "output_size_in_bytes"),
                        ("temp_bytes", "temp_size_in_bytes"),
                        ("alias_bytes", "alias_size_in_bytes"),
                        ("generated_code_bytes",
                         "generated_code_size_in_bytes")):
        v = getattr(ma, attr, None)
        if isinstance(v, int):
            out[field] = v
    if not out:
        return None, "memory_analysis: no fields"
    out["peak_bytes"] = (out.get("argument_bytes", 0) +
                         out.get("output_bytes", 0) +
                         out.get("temp_bytes", 0) +
                         out.get("generated_code_bytes", 0) -
                         out.get("alias_bytes", 0))
    return out, None


#: Marks a signature whose AOT capture failed: calls fall back to the
#: plain jitted path for good (one event, no retry storm).
_FALLBACK = object()


class _InstrumentedFunction:
    """The callable :func:`instrumented_jit` returns: ``jax.jit(fn)``
    plus, under ``PIPELINEDP_TPU_COSTS``, an AOT compile-and-capture
    per abstract-shape signature with dispatch through the captured
    executable (one XLA compile per program, ever)."""

    def __init__(self, fn: Callable, phase: str,
                 jit_kwargs: Dict[str, Any]):
        import jax
        self._fn = fn
        self._phase = phase
        self._jit_kwargs = jit_kwargs
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._cache_size = self._jitted._cache_size
        self._compiled: Dict[Any, Any] = {}
        #: abstract signature key -> (args, kwargs) to lower it again,
        #: and -> (module name, fingerprint, scope table) once lowered.
        self._signatures: Dict[Any, Tuple[tuple, dict]] = {}
        self._scope_tables: Dict[Any, Tuple[str, str, Dict[str, str]]] = {}
        self._lock = threading.Lock()
        _PROGRAMS.add(self)
        # Static-parameter resolution mirrors jax's: names from
        # ``static_argnames``, positions from ``static_argnums``,
        # mapped onto the function's signature once.
        names = jit_kwargs.get("static_argnames") or ()
        if isinstance(names, str):
            names = (names,)
        nums = jit_kwargs.get("static_argnums")
        if nums is None:
            nums = ()
        elif isinstance(nums, int):
            nums = (nums,)
        params = list(inspect.signature(fn).parameters.values())
        self._exotic = any(
            p.kind in (inspect.Parameter.VAR_POSITIONAL,
                       inspect.Parameter.VAR_KEYWORD) for p in params)
        self._pos_names = tuple(p.name for p in params)
        self._static_names = frozenset(names) | frozenset(
            self._pos_names[i] for i in nums
            if 0 <= i < len(self._pos_names))
        functools.update_wrapper(self, fn)

    def __getattr__(self, name: str) -> Any:
        # Everything jax.jit exposes (``lower``, ``trace``,
        # ``clear_cache``, ...) passes through untouched.
        return getattr(self._jitted, name)

    def __call__(self, *args, **kwargs):
        if not costs_enabled():
            # jax's dispatch cache grows exactly when this call's
            # signature is new: only then is it worth keeping.
            before = self._cache_size()
            out = self._jitted(*args, **kwargs)
            if self._cache_size() != before:
                self._note_signature(args, kwargs)
            return out
        split = self._split(args, kwargs)
        if split is None:  # *args/**kwargs signature: capture skipped
            return self._jitted(*args, **kwargs)
        key, dyn_args, dyn_kwargs = split
        entry = self._compiled.get(key)
        if entry is None:
            entry = self._capture(key, args, kwargs)
        if entry is _FALLBACK:
            return self._jitted(*args, **kwargs)
        compiled, table_key = entry
        TABLE.note_call(table_key)
        try:
            return compiled(*dyn_args, **dyn_kwargs)
        except Exception as e:
            # The signature key sees abstract shapes, not input
            # sharding/placement — an AOT executable is stricter than
            # jax.jit about those, and capture must never take an
            # aggregation down: fall back to the traced path (which
            # recompiles for the new placement like any jit call).
            from pipelinedp_tpu import obs
            obs.inc("cost.dispatch_fallbacks")
            obs.event("cost.dispatch_fallback",
                      program=self._fn.__name__,
                      error=f"{type(e).__name__}: {e}")
            return self._jitted(*args, **kwargs)

    # --- signature handling ---

    def _split(self, args, kwargs):
        """(hashable signature key, dynamic args, dynamic kwargs) for
        one call, or None when the wrapped signature is too exotic to
        split (``*args``/``**kwargs`` — none of the instrumented
        kernels are). The key mirrors jax's dispatch cache key: static
        values by equality, dynamic leaves by abstract shape/dtype,
        plus the dynamic pytree structure — AND the positional/keyword
        split of the call, which the AOT executable also pins."""
        if self._exotic or len(args) > len(self._pos_names):
            return None
        from jax.api_util import shaped_abstractify
        from jax.tree_util import tree_flatten
        statics: List[Tuple[str, Any]] = []
        dyn_args: List[Any] = []
        dyn_kwargs: Dict[str, Any] = {}
        for i, a in enumerate(args):
            name = self._pos_names[i]
            if name in self._static_names:
                statics.append((name, a))
            else:
                dyn_args.append(a)
        for name in sorted(kwargs):
            if name in self._static_names:
                statics.append((name, kwargs[name]))
            else:
                dyn_kwargs[name] = kwargs[name]
        leaves, treedef = tree_flatten((tuple(dyn_args), dyn_kwargs))
        try:
            avals = tuple(str(shaped_abstractify(leaf))
                          for leaf in leaves)
            key = (tuple(statics), treedef, avals)
            # lint: disable=sketch-confinement(in-process hashability probe of a jit signature tuple, not a data key)
            hash(key)
        except TypeError:
            return None  # unhashable static: let jax handle it
        return key, dyn_args, dyn_kwargs

    def _note_signature(self, args, kwargs) -> None:
        """Keeps the abstract signature of one call: static values as
        given, every other leaf as a ``ShapeDtypeStruct`` (with its
        sharding where the array is committed to one), so no array
        stays referenced. Never raises: an unkeepable signature only
        leaves its program out of :func:`program_scopes`."""
        import jax
        from jax.api_util import shaped_abstractify

        def abstract(x):
            aval = shaped_abstractify(x)
            sharding = (x.sharding if getattr(x, "_committed", False)
                        else None)
            return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                        sharding=sharding,
                                        weak_type=aval.weak_type)

        def keep(name, value):
            return (value if name in self._static_names
                    else jax.tree.map(abstract, value))

        try:
            a_args = tuple(keep(self._pos_names[i], a)
                           for i, a in enumerate(args))
            a_kwargs = {k: keep(k, v) for k, v in kwargs.items()}
            leaves, treedef = jax.tree.flatten((a_args, a_kwargs))
            key = (treedef, tuple(leaves))
            # lint: disable=sketch-confinement(in-process hashability probe of a jit signature tuple, not a data key)
            hash(key)
        except Exception as e:  # a signature must never fail the call
            from pipelinedp_tpu import obs
            obs.event("cost.signature_skipped", program=self._fn.__name__,
                      error=f"{type(e).__name__}: {e}")
            return
        with self._lock:
            self._signatures.setdefault(key, (a_args, a_kwargs))

    def scope_tables(self) -> List[Tuple[str, str, Dict[str, str]]]:
        """(HLO module name, executable fingerprint, {instruction:
        scope}) for every signature this program ran with, compiling
        the ones not yet mapped (from the persistent cache where it
        holds them)."""
        with self._lock:
            pending = [(k, v) for k, v in self._signatures.items()
                       if k not in self._scope_tables]
        for key, (a_args, a_kwargs) in pending:
            lowered = self._jitted.lower(*a_args, **a_kwargs)
            compiled = lowered.compile()
            module, table = hlo_scope_table(compiled.as_text())
            if not table and any(f"/{s}/" in lowered.as_text(debug_info=True)
                                 for s in SCOPES):
                # The compile caches key programs with their metadata
                # stripped, so they can hand back an executable built
                # from the same program before it named its scopes.
                # An option that is in the key but read by no CPU or
                # TPU compile misses both caches; XLA names the
                # instructions of the same program the same way.
                try:
                    fresh = lowered.compile(compiler_options=_FRESH_COMPILE)
                except Exception as e:  # the table stays empty, loudly
                    from pipelinedp_tpu import obs
                    obs.event("cost.scope_table_stale", program=module,
                              error=f"{type(e).__name__}: {e}")
                else:
                    module, table = hlo_scope_table(fresh.as_text())
            with self._lock:
                self._scope_tables[key] = (module, _fingerprint(compiled),
                                           table)
        with self._lock:
            return list(self._scope_tables.values())

    def _table_key(self, key) -> str:
        # lint: disable=sketch-confinement(in-process program-table digest of a jit signature, not a data key; never persisted)
        return f"{self._fn.__name__}#{abs(hash(key)) % (16 ** 8):08x}"

    def _signature_label(self, key) -> str:
        statics, _, avals = key
        frags = [f"{n}={v!r}" if not hasattr(v, "axis_names")
                 else f"{n}=<mesh>" for n, v in statics]
        frags.extend(avals)
        label = ", ".join(frags)
        return label if len(label) <= 512 else label[:509] + "..."

    # --- the capture ---

    def _capture(self, key, args, kwargs):
        """One AOT compile-and-record for ``key``; returns the cached
        ``(compiled, table_key)`` pair (or ``_FALLBACK``)."""
        from pipelinedp_tpu import obs
        with _CAPTURE_LOCK:
            entry = self._compiled.get(key)
            if entry is not None:
                return entry
            _ensure_cache_listener()
            name = self._fn.__name__
            cache_dir = _persistent_cache_dir()
            hits0, misses0 = (_CACHE_EVENTS["hits"],
                              _CACHE_EVENTS["misses"])
            # obs/ is the one package allowed the raw timer; the span
            # only reaches the ledger when tracing is ALSO on, so the
            # wall time is measured here and stored in the table.
            t0 = _time.perf_counter()
            try:
                with obs.tracer().span("compile.program", cat="compile",
                                       program=name, phase=self._phase):
                    compiled = self._jitted.lower(*args,
                                                  **kwargs).compile()
            except Exception as e:
                obs.inc("cost.capture_errors")
                obs.event("cost.capture_error", program=name,
                          error=f"{type(e).__name__}: {e}")
                self._compiled[key] = _FALLBACK
                return _FALLBACK
            compile_s = _time.perf_counter() - t0
            # Best-effort attribution: _CAPTURE_LOCK serializes the
            # instrumented captures, but an un-instrumented jax.jit
            # compiling concurrently on another thread can fire cache
            # events inside this window and alias the verdict.
            if cache_dir is None:
                cache = "disabled"
            elif _CACHE_EVENTS["hits"] > hits0:
                cache = "hit"
            elif _CACHE_EVENTS["misses"] > misses0:
                cache = "miss"
            else:
                cache = "unknown"
            try:
                import jax
                dev = jax.devices()[0]
                TABLE.note_device(dev.platform, dev.device_kind)
                device_kind = dev.device_kind
            except Exception:
                device_kind = None
            costs, cost_err = _cost_analysis(compiled)
            memory, mem_err = _memory_analysis(compiled)
            unavailable = [e for e in (cost_err, mem_err) if e]
            if unavailable:
                obs.inc("cost.unavailable")
                obs.event("cost.unavailable", program=name,
                          analyses=", ".join(unavailable))
            flops = (costs or {}).get("flops")
            bytes_accessed = (costs or {}).get("bytes_accessed")
            verdict = roofline_verdict(flops, bytes_accessed,
                                       device_peaks(device_kind))
            table_key = self._table_key(key)
            TABLE.record(table_key, {
                "program": name,
                "phase": self._phase,
                "signature": self._signature_label(key),
                "compile_s": round(compile_s, 6),
                "compile_cache": cache,
                "flops": flops,
                "bytes_accessed": bytes_accessed,
                "intensity": verdict["intensity"],
                "verdict": verdict["verdict"],
                "memory": memory,
                "unavailable": unavailable or None,
                "calls": 0,
            })
            obs.inc("cost.programs_captured")
            self._note_signature(args, kwargs)
            entry = (compiled, table_key)
            # lint: disable=blocking-under-lock(leaf dict lock; never held around _CAPTURE_LOCK)
            with self._lock:
                self._compiled[key] = entry
            return entry


def instrumented_jit(fn: Optional[Callable] = None, *,
                     phase: str = "device", **jit_kwargs):
    """Drop-in ``functools.partial(jax.jit, ...)`` replacement that
    feeds the device-cost observatory. ``phase`` labels the program's
    roofline bucket (``pass_a`` / ``pass_b`` / ``walk`` / ...). Usable
    bare (``@instrumented_jit``) or configured
    (``@instrumented_jit(phase="walk", static_argnames=(...))``)."""
    if fn is not None:
        return _InstrumentedFunction(fn, phase, jit_kwargs)

    def wrap(f: Callable) -> _InstrumentedFunction:
        return _InstrumentedFunction(f, phase, jit_kwargs)
    return wrap


# --- HLO instruction -> named scope (profiler trace attribution) ---

_HLO_MODULE = re.compile(r"HloModule\s+([^\s,]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_APPLIES = re.compile(r"\bto_apply=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_COLLECTIVE = re.compile(
    r"\s(?:all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_HLO_CONTROL = re.compile(r"\s(?:conditional|while)\(")


def _op_scope(op_name: str) -> Optional[str]:
    """The innermost declared scope on an ``op_name`` path
    (``jit(f)/shard_map/bound_caps/segsum/scatter-add`` -> segsum)."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def hlo_scope_table(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: scope}) of one compiled
    program's HLO text, for the instructions a profiler trace shows as
    device operations: those of every computation that is not a fusion
    body or an applied reduction. An instruction takes the scope of its
    own ``op_name``; a fusion whose ``op_name`` names none takes its
    root's, then the scope most of its instructions name; a cross-chip
    collective still without one is ``combine`` (XLA merges the
    combine's collectives into one and drops their metadata); any other
    instruction without one (a layout copy, a scan XLA emits without
    metadata) takes its first scoped operand's. Instructions under no
    declared scope are left out, and so are ``conditional`` and
    ``while``: on a trace their interval encloses the operations of
    their branches and body, which are mapped themselves, so mapping
    both would count those seconds twice."""
    m = _HLO_MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    comps: Dict[str, List[Tuple[str, Optional[str], Optional[str],
                                bool, bool, List[str]]]] = {}
    inner, control = set(), set()
    current: Optional[List] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            c = _HLO_COMPUTATION.match(line)
            current = comps.setdefault(c.group(1), []) if c else None
            continue
        i = _HLO_INSTRUCTION.match(line)
        if current is None or i is None:
            continue
        if _HLO_CONTROL.search(line):
            control.add(i.group(2))
        op = _HLO_OP_NAME.search(line)
        calls = _HLO_CALLS.search(line)
        inner.update(x.group(1) for x in (calls, _HLO_APPLIES.search(line))
                     if x)
        current.append((i.group(2), _op_scope(op.group(1)) if op else None,
                        calls.group(1) if calls else None, bool(i.group(1)),
                        bool(_HLO_COLLECTIVE.search(line)),
                        _HLO_OPERAND.findall(line[i.end():])))

    def comp_scope(name: str, depth: int = 0) -> Optional[str]:
        body = comps.get(name, ())
        for _, own, calls, root, _, _ in body:
            if root:
                got = own or (comp_scope(calls, depth + 1)
                              if calls and depth < 8 else None)
                if got:
                    return got
        votes = collections.Counter(b[1] for b in body if b[1])
        return votes.most_common(1)[0][0] if votes else None

    table: Dict[str, str] = {}
    for name, body in comps.items():
        if name in inner:
            continue
        for instr, own, calls, _, collective, operands in body:
            got = (own or (comp_scope(calls) if calls else None) or
                   ("combine" if collective else None) or
                   next((table[o] for o in operands if o in table), None))
            if got and instr not in control:
                table[instr] = got
    return module, table


def _fingerprint(compiled) -> str:
    """The executable's fingerprint as text: as given where the backend
    spells it in digits (CPU), else its bytes in hex (TPU)."""
    raw = compiled.runtime_executable().fingerprint
    if isinstance(raw, bytes):
        return raw.decode() if raw.isdigit() else raw.hex()
    return str(raw)


def program_scopes() -> Dict[str, Dict[str, str]]:
    """``{program: {HLO instruction name: scope}}`` for every
    ``instrumented_jit`` program that has run in this process, compiled
    on request (not on any hot path). ``program`` is the HLO module
    name a profiler trace gives the program's ``XLA Modules`` events
    (``jit_fused_aggregate_kernel``); a program that ran with several
    signatures keys each apart as ``<name>(<fingerprint>)``, the
    fingerprint of its executable (:func:`_fingerprint`)."""
    by_module: Dict[str, List[Tuple[str, Dict[str, str]]]] = {}
    for fn in list(_PROGRAMS):
        for module, fingerprint, table in fn.scope_tables():
            by_module.setdefault(module, []).append((fingerprint, table))
    out: Dict[str, Dict[str, str]] = {}
    for module, tables in by_module.items():
        for fingerprint, table in tables:
            out[module if len(tables) == 1
                else f"{module}({fingerprint})"] = table
    return out


# --- HBM watermark sampling (monitor beat hook) ---

_HBM_LOCK = threading.Lock()
_HBM = {"live_bytes": None, "watermark": 0}


def sample_live_bytes() -> Optional[int]:
    """Sum live device-array bytes (``jax.live_arrays()``) into the
    ``hbm.live_bytes`` gauge, the ``hbm.watermark`` running max and the
    ledger time-series behind the Chrome-trace counter track. Called by
    the monitor each heartbeat beat; a no-op (None) when
    ``PIPELINEDP_TPU_COSTS`` is off or jax is unavailable — sampling
    must never take the beat down."""
    if not costs_enabled():
        return None
    try:
        import jax
        n = sum(int(a.nbytes) for a in jax.live_arrays())
    except Exception:
        return None
    from pipelinedp_tpu import obs
    from pipelinedp_tpu.obs.tracer import trace_enabled
    with _HBM_LOCK:
        _HBM["live_bytes"] = n
        _HBM["watermark"] = max(_HBM["watermark"], n)
    led = obs.ledger()
    led.gauge("hbm.live_bytes", n)
    led.gauge_max("hbm.watermark", n)
    # The time series only feeds the Chrome-trace counter track, so it
    # accumulates only when tracing will export it (same gate as the
    # sampled progress counters).
    if trace_enabled():
        led.sample("hbm.live_bytes", n)
    return n


def hbm_snapshot() -> Optional[Dict[str, int]]:
    """{live_bytes, watermark} from the most recent sample, or None
    before the first one (the heartbeat omits the section then)."""
    with _HBM_LOCK:
        if _HBM["live_bytes"] is None:
            return None
        return {"live_bytes": _HBM["live_bytes"],
                "watermark": _HBM["watermark"]}


def reset() -> None:
    """Clear the cost table and HBM watermark (run boundaries; tests).
    Captured executables stay cached on their wrappers — the programs
    are still compiled, only the RECORD restarts."""
    TABLE.reset()
    with _HBM_LOCK:
        _HBM["live_bytes"] = None
        _HBM["watermark"] = 0
