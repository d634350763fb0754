"""The nine Makefile grep lints, ported to precise AST rules.

Each rule keeps the legacy target's name as its id (so `make nosleep`
stays meaningful as a thin alias) and the legacy scoping, but gains
what grep never had: strings and docstrings can mention the banned
names freely, aliases (``_time.sleep``) are still caught, and the
"max 2 stager sites, only in these functions" shape checks that used
to live only in the test twins are enforced everywhere the engine
runs.
"""

from __future__ import annotations

import ast

from pipelinedp_tpu.lint.rules.base import (Rule, dotted_name,
                                            import_bindings,
                                            receiver_terminal,
                                            subtree_names,
                                            terminal_name,
                                            walk_with_function)


class NoSleepRule(Rule):
    """No direct ``time.sleep`` and no bare ``threading.Thread``."""

    id = "nosleep"
    legacy_target = "nosleep"
    invariant = ("waits route through the injectable resilience clock; "
                 "worker threads through the ingest executor's "
                 "cancellable lifecycle (fault kills must drain to "
                 "zero orphan pdp-* threads)")
    fix_hint = ("use pipelinedp_tpu.resilience.clock for sleeps and "
                "the pipelinedp_tpu.ingest executor for threads")
    blessed = ()
    _SLEEP_EXEMPT = ("pipelinedp_tpu/resilience/clock.py",)
    _THREAD_EXEMPT = ("pipelinedp_tpu/ingest/",
                      "pipelinedp_tpu/resilience/")

    def check(self, ctx):
        sleep_ok = any(ctx.rel == p or ctx.rel.startswith(p)
                       for p in self._SLEEP_EXEMPT)
        thread_ok = any(ctx.rel == p or ctx.rel.startswith(p)
                        for p in self._THREAD_EXEMPT)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                recv = receiver_terminal(fn)
                if (not sleep_ok and terminal_name(fn) == "sleep"
                        and recv is not None
                        and recv.endswith("time")):
                    yield (node.lineno,
                           "direct time.sleep — waits must route "
                           "through resilience.clock")
                if (not thread_ok
                        and terminal_name(fn) == "Thread"
                        and recv == "threading"):
                    yield (node.lineno,
                           "bare threading.Thread — worker threads "
                           "must use the ingest executor")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = {a.name for a in node.names}
                if not sleep_ok and mod == "time" and "sleep" in names:
                    yield (node.lineno,
                           "from-import of time.sleep — waits must "
                           "route through resilience.clock")
                if (not thread_ok and mod == "threading"
                        and "Thread" in names):
                    yield (node.lineno,
                           "from-import of threading.Thread — worker "
                           "threads must use the ingest executor")


class NoFoldinRule(Rule):
    """No per-element ``vmap(fold_in)`` key schedules."""

    id = "nofoldin"
    legacy_target = "nofoldin"
    invariant = ("per-element vmap(fold_in) rebuilds a full threefry "
                 "key schedule per element — the cost the counter-based "
                 "node-noise generator removed from the quantile walk")
    fix_hint = "use pipelinedp_tpu.ops.counter_rng (counter-based keys)"
    blessed = ("pipelinedp_tpu/ops/counter_rng.py",)

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fnames = subtree_names(node.func)
            if "vmap" not in fnames and "fold_in" not in fnames:
                continue
            allnames = subtree_names(node)
            if "vmap" in allnames and "fold_in" in allnames:
                yield (node.lineno,
                       "vmap(fold_in) per-element key construction")


class NoStagerRule(Rule):
    """``BackgroundStager`` construction is confined, and the two
    consumer modules keep exactly their blessed sites."""

    id = "nostager"
    legacy_target = "nostager"
    invariant = ("pass-B restreaming flows through the sweep planner's "
                 "ONE stream source (and the sketch phase through its "
                 "one accumulation loop); stray stager constructions "
                 "silently reintroduce per-tile restreaming")
    fix_hint = ("stream through streaming.run_sweep / "
                "sketch.engine._accumulate_stream / the ingest "
                "package; do not construct BackgroundStager directly")
    blessed = ("pipelinedp_tpu/ingest/",)
    #: consumer module -> (allowed enclosing functions, max sites).
    _CONSUMERS = {
        "pipelinedp_tpu/streaming.py": (
            frozenset({"_stream_impl", "run_sweep"}), 2,
            "pass A's overlapped loop (inside the elastic wrapper's "
            "_stream_impl) and run_sweep"),
        "pipelinedp_tpu/sketch/engine.py": (
            frozenset({"_accumulate_stream"}), 1,
            "the sketch accumulation loop"),
    }

    def check(self, ctx):
        sites = []
        for node, func in walk_with_function(ctx.tree):
            if (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "BackgroundStager"):
                sites.append((node.lineno, func))
        consumer = self._CONSUMERS.get(ctx.rel)
        if consumer is None:
            for line, _ in sites:
                yield (line, "direct BackgroundStager construction "
                       "outside ingest/ and the blessed consumers")
            return
        allowed, max_sites, prose = consumer
        for line, func in sites:
            if func not in allowed:
                yield (line,
                       f"BackgroundStager site in '{func}' — only "
                       f"{prose} may build stagers here")
        if len(sites) > max_sites:
            for line, _ in sites[max_sites:]:
                yield (line,
                       f"{len(sites)} stager sites in {ctx.rel} "
                       f"(max {max_sites}: {prose})")


class NoPerfRule(Rule):
    """No raw ``perf_counter`` outside obs/, and ``obs/monitor.py``
    never touches the ``time`` module at all."""

    id = "noperf"
    legacy_target = "noperf"
    invariant = ("measured phases flow through obs spans so they land "
                 "in the run ledger; the watchdog's deadline story "
                 "rides the injectable clock, so monitor.py gets the "
                 "stricter no-time-module check")
    fix_hint = ("time through pipelinedp_tpu.obs spans; in "
                "obs/monitor.py use the injectable resilience clock")
    _MONITOR = "pipelinedp_tpu/obs/monitor.py"

    def check(self, ctx):
        in_obs = ctx.rel.startswith("pipelinedp_tpu/obs/")
        is_monitor = ctx.rel == self._MONITOR
        if in_obs and not is_monitor:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                if terminal_name(fn) == "perf_counter":
                    yield (node.lineno,
                           "raw perf_counter timing — route through "
                           "obs spans" if not is_monitor else
                           "raw perf_counter in the monitor — use the "
                           "injectable clock")
            if not is_monitor:
                continue
            # monitor.py: ANY use of the time module is a finding
            # (time.monotonic would dodge a perf_counter-only check).
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mod = getattr(node, "module", "") or ""
                names = [a.name for a in node.names]
                if mod == "time" or "time" in names:
                    yield (node.lineno,
                           "obs/monitor.py imports time — all timing "
                           "must ride the injectable clock")
            elif (isinstance(node, ast.Attribute)
                  and isinstance(node.value, ast.Name)
                  and node.value.id in ("time", "_time")):
                yield (node.lineno,
                       f"obs/monitor.py touches time.{node.attr} — "
                       "all timing must ride the injectable clock")


class NoArtifactsRule(Rule):
    """No ad-hoc ``json.dump`` file writes outside obs/."""

    id = "noartifacts"
    legacy_target = "noartifacts"
    invariant = ("run knowledge lands in the schema-versioned "
                 "report/store, never scattered one-off JSON "
                 "files (bench.py is the one blessed artifact emitter)")
    fix_hint = "route through pipelinedp_tpu.obs (report/store)"
    blessed = ("pipelinedp_tpu/obs/",)
    scans_bench = False  # bench.py is the blessed emitter

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "dump"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "json"):
                yield (node.lineno, "ad-hoc json.dump artifact write")


class NoCostRule(Rule):
    """Compiled-program analysis calls confined to obs/."""

    id = "nocost"
    legacy_target = "nocost"
    invariant = ("cost_analysis/memory_analysis/live_arrays flow "
                 "through the device-cost observatory so every "
                 "measurement lands in the versioned run report")
    fix_hint = ("use pipelinedp_tpu.obs.costs (instrumented_jit / "
                "sample_live_bytes)")
    blessed = ("pipelinedp_tpu/obs/",)
    _BANNED = frozenset({"cost_analysis", "memory_analysis",
                         "live_arrays"})

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and terminal_name(node.func) in self._BANNED):
                yield (node.lineno,
                       f"direct {terminal_name(node.func)}() call")


class NoKnobsRule(Rule):
    """Registered knob constants are read only through the plan
    registry; the defining modules keep Store-context seams."""

    id = "noknobs"
    legacy_target = "noknobs"
    invariant = ("every knob consumer resolves through plan.knobs "
                 "(env > seam > default) so an env override or a test "
                 "seam reaches every reader and the resolution lands "
                 "in the run report's plan section")
    fix_hint = ("resolve through pipelinedp_tpu.plan (knob_value / "
                "knobs.resolve_all / seam_override)")
    blessed = ("pipelinedp_tpu/plan/",)
    KNOB_CONSTANTS = frozenset({"_SUBHIST_BYTE_CAP",
                                "_SELECT_UNITS_CAP",
                                "_TREE_ROWS_CAP", "_Q_CHUNK"})
    DEFINING = {"_SUBHIST_BYTE_CAP": "pipelinedp_tpu/jax_engine.py",
                "_SELECT_UNITS_CAP": "pipelinedp_tpu/streaming.py",
                "_TREE_ROWS_CAP": "pipelinedp_tpu/streaming.py",
                "_Q_CHUNK": "pipelinedp_tpu/streaming.py"}

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            name = ctxk = None
            if (isinstance(node, ast.Name)
                    and node.id in self.KNOB_CONSTANTS):
                name, ctxk = node.id, node.ctx
            elif (isinstance(node, ast.Attribute)
                  and node.attr in self.KNOB_CONSTANTS):
                name, ctxk = node.attr, node.ctx
            if name is None:
                continue
            if (isinstance(ctxk, ast.Store)
                    and ctx.rel == self.DEFINING.get(name)):
                continue  # the definition IS the seam
            yield (node.lineno, f"direct knob-constant access: {name}")


class NoPallasRule(Rule):
    """Pallas imports confined to ops/kernels/."""

    id = "nopallas"
    legacy_target = "nopallas"
    invariant = ("every module dispatches through ops.kernels "
                 "(kernel_backend knob -> select_backend) so fallback "
                 "events, envelope checks and the interpret-mode story "
                 "stay in ONE place; you cannot call pallas without "
                 "importing it, so the import ban is the precise form")
    fix_hint = "dispatch through pipelinedp_tpu.ops.kernels"
    blessed = ("pipelinedp_tpu/ops/kernels/",)

    def check(self, ctx):
        # One finding per line: a nested chain like
        # jax.experimental.pallas.pallas_call(...) matches several
        # node forms but is one violation.
        hits = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                if any("pallas" in n
                       for n in import_bindings(node)):
                    hits.setdefault(node.lineno,
                                    "pallas import outside "
                                    "ops/kernels/")
            elif isinstance(node, ast.Call):
                # The import ban alone misses attribute access through
                # an already-imported submodule
                # (jax.experimental.pallas.pallas_call(...)) and the
                # conventional `pl.` alias — the legacy grep banned
                # both call forms explicitly.
                if terminal_name(node.func) == "pallas_call":
                    hits.setdefault(node.lineno,
                                    "pallas_call site outside "
                                    "ops/kernels/")
            elif isinstance(node, ast.Attribute):
                dotted = dotted_name(node) or ""
                if (dotted.startswith("pl.")
                        or ".pallas." in f".{dotted}."):
                    hits.setdefault(node.lineno,
                                    f"pallas attribute access "
                                    f"({dotted}) outside ops/kernels/")
        for line in sorted(hits):
            yield (line, hits[line])


class NoServeRule(Rule):
    """The service depends on the engine, never the reverse; durable
    budget-ledger state has ONE writer stack."""

    id = "noserve"
    legacy_target = "noserve"
    invariant = ("batch mode stays byte-for-byte oblivious to serving "
                 "(no serve imports outside serve/), and "
                 "TenantBudgetLedger construction is confined to "
                 "serve/ + budget_accounting.py so budget debits have "
                 "one durable writer stack")
    fix_hint = ("route budget debits through the serve layer's "
                "durable ledger; never import pipelinedp_tpu.serve "
                "from engine modules")
    blessed = ("pipelinedp_tpu/serve/",)
    _LEDGER_EXTRA_BLESSED = ("pipelinedp_tpu/budget_accounting.py",)

    def check(self, ctx):
        ledger_ok = ctx.rel in self._LEDGER_EXTRA_BLESSED
        for node in ast.walk(ctx.tree):
            if (isinstance(node, (ast.Import, ast.ImportFrom))
                    and ctx.rel != "bench.py"):
                if any(n == "pipelinedp_tpu.serve"
                       or n.startswith("pipelinedp_tpu.serve.")
                       for n in import_bindings(node)):
                    yield (node.lineno,
                           "serve import in a batch-engine module — "
                           "the service depends on the engine, never "
                           "the reverse")
            if (not ledger_ok and isinstance(node, ast.Call)
                    and terminal_name(node.func)
                    == "TenantBudgetLedger"):
                yield (node.lineno,
                       "TenantBudgetLedger construction outside "
                       "serve/ + budget_accounting.py")


class FusionMaskingRule(Rule):
    """Fused-batch pad-mask construction is confined to the serve
    fusion layer + the blessed ``jax_engine`` batched-kernel seam."""

    id = "fusion-masking"
    legacy_target = None  # born with `make fusecheck`, never a grep
    invariant = ("request padding for fused batches is built ONLY by "
                 "serve/fusion.pad_request_to_bucket (the validity "
                 "mask is constructed alongside the padding) and "
                 "dispatched ONLY through jax_engine's batched-kernel "
                 "seam from serve/fusion.py — the engine must never "
                 "see unmasked padded rows, because only the mask "
                 "keeps bucket padding out of released values")
    fix_hint = ("pad through pipelinedp_tpu.serve.fusion."
                "pad_request_to_bucket and dispatch fused batches "
                "from serve/fusion.py only")
    blessed = ("pipelinedp_tpu/serve/fusion.py",)
    #: jax_engine DEFINES the batched kernel (and may dispatch it from
    #: its own blessed seam); everywhere else a dispatch site means a
    #: second pad/mask policy is growing.
    _KERNEL_EXTRA_BLESSED = ("pipelinedp_tpu/jax_engine.py",)

    def check(self, ctx):
        kernel_ok = ctx.rel in self._KERNEL_EXTRA_BLESSED
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = terminal_name(node.func)
            if name == "pad_request_to_bucket":
                yield (node.lineno,
                       "fused-batch pad-mask construction outside "
                       "serve/fusion.py")
            elif (name == "fused_aggregate_batch_kernel"
                  and not kernel_ok):
                yield (node.lineno,
                       "batched-kernel dispatch outside the blessed "
                       "serve-fusion seam")


class SketchConfinementRule(Rule):
    """Key hashing and candidate-table construction are confined to
    ``sketch/``; raw builtin ``hash()`` is banned on keys everywhere.

    Python's builtin hash is salted per process (``PYTHONHASHSEED``):
    a key bucketed with it lands in DIFFERENT buckets across runs,
    resumes and mesh processes, which silently voids sketch replay
    and candidate-table stability. The seeded stable hash
    (``sketch/hashing.py``) is the one blessed key hash for
    replayable key→bucket maps; ``__hash__`` protocol implementations
    are exempt (dict/set membership is in-process by definition), and
    equality-semantic in-process uses stay on builtin hash() via a
    reasoned suppression."""

    id = "sketch-confinement"
    legacy_target = None  # born with `make sketchcheck`, never a grep
    invariant = ("key→bucket maps are pure functions of (key bytes, "
                 "seed): raw hash() is process-salted and cannot "
                 "replay; bucket derivation and candidate tables have "
                 "ONE owner (sketch/) so the DP selection's "
                 "sensitivity story cannot fork")
    fix_hint = ("hash keys via pipelinedp_tpu.sketch.hashing "
                "(stable_hash64 / stable_hash_any); build candidate/"
                "bucket tables only inside sketch/")
    blessed = ("pipelinedp_tpu/sketch/",)
    #: Construction confined to sketch/: deriving bucket rows and
    #: building the key→candidate-id table ARE the sketch mechanism —
    #: a second site means a second sensitivity story.
    _CONFINED_CALLS = frozenset({"bucket_ids", "build_candidate_table"})

    def check(self, ctx):
        for node, func in walk_with_function(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if (isinstance(fn, ast.Name) and fn.id == "hash"
                    and func != "__hash__"):
                yield (node.lineno,
                       "raw builtin hash() — process-salted, cannot "
                       "replay; use sketch.hashing.stable_hash_any")
            elif terminal_name(fn) in self._CONFINED_CALLS:
                yield (node.lineno,
                       f"{terminal_name(fn)}() outside sketch/ — "
                       "bucket/candidate-table construction has one "
                       "owner")


class SocketConfinementRule(Rule):
    """Raw wire machinery (``socket`` / ``http.server`` /
    ``socketserver``) is confined to ``obs/http.py``.

    The introspection endpoint is the repo's ONE wire surface, and it
    is read-only by construction. A second module opening sockets
    would grow a second listener lifecycle outside the serve drain
    discipline (orphan accept threads survive ``Service.close``) and a
    second place where per-tenant budget state could leak off-box.
    You cannot serve a port without importing the machinery, so the
    import ban is the precise form — client-side stdlib
    (``urllib``, ``http.client``) stays free for tests and tools."""

    id = "socket-confinement"
    legacy_target = None  # born with `make metricscheck`, never a grep
    invariant = ("the process has ONE wire surface — the read-only "
                 "obs/http.py introspection endpoint, whose accept "
                 "thread the serve lifecycle starts and drains; any "
                 "other socket/http.server/socketserver import grows "
                 "an unmanaged listener")
    fix_hint = ("expose data through pipelinedp_tpu.obs.http "
                "(maybe_start / IntrospectionServer); never open "
                "sockets elsewhere")
    blessed = ("pipelinedp_tpu/obs/http.py",)
    _BANNED_MODULES = ("socket", "socketserver", "http.server")

    def check(self, ctx):
        hits = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            for name in import_bindings(node):
                if any(name == m or name.startswith(m + ".")
                       for m in self._BANNED_MODULES):
                    hits.setdefault(
                        node.lineno,
                        f"wire-machinery import ({name}) outside "
                        "obs/http.py — the introspection endpoint is "
                        "the one wire surface")
        for line in sorted(hits):
            yield (line, hits[line])


class CollectiveConfinementRule(Rule):
    """Cross-device collectives (``psum`` / ``psum_scatter`` /
    ``all_gather``) are confined to ``parallel/sharded.py``.

    The topology layer holds three invariants at its one seam: the
    exchange ORDER is fixed (hier-vs-flat bit-parity rests on both
    paths reducing through the same deterministic trees — PARITY row
    43), every exchange is byte-accounted (``comms.ici_bytes`` /
    ``comms.dcn_bytes``), and the ``mesh_topology`` knob steers every
    exchange. A raw ``jax.lax`` collective anywhere else is invisible
    to all three: it ignores the topology (owner-block traffic back on
    DCN at ICI cadence), skips the byte meter, and its reduction
    grouping is outside the parity contract."""

    id = "collective-confinement"
    legacy_target = None  # born with `make topocheck`, never a grep
    invariant = ("every cross-device collective goes through "
                 "parallel/sharded.py's topology-aware helpers "
                 "(combine_shards / gather_blocks / scatter_to_owner): "
                 "ONE exchange seam carries the hier-vs-flat parity "
                 "contract, the mesh_topology knob and the ici/dcn "
                 "byte accounting")
    fix_hint = ("call parallel.sharded.combine_shards / gather_blocks "
                "/ scatter_to_owner (pass topology_of(mesh)) instead "
                "of raw jax.lax psum/psum_scatter/all_gather")
    blessed = ("pipelinedp_tpu/parallel/sharded.py",)
    _COLLECTIVES = frozenset({"psum", "psum_scatter", "all_gather"})

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = terminal_name(node.func)
            if name in self._COLLECTIVES:
                yield (node.lineno,
                       f"raw collective {name}() outside "
                       "parallel/sharded.py — exchanges go through "
                       "the topology-aware seam")


PORTED_RULES = (NoSleepRule, NoFoldinRule, NoStagerRule, NoPerfRule,
                NoArtifactsRule, NoCostRule, NoKnobsRule,
                NoPallasRule, NoServeRule)
