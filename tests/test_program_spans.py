"""The program's spans on the profiler's clock, the split of
``engine.device``, and the fused programs' named scopes.

* every measuring ``obs`` span (and ``obs.device_annotation``) is a
  host event of the same name in a ``jax.profiler`` trace, and records
  nothing with no profiler session running;
* ``LazyFusedResult.timings`` splits ``device_s`` into ``h2d_s``,
  ``fetch_s`` and, on a mesh, ``reshard_s``, whose sum stays inside it;
* ``obs.program_scopes()`` maps the fused programs' HLO instructions to
  every declared scope, from signatures that keep no array; the
  percentile walk's instructions map to ``walk`` or ``noise``;
* the scopes and spans leave the released rows bit-identical: the
  digests below are those of the same releases before either existed.
"""

import glob
import hashlib
import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine, obs, parallel
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.obs import costs
from pipelinedp_tpu.parallel import sharded

# ``obs.tracer`` is also a function: reach the module itself.
obs_tracer = importlib.import_module("pipelinedp_tpu.obs.tracer")

# sha256 of repr(sorted(rows)) of ``_release`` (row count 50 each),
# computed before the spans and scopes were added.
PARENT_DIGESTS = {
    ("one", "l0_linf"):
        "531b8fbf2f4fb55bb2eb13453ee1ce82d2fa1f6a45dd9450504f188dfb0dc606",
    ("mesh", "l0_linf"):
        "0f30a119518e49f7d4bad2699b40845540ccc7639fbca6e292c58c3c62ca22f4",
    ("one", "total_cap"):
        "2e42cb3c8c65eedad445a12b8f96209c6c048216114093f86a935aaad4419a75",
    ("mesh", "total_cap"):
        "c52504a454cb9d6fc2ee508aea29a78273fe888bb0e3e38b90466c4259e33a09",
}


def _release(mesh, bounding="l0_linf"):
    """One small COUNT/SUM/MEAN release with private selection, seeded:
    (rows, timings)."""
    rng = np.random.default_rng(23)
    n = 6_000
    ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 400, n),
                          partition_keys=rng.integers(0, 50, n),
                          values=rng.uniform(0.0, 5.0, n))
    kw = (dict(max_contributions=6) if bounding == "total_cap" else
          dict(max_partitions_contributed=3,
               max_contributions_per_partition=2))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN],
        min_value=0.0, max_value=5.0, **kw)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=100.0, total_delta=1e-6)
    res = pdp.DPEngine(acc, JaxBackend(mesh=mesh, rng_seed=2024)).aggregate(
        ds, params, pdp.DataExtractors())
    acc.compute_budgets()
    return sorted(res), res.timings


def _mesh(where):
    return parallel.make_mesh(4) if where == "mesh" else None


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    profile = ProfileData.from_file(path)
    return [ev.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


class TestProfilerBridge:

    def test_span_is_a_host_event_of_the_trace(self, tmp_path):
        tr = obs.run_tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("engine.bridge_probe", cat="test"):
                with obs.device_annotation("pdp.bridge_probe"):
                    jnp.ones(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        names = _host_event_names(tmp_path)
        assert names.count("engine.bridge_probe") == 1
        assert names.count("pdp.bridge_probe") == 1
        assert tr.count("engine.bridge_probe") == 1

    def test_no_session_records_nothing_and_raises_nothing(self, tmp_path):
        tr = obs.run_tracer()
        with tr.span("engine.before_session", cat="test") as sp:
            with obs.device_annotation("pdp.before_session"):
                pass
        assert sp.duration >= 0.0
        jax.profiler.start_trace(str(tmp_path))
        jax.profiler.stop_trace()
        names = _host_event_names(tmp_path)
        assert "engine.before_session" not in names
        assert "pdp.before_session" not in names

    def test_no_jax_imported_means_no_annotation(self, monkeypatch):
        monkeypatch.setattr(obs_tracer, "_TRACE_ANNOTATION", None)
        monkeypatch.delitem(sys.modules, "jax")
        assert obs.profiler_annotation("engine.x") is obs.NOOP_SPAN
        # Nothing was resolved (or imported) on the way.
        assert obs_tracer._TRACE_ANNOTATION is None

    def test_noop_tracer_opens_no_annotation(self, monkeypatch):
        opened = []
        monkeypatch.setattr(obs_tracer, "profiler_annotation",
                            lambda name: opened.append(name))
        with obs.NOOP_TRACER.span("engine.noop"):
            pass
        assert opened == []


class TestDeviceSplit:

    @pytest.mark.parametrize("where", ["one", "mesh"])
    def test_children_sum_inside_device_s(self, where):
        _, timings = _release(_mesh(where))
        keys = ["h2d_s", "fetch_s"] + (["reshard_s"] if where == "mesh"
                                       else [])
        for k in keys:
            assert timings[k] >= 0.0, k
        assert sum(timings[k] for k in keys) <= timings["device_s"]
        assert ("reshard_s" in timings) == (where == "mesh")

    def test_resident_dataset_skips_the_transfer(self):
        rng = np.random.default_rng(5)
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 300, 4_000),
                              partition_keys=rng.integers(0, 20, 4_000),
                              values=rng.uniform(0.0, 5.0, 4_000))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=20,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=5.0)
        spans = []
        for seed in (1, 2):
            acc = pdp.NaiveBudgetAccountant(total_epsilon=100.0,
                                            total_delta=1e-6)
            res = pdp.DPEngine(acc, JaxBackend(rng_seed=seed)).aggregate(
                ds, params, pdp.DataExtractors())
            acc.compute_budgets()
            list(res)
            spans.append(res.timings)
        # The second release finds its columns in the device cache.
        assert spans[1]["host_encode_s"] < spans[0]["host_encode_s"]
        assert spans[1]["h2d_s"] <= spans[0]["h2d_s"]


class TestBitIdentity:

    @pytest.mark.parametrize("where", ["one", "mesh"])
    @pytest.mark.parametrize("bounding", ["l0_linf", "total_cap"])
    def test_rows_match_the_release_before_scopes(self, where, bounding):
        rows, _ = _release(_mesh(where), bounding)
        assert len(rows) == 50
        digest = hashlib.sha256(repr(rows).encode()).hexdigest()
        assert digest == PARENT_DIGESTS[(where, bounding)]


class TestProgramScopes:

    @pytest.mark.parametrize("where,program,expected", [
        ("one", "jit_fused_aggregate_kernel",
         set(obs.SCOPES) - {"combine", "compact", "walk"}),
        ("mesh", "jit__sharded_kernel",
         set(obs.SCOPES) - {"compact", "walk"}),
    ])
    def test_every_declared_scope_is_mapped(self, where, program,
                                            expected):
        # The L0/Linf program gathers nothing by the bounding sort's
        # order; the total-cap program's keep bits still map
        # ``bound_permute``.
        _release(_mesh(where))
        _release(_mesh(where), "total_cap")
        tables = obs.program_scopes()
        # Other tests' signatures of the program key by fingerprint; the
        # percentile programs among them are the ones that map ``walk``.
        mapped = set().union(*(t.values() for name, t in tables.items()
                               if name.split("(")[0] == program
                               and "walk" not in t.values()))
        assert mapped == expected
        compact = [t for name, t in tables.items()
                   if name.startswith("jit__compact_fetch_kernel")]
        assert compact and all(set(t.values()) == {"compact"}
                               for t in compact)

    def test_signatures_keep_no_arrays(self):
        _release(None)
        fn = jax_engine.fused_aggregate_kernel
        assert fn._signatures
        for args, kwargs in fn._signatures.values():
            leaves = jax.tree.leaves((args, kwargs))
            assert not any(isinstance(x, (jax.Array, np.ndarray))
                           for x in leaves)
        abstract = [x for args, kw in fn._signatures.values()
                    for x in jax.tree.leaves((args, kw))
                    if isinstance(x, jax.ShapeDtypeStruct)]
        assert abstract

    def test_one_signature_per_new_program(self):
        @costs.instrumented_jit(phase="test", static_argnames=("k",))
        def probe(x, k):
            with costs.scope("segsum"):
                return jnp.cumsum(x) * k

        for _ in range(3):
            probe(jnp.ones(8), k=2)
        assert len(probe._signatures) == 1
        probe(jnp.ones(16), k=2)
        probe(jnp.ones(8), k=3)
        assert len(probe._signatures) == 3
        names = [n for n in obs.program_scopes() if n.startswith("jit_probe")]
        # Several signatures of one program key apart by fingerprint.
        assert len(names) == 3 and all(n.startswith("jit_probe(")
                                       for n in names)

    def test_cached_executable_without_scopes_is_compiled_afresh(
            self, tmp_path):
        """The compile caches strip metadata from their keys, so a
        program cached before it named its scopes comes back unnamed:
        the table is then built from a fresh compile of the same
        program."""
        from jax.experimental.compilation_cache import compilation_cache

        def body(x):
            return jnp.sort(x * 3.0).cumsum()

        def scoped_body(x):
            with costs.scope("bound_sort"):
                y = jnp.sort(x * 3.0)
            return y.cumsum()

        body.__name__ = scoped_body.__name__ = "stale_probe"
        saved = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        compilation_cache.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        try:
            jax.jit(body)(jnp.ones(64)).block_until_ready()
            probe = costs.instrumented_jit(scoped_body, phase="test")
            out = probe(jnp.ones(64))
            text = probe.lower(jnp.ones(64)).compile().as_text()
            assert "bound_sort" not in text  # the cache's stale copy
            (module, _, table), = probe.scope_tables()
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
        assert module == "jit_stale_probe"
        assert "bound_sort" in table.values()
        np.testing.assert_array_equal(out, body(jnp.ones(64)))

    def test_mesh_signature_lowers_to_the_same_program(self):
        _release(_mesh("mesh"))
        fn = sharded._sharded_kernel
        (args, kwargs), = list(fn._signatures.values())[-1:]
        shardings = [x.sharding for x in jax.tree.leaves((args, kwargs))
                     if isinstance(x, jax.ShapeDtypeStruct)
                     and x.sharding is not None]
        assert shardings, "mesh arrays must keep their NamedSharding"
        assert fn.lower(*args, **kwargs).compile() is not None


def _pct_release():
    """A small seeded PERCENTILE(50, 90, 99) and VARIANCE release with
    private selection; ratings on the half-star grid."""
    rng = np.random.default_rng(8)
    n = 20_000
    ds = pdp.ArrayDataset(privacy_ids=np.arange(n),
                          partition_keys=rng.integers(0, 40, n),
                          values=0.5 * rng.integers(1, 11, n))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                 pdp.Metrics.VARIANCE, pdp.Metrics.PERCENTILE(50),
                 pdp.Metrics.PERCENTILE(90), pdp.Metrics.PERCENTILE(99)],
        max_partitions_contributed=1, max_contributions_per_partition=1,
        min_value=0.5, max_value=5.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1e9, total_delta=1e-6)
    res = pdp.DPEngine(acc, JaxBackend(rng_seed=5)).aggregate(
        ds, params, pdp.DataExtractors())
    acc.compute_budgets()
    return sorted(res), params


def _unnamed(hlo_text):
    """(scope table, op_names of the instructions it leaves out that
    carry metadata, parameters and control flow aside): what a trace
    would show under no scope."""
    _, table = costs.hlo_scope_table(hlo_text)
    inner, names, current = set(), set(), None
    bodies = {}
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            c = costs._HLO_COMPUTATION.match(line)
            current = bodies.setdefault(c.group(1), []) if c else None
            continue
        inner.update(x.group(1) for x in (costs._HLO_CALLS.search(line),
                                          costs._HLO_APPLIES.search(line))
                     if x)
        i = costs._HLO_INSTRUCTION.match(line)
        if current is not None and i:
            current.append((i.group(2), line))
    for comp, body in bodies.items():
        if comp in inner:
            continue
        for instr, line in body:
            op = costs._HLO_OP_NAME.search(line)
            if (instr not in table and op and " parameter(" not in line
                    and not costs._HLO_CONTROL.search(line)):
                names.add(op.group(1).split("/", 1)[-1])
    return table, names


class TestWalkScope:
    """The percentile walk's counting under its own scope."""

    def test_walk_is_a_declared_scope(self):
        assert "walk" in obs.SCOPES
        with costs.scope("walk"):
            pass

    def test_walk_alone_maps_to_walk_or_noise(self):
        """Leaf mapping, histograms, the compaction and both branches of
        its fallback, the descent and the monotone pass: every
        instruction with metadata is ``walk``, or ``noise`` for the node
        draws (the innermost scope wins)."""
        _, params = _pct_release()
        config = jax_engine.FusedConfig.from_params(params, public=False)

        def walk(pk, values, kept, key):
            qrows = jax_engine._qrows(config, pk, values, kept)
            return jax_engine._percentile_values(config, 64, qrows,
                                                 jnp.float32(0.1), key)

        n = 16_384
        text = jax.jit(walk).lower(
            jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.float32),
            jnp.ones(n, bool), jax.random.PRNGKey(0)).compile().as_text()
        assert " conditional(" in text  # the compaction's fallback
        table, unnamed = _unnamed(text)
        assert set(table.values()) == {"walk", "noise"}
        assert unnamed == set()

    def test_percentile_program_adds_nothing_unnamed(self):
        """The fused percentile program maps every declared phase it
        runs, the walk included, and leaves out nothing the sum program
        does not (the root key split). Its values ride the bounding
        sort, so nothing maps to ``bound_permute``."""
        _release(None)
        _pct_release()
        fn = jax_engine.fused_aggregate_kernel
        found = {}
        for args, kwargs in fn._signatures.values():
            config = next(a for a in args
                          if isinstance(a, jax_engine.FusedConfig))
            text = fn._jitted.lower(*args, **kwargs).compile().as_text()
            found[bool(config.percentiles)] = _unnamed(text)
        (pct_table, pct_unnamed), (_, sum_unnamed) = found[True], found[False]
        assert set(pct_table.values()) == (
            set(obs.SCOPES) - {"combine", "compact", "bound_permute"})
        assert pct_unnamed <= sum_unnamed


class TestHloScopeTable:
    """The parser on a compiled program of known structure."""

    def test_innermost_scope_fusion_root_and_operand_fallback(self):
        def f(x):
            with costs.scope("bound_sort"):
                y = jnp.sort(x * 3.0)
            with costs.scope("bound_caps"):
                with costs.scope("segsum"):
                    z = jnp.cumsum(y)
            return z + 1.0

        text = jax.jit(f).lower(jnp.ones(64)).compile().as_text()
        module, table = costs.hlo_scope_table(text)
        assert module == "jit_f"
        scopes = set(table.values())
        assert "bound_sort" in scopes and "segsum" in scopes
        # Innermost wins: nothing is attributed to the enclosing scope.
        assert "bound_caps" not in scopes
        sort_ops = [n for n, s in table.items() if n.startswith("sort")]
        assert sort_ops and all(table[n] == "bound_sort" for n in sort_ops)

    def test_control_flow_is_left_to_its_branches(self):
        """A conditional's interval on a trace encloses its branch's
        operations: the branches' instructions carry the scope, the
        conditional none, so no second is counted twice."""
        def f(x, n):
            with costs.scope("walk"):
                return jax.lax.cond(n > 3, lambda y: jnp.sort(y) * 2.0,
                                    lambda y: jnp.cumsum(y) + 1.0, x)

        text = jax.jit(f).lower(jnp.ones(64), 5).compile().as_text()
        _, table = costs.hlo_scope_table(text)
        cond = [ln for ln in text.splitlines() if " conditional(" in ln]
        assert len(cond) == 1
        name = costs._HLO_INSTRUCTION.match(cond[0]).group(2)
        assert name not in table
        sorts = [n for n in table if n.startswith("sort")]
        assert sorts and all(table[n] == "walk" for n in sorts)

    def test_unknown_scope_name_is_refused(self):
        with pytest.raises(ValueError):
            costs.scope("bound_srot")
        with pytest.raises(ValueError):
            costs.scoped("bound_srot")
