"""Knob-registry tests (``pipelinedp_tpu/plan``, ``make plancheck``).

Coverage contract:

* knob registry — cold-start resolution (no env, no seam) is
  BYTE-IDENTICAL to the hardcoded defaults; for every registered knob
  an env override wins, every module seam wins under
  ``seam_override`` and restores on exit, and an unknown value of an
  enumerated knob resolves to its default;
* a stale plan file — one written by the retired autotune loop, left
  on disk in the two places that loop used to look — steers nothing:
  every knob still resolves to its default, and a release is
  bit-identical to one without the files;
* pass-B q_chunk pin — a pinned quantile-group width constrains the
  sweep planner's tiling; an infeasible pin falls back to the search;
* ``--since-run-id`` — the store's run-windowed reads (module helper,
  incremental ``read_from`` offsets, and the CLI flag);
* lint twin — AST-precise ban on direct reads of the registered knob
  constants outside ``pipelinedp_tpu/plan/`` (``make noknobs`` runs
  the grep twin).
"""

import json
import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import obs
from pipelinedp_tpu import plan as plan_pkg
from pipelinedp_tpu import streaming
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.obs import store as obs_store
from pipelinedp_tpu.plan import knobs as plan_knobs

#: Today's hardcoded defaults, restated literally: the cold-start
#: acceptance criterion is byte-identity against THESE values, so the
#: test must not derive them from the registry it is checking.
HARDCODED_DEFAULTS = {
    "subhist_byte_cap": 600 << 20,
    "stream_chunk_rows": 1 << 26,
    "stream_cache_bytes": 4 << 30,
    "ingest_executor": True,
    "q_chunk": 0,
    "kernel_backend": "xla",
    "segsum_wide_d_block": 0,
    "sweep_config_batch": 0,
    "vector_accumulator": "f32",
    "serve_fusion": False,
    "serve_fuse_window_ms": 8,
    "serve_fuse_batch": 8,
    "serve_fuse_rows_floor": 8192,
    "sketch_width": 1 << 16,
    "sketch_depth": 2,
    "sketch_candidate_cap": 4096,
    "sketch_backend": "matmul",
    "mesh_topology": "flat",
    "select_units_cap": int(np.iinfo(np.int32).max),
    "tree_rows_cap": int(np.iinfo(np.int32).max),
}

#: One legal non-default env value per knob that has an env override,
#: with the value it parses to.
ENV_OVERRIDES = {
    "subhist_byte_cap": ("1048576", 1 << 20),
    "stream_chunk_rows": ("997", 997),
    "stream_cache_bytes": ("0", 0),
    "ingest_executor": ("off", False),
    "q_chunk": ("2", 2),
    "kernel_backend": ("pallas", "pallas"),
    "segsum_wide_d_block": ("128", 128),
    "sweep_config_batch": ("64", 64),
    "vector_accumulator": ("fx", "fx"),
    "serve_fusion": ("1", True),
    "serve_fuse_window_ms": ("20", 20),
    "serve_fuse_batch": ("4", 4),
    "serve_fuse_rows_floor": ("16384", 16384),
    "sketch_width": ("1024", 1024),
    "sketch_depth": ("3", 3),
    "sketch_candidate_cap": ("512", 512),
    "sketch_backend": ("xla", "xla"),
    "mesh_topology": ("hier", "hier"),
}

#: One non-default value per knob that has a module seam.
SEAM_VALUES = {
    "subhist_byte_cap": 4096,
    "q_chunk": 3,
    "kernel_backend": "pallas",
    "segsum_wide_d_block": 256,
    "sweep_config_batch": 8,
    "vector_accumulator": "fx",
    "mesh_topology": "hier",
    "select_units_cap": 7,
    "tree_rows_cap": 9,
}

ENUMERATED = ("kernel_backend", "vector_accumulator", "sketch_backend",
              "mesh_topology")


@pytest.fixture(autouse=True)
def fresh_knob_state(monkeypatch):
    """Isolate every test: no ambient knob env, and a fresh obs ledger
    so event assertions see only this test's emissions."""
    for spec in plan_knobs.REGISTRY:
        if spec.env_var is not None:
            monkeypatch.delenv(spec.env_var, raising=False)
    obs.reset()
    yield
    obs.reset()


def _events(name):
    return [e for e in obs.ledger().snapshot()["events"]
            if e["name"] == name]


class TestKnobRegistry:
    """Resolution precedence and the cold-start contract."""

    def test_cold_start_is_byte_identical_to_defaults(self):
        resolved = plan_knobs.resolve_all()
        assert {k: v for k, (v, _) in resolved.items()} == (
            HARDCODED_DEFAULTS)
        assert {s for _, (_, s) in resolved.items()} == {"default"}
        assert plan_knobs.defaults() == HARDCODED_DEFAULTS

    def test_tables_cover_the_registry(self):
        with_env = {s.name for s in plan_knobs.REGISTRY if s.env_var}
        with_seam = {s.name for s in plan_knobs.REGISTRY if s.seam}
        enumerated = {s.name for s in plan_knobs.REGISTRY
                      if s.kind is str}
        assert set(ENV_OVERRIDES) == with_env
        assert set(SEAM_VALUES) == with_seam
        assert set(ENUMERATED) == enumerated

    @pytest.mark.parametrize("name", sorted(ENV_OVERRIDES))
    def test_env_override_wins(self, name, monkeypatch):
        spec = plan_knobs.BY_NAME[name]
        raw, parsed = ENV_OVERRIDES[name]
        assert parsed != spec.default
        monkeypatch.setenv(spec.env_var, raw)
        assert plan_knobs.resolve_value(spec) == (parsed, "env")
        assert plan_pkg.knob_value(name) == parsed

    @pytest.mark.parametrize("name", sorted(SEAM_VALUES))
    def test_seam_wins_and_restores(self, name):
        spec = plan_knobs.BY_NAME[name]
        mod = __import__(spec.seam[0], fromlist=[spec.seam[1]])
        before = getattr(mod, spec.seam[1])
        value = SEAM_VALUES[name]
        assert value != spec.default
        with plan_pkg.seam_override(name, value):
            assert getattr(mod, spec.seam[1]) == value
            assert plan_knobs.resolve_value(spec) == (value, "seam")
            assert plan_pkg.knob_value(name) == value
        assert getattr(mod, spec.seam[1]) == before
        assert plan_knobs.resolve_value(spec) == (spec.default,
                                                  "default")

    @pytest.mark.parametrize("name", ENUMERATED)
    def test_unknown_choice_resolves_default(self, name, monkeypatch):
        spec = plan_knobs.BY_NAME[name]
        monkeypatch.setenv(spec.env_var, "no-such-choice")
        assert plan_knobs.resolve_value(spec) == (spec.default, "env")

    def test_bool_parsing(self, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_INGEST_EXECUTOR", "off")
        v, s = plan_knobs.resolve_value(
            plan_knobs.BY_NAME["ingest_executor"])
        assert (v, s) == (False, "env")


#: A value off the default for every dp-safe knob: what a plan file of
#: the retired autotune loop could carry.
_STALE_PLAN_KNOBS = {
    "subhist_byte_cap": 64 << 10,
    "stream_cache_bytes": 0,
    "ingest_executor": 0,
    "q_chunk": 1,
    "kernel_backend": "pallas",
    "segsum_wide_d_block": 128,
    "sweep_config_batch": 4,
    "serve_fusion": 1,
    "serve_fuse_window_ms": 1,
    "serve_fuse_batch": 2,
    "serve_fuse_rows_floor": 16384,
    "sketch_backend": "xla",
    "mesh_topology": "hier",
}


def _release(backend):
    rng = np.random.default_rng(88)
    n = 3_000
    ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 600, n),
                          partition_keys=rng.integers(0, 6, n),
                          values=rng.uniform(0.0, 20.0, n))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PERCENTILE(50)],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=3,
        max_contributions_per_partition=4,
        min_value=0.0, max_value=20.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0, total_delta=1e-6)
    res = pdp.DPEngine(acc, backend).aggregate(ds, params,
                                               pdp.DataExtractors())
    acc.compute_budgets()
    return dict(res)


class TestStalePlanFile:
    """Deployments that once ran ``bench.py --autotune`` may still hold
    its ``plan.json``. Nothing reads it any more."""

    def test_stale_plan_file_steers_nothing(self, tmp_path,
                                            monkeypatch):
        assert set(_STALE_PLAN_KNOBS) == {
            s.name for s in plan_knobs.REGISTRY if s.dp_safe}
        # Built before the env points at a compile cache: constructing
        # a backend would otherwise switch the persistent cache on for
        # the rest of this process.
        off_backend = JaxBackend(rng_seed=7)
        on_backend = JaxBackend(rng_seed=7)
        off = _release(off_backend)
        plan = {"schema_version": 1,
                "fingerprint": obs_store.fingerprint_key(
                    obs.environment_fingerprint()),
                "device_kind": "cpu", "created_by": "bench --autotune",
                "trials": 1, "knobs": {"default": _STALE_PLAN_KNOBS},
                "model": {}}
        stale_dir = tmp_path / "plan"
        cache = tmp_path / "cache" / "xla"
        for d in (stale_dir, cache.parent / "pdp_plan"):
            d.mkdir(parents=True)
            (d / "plan.json").write_text(json.dumps(plan), "utf-8")
        monkeypatch.setenv("PIPELINEDP_TPU_PLAN_DIR", str(stale_dir))
        monkeypatch.setenv("PIPELINEDP_TPU_COMPILE_CACHE", str(cache))

        resolved = plan_knobs.resolve_all()
        assert {k: v for k, (v, _) in resolved.items()} == (
            plan_knobs.defaults())
        assert {s for _, (_, s) in resolved.items()} == {"default"}
        for name, default in plan_knobs.defaults().items():
            assert plan_pkg.knob_value(name) == default, name

        on = _release(on_backend)
        assert set(on) == set(off)
        for pk in off:
            for field in off[pk]._fields:
                assert getattr(on[pk], field) == getattr(off[pk], field), (
                    f"stale plan file moved {pk}.{field}")
        report = obs.build_run_report()
        assert {v["source"] for v in report["plan"]["knobs"].values()} == (
            {"default"})


class TestQChunkPin:
    """The q_chunk knob constrains the pass-B sweep planner's tiling."""

    def test_pin_constrains_tiling(self):
        _, _, _, span = streaming._tree_consts()
        plan = streaming.plan_pass_b_sweeps(1 << 10, 4, span,
                                            600 << 20, q_chunk=1)
        assert plan.q_chunk == 1
        assert all(qn == 1 for _, qn, _ in plan.tiles)
        # Unpinned, the same under-budget shape is one full-grid tile.
        free = streaming.plan_pass_b_sweeps(1 << 10, 4, span, 600 << 20)
        assert free.n_tiles == 1 and free.q_chunk == 4

    def test_infeasible_pin_falls_back_to_search(self):
        _, _, _, span = streaming._tree_consts()
        unit = span * 4
        # Budget of 2 blocks: qc=3 fits no partition block -> fallback.
        pinned = streaming.plan_pass_b_sweeps(8, 4, span, 2 * unit,
                                              q_chunk=3)
        free = streaming.plan_pass_b_sweeps(8, 4, span, 2 * unit)
        assert pinned == free
        assert _events("plan.q_chunk_infeasible")


class TestSinceRunId:
    """Run-windowed ledger reads: the autotune fitter's linearity."""

    def _store(self, tmp_path):
        s = obs_store.LedgerStore(str(tmp_path / "ledger"))
        env = {"device_kind": "cpu"}
        s.append("m", {"record": {"value": 1}}, env=env, run_id="r1")
        s.append("m", {"record": {"value": 2}}, env=env, run_id="r2")
        s.append("m", {"record": {"value": 3}}, env=env, run_id="r2")
        return s

    def test_window_module_helper(self, tmp_path):
        s = self._store(tmp_path)
        entries = s.entries()
        win = obs_store.entries_since_run_id(entries, "r2")
        assert [e["payload"]["record"]["value"] for e in win] == [2, 3]
        assert obs_store.entries_since_run_id(entries, "nope") == []

    def test_read_from_is_incremental(self, tmp_path):
        s = self._store(tmp_path)
        first, offset = s.read_from(0)
        assert len(first) == 3
        env = {"device_kind": "cpu"}
        s.append("m", {"record": {"value": 4}}, env=env, run_id="r3")
        tail, end = s.read_from(offset)
        assert [e["payload"]["record"]["value"] for e in tail] == [4]
        assert end > offset
        assert s.read_from(end)[0] == []

    def test_cli_since_run_id(self, tmp_path, capsys):
        s = self._store(tmp_path)
        rc = obs_store.main(["--summarize", "--dir", s.directory,
                             "--since-run-id", "r2", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["entries"] == 2


class TestNoDirectKnobReads:
    """AST-precise twin of ``make noknobs``: the registered knob
    constants may be READ only inside ``pipelinedp_tpu/plan/`` (the
    registry's seam layer); the defining modules keep the names as
    assignable test seams but must route their own reads through
    ``plan.knobs``. Tests and the seam-override context are exempt."""

    def test_knob_reads_only_under_plan(self):
        # Delegates to the shared AST engine (which owns the
        # KNOB_CONSTANTS/DEFINING tables); `make noknobs` is the
        # same rule.
        from pipelinedp_tpu import lint
        assert lint.check_tree("noknobs") == []

    def test_registry_knows_every_registered_knob_constant(self):
        """The rule's constant table must track the knob registry —
        a knob added to plan/ without a lint constant would silently
        escape the read ban."""
        from pipelinedp_tpu.lint.rules.confinement import NoKnobsRule
        assert NoKnobsRule.KNOB_CONSTANTS == {
            "_SUBHIST_BYTE_CAP", "_SELECT_UNITS_CAP",
            "_TREE_ROWS_CAP", "_Q_CHUNK"}
