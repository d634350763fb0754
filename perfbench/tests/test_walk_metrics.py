"""The percentile cell's harness on the CPU, at a tenth of a percent of
its rows: ``movielens25m_pct``'s query, value grid and guarantees over a
small dataset read correct, and a release whose percentiles sit two
leaves off does not; the walk's two per-layer readers read the keys the
program records, and nothing where a program records none."""

import json
import os
import shutil
import time

import jax
import pytest

import harness

from conftest import BENCH, ROOT

CELL = "tiny.pct.ml25m"


def _tiny_config():
    with open(os.path.join(BENCH, "configs", "movielens25m_pct.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny_ml25m_pct"
    cfg["dataset"] = dict(cfg["dataset"], rows=40_000, privacy_units=800,
                          partitions=1_000, sizes_seed=11,
                          rows_per_unit={"min": 5, "median": 30, "max": 400})
    return cfg


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A checkout with this benchmark and the tiny cell added."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = _tiny_config()
    (root / "perfbench" / "configs" / "tiny_ml25m_pct.json").write_text(
        json.dumps(cfg))
    spec["configs"].append({"name": cfg["name"], "source": "test",
                            "file": "perfbench/configs/tiny_ml25m_pct.json",
                            "reduced": ["rows"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": cfg["name"],
                              "traffic": "fresh", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(str(root))


def _run(bench, seed=2**31 + 29):
    return harness.run(bench, CELL, seed, 0.5, False, time.monotonic(),
                       jax.devices()[:1])


def test_tiny_copy_keeps_query_grid_and_guarantees(bench):
    _, cfg, _ = bench.cell(CELL)
    with open(os.path.join(BENCH, "configs", "movielens25m_pct.json")) as f:
        full = json.load(f)
    for key in ("query", "guarantees", "limits"):
        assert cfg[key] == full[key], key
    assert cfg["dataset"]["values"] == full["dataset"]["values"]


def test_sound_run_is_correct(bench):
    line, checks = _run(bench)
    assert line["correct"], checks
    assert set(checks) >= {"pct_outside", "pct_unordered", "variance_err",
                           "count_err", "noise_scale_err"}


def _percentiles_two_leaves_up(monkeypatch):
    from pipelinedp_tpu import jax_engine
    assemble = jax_engine._assemble_output
    cfg = _tiny_config()
    g, tree = cfg["guarantees"], cfg["query"]["quantile_tree"]
    leaf = (g["max_value"] - g["min_value"]) / tree["branching"]**tree[
        "height"]

    def shifted(config, vocab, metric_arrays, rel_sel, vocab_idx):
        arrays = dict(metric_arrays)
        for f in arrays:
            if f.startswith("percentile_"):
                arrays[f] = arrays[f] + 2 * leaf
        return assemble(config, vocab, arrays, rel_sel, vocab_idx)

    monkeypatch.setattr(jax_engine, "_assemble_output", shifted)


def test_percentiles_two_leaves_off_are_not_correct(bench, monkeypatch):
    _percentiles_two_leaves_up(monkeypatch)
    line, checks = _run(bench, seed=7)
    monkeypatch.undo()
    assert not line["correct"], checks
    assert checks["pct_outside"][0] > 0


def test_run_records_the_walk_outcome(bench):
    """Every timed release carries the compaction's outcome; half-star
    ratings overflow its prefix, so the share reads 100."""
    line, _ = _run(bench, seed=3)
    assert line["correct"]
    reader = bench.module("metrics", "walk_full_scatter_share")
    _, cfg, traffic = bench.cell(CELL)
    job = bench.module("jobs", traffic["job"]).Job(cfg, traffic, 3)
    _, timings = job.release(0)
    assert timings["walk_full_scatter"] == 1
    assert timings["walk_relevant_rows"] > 0.2 * job.rows
    record = {"releases": [{"rows": job.rows, "timings": timings}],
              "trace": None}
    assert reader.read(record) == 100.0


def _record(timings, trace=None):
    return {"releases": [{"rows": 10, "timings": t} for t in timings],
            "rows_released": 10 * len(timings), "window_s": 10.0,
            "setup_s": 1.0, "setup_compile_s": 0.1, "trace": trace}


def test_full_scatter_share_reads_the_releases_that_carry_it(bench):
    reader = bench.module("metrics", "walk_full_scatter_share")
    got = reader.read(_record([{"walk_full_scatter": 1},
                               {"walk_full_scatter": 0},
                               {"walk_full_scatter": 1}, {"device_s": 1.0}]))
    assert got == pytest.approx(100.0 * 2 / 3)
    assert reader.read(_record([{"device_s": 1.0}])) is None
    assert reader.read(_record([])) is None


TRACE = {"window_s": 10.0, "busy_s": 6.0, "collective_s": 0.0,
         "device_ops": [["jit_fused_aggregate_kernel fusion.7", 2.5],
                        ["jit_fused_aggregate_kernel fusion.1", 1.5],
                        ["jit_fused_aggregate_kernel fusion.9", 0.5]]}
TABLES = {"jit_fused_aggregate_kernel": {"fusion.7": "walk",
                                         "fusion.9": "walk",
                                         "fusion.1": "segsum"}}


def test_walk_device_share_reads_the_walk_scope(bench, monkeypatch):
    from pipelinedp_tpu import obs
    monkeypatch.setattr(obs, "program_scopes", lambda: TABLES)
    reader = bench.module("metrics", "walk_device_share")
    assert reader.read(_record([], TRACE)) == pytest.approx(30.0)
    assert reader.read(_record([], None)) is None


def test_walk_device_share_is_silent_without_the_scope(bench, monkeypatch):
    """A program that declares no ``walk`` scope reads nothing, not 0."""
    from pipelinedp_tpu import obs
    monkeypatch.setattr(obs, "program_scopes", lambda: TABLES)
    monkeypatch.setattr(obs, "SCOPES", tuple(s for s in obs.SCOPES
                                             if s != "walk"))
    reader = bench.module("metrics", "walk_device_share")
    assert reader.read(_record([], TRACE)) is None
