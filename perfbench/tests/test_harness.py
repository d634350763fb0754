"""The harness on the CPU, through its functions rather than the
command (which refuses the CPU): a configuration dropped into its
directory is found by name, a sound run is correct, and a run with the
timed path broken underneath, or the control in the program's place,
is not."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import harness

from conftest import BENCH, ROOT

TINY = {
    "name": "tiny",
    "source": "a test-sized stand-in for movielens25m",
    "chips": 1,
    "dataset": {
        "rows": 20000, "privacy_units": 600, "partitions": 1000,
        "values": {"kind": "grid", "lo": 0.5, "hi": 5.0, "step": 0.5},
        "rows_per_unit": {"min": 1, "median": 20, "max": 200},
        "partition_zipf_a": 1.3, "sizes_seed": 7},
    "query": {"metrics": ["COUNT", "SUM", "MEAN"]},
    "guarantees": {
        "release_epsilon": 1e8, "delta": 1e-6, "noise_kind": "LAPLACE",
        "max_partitions_contributed": 200,
        "max_contributions_per_partition": 1, "min_value": 0.5,
        "max_value": 5.0, "partition_selection": "private",
        "partition_selection_strategy": "TRUNCATED_GEOMETRIC",
        "value_precision": "float32"},
    "reduced": [], "assumed": {},
    "limits": {"count_err": 0.5, "sum_err": 3.0, "mean_err_x_n": 8.0,
               "noise_scale_err": 0.3, "kept_missing": 0, "kept_unlikely": 0,
               "kept_extra": 0},
}
TINY_PCT = dict(
    TINY, name="tiny_pct",
    dataset=dict(TINY["dataset"], values={"kind": "uniform", "lo": 0.0,
                                          "hi": 10.0}),
    query={"metrics": ["PERCENTILE(50)", "PERCENTILE(90)", "PERCENTILE(99)",
                       "VARIANCE"],
           "quantile_tree": {"height": 4, "branching": 16}},
    guarantees=dict(TINY["guarantees"], release_epsilon=1e9, min_value=0.0,
                    max_value=10.0),
    limits={"pct_outside": 0, "pct_unordered": 0, "variance_err": 0.001,
            "kept_missing": 0, "kept_unlikely": 0, "kept_extra": 0})
CELLS = [("tiny.sum.fresh", "tiny", "fresh", 1),
         ("tiny.sum.resident", "tiny", "resident", 1),
         ("tiny.pct.fresh", "tiny_pct", "fresh", 1),
         ("tiny.sum.mesh4", "tiny", "fresh", 4)]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A checkout with this benchmark, and tiny configurations and cells
    added as new files and entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    for cfg in (TINY, TINY_PCT):
        (root / "perfbench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        spec["configs"].append({
            "name": cfg["name"], "source": cfg["source"], "reduced": [],
            "file": f"perfbench/configs/{cfg['name']}.json", "why": "test"})
    for name, config, traffic, chips in CELLS:
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": chips,
                                  "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(str(root))


def run(bench, workload, seed=2**31 + 11, trace=False, seconds=0.5):
    chips = bench.cell(workload)[0]["chips"]
    import time
    return harness.run(bench, workload, seed, seconds, trace,
                       time.monotonic(), jax.devices()[:chips])


def test_added_files_only(bench):
    """The copy's files that the checkout has are unchanged: the tiny
    cells came in as new files and new entries."""
    for dirpath, _, files in os.walk(bench.dir):
        for f in files:
            mine = os.path.join(dirpath, f)
            theirs = os.path.join(BENCH, os.path.relpath(mine, bench.dir))
            if os.path.exists(theirs):
                assert open(mine, "rb").read() == open(theirs, "rb").read()
            else:
                assert f in ("tiny.json", "tiny_pct.json")


@pytest.mark.parametrize("workload", [c[0] for c in CELLS])
def test_sound_run_is_correct(bench, workload):
    line, checks = run(bench, workload)
    assert line["correct"], checks
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"release_rows_per_s", "setup_s"}
    assert line["device"]["count"] == bench.cell(workload)[0]["chips"]


def test_emit_puts_checks_last(bench, capsys):
    line, checks = run(bench, "tiny.sum.fresh")
    harness.emit(line, checks)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    tail = err.strip().splitlines()[-len(checks):]
    assert [t.split()[1] for t in tail] == list(checks)


def _half_batch(monkeypatch):
    """Half of the rows left out where the program encodes them."""
    from pipelinedp_tpu import jax_engine
    encode = jax_engine.encode

    def half(*a, **k):
        enc = encode(*a, **k)
        n = enc.n_rows // 2
        return jax_engine.EncodedData(enc.pid[:n], enc.pk[:n], enc.values[:n],
                                      enc.pk_vocab, n)

    monkeypatch.setattr(jax_engine, "encode", half)


def _altered_answer(monkeypatch):
    """Two partitions' released answers swapped where the program
    assembles the release."""
    from pipelinedp_tpu import jax_engine
    assemble = jax_engine._assemble_output

    def altered(config, vocab, metric_arrays, rel_sel, vocab_idx):
        a, b = rel_sel[0], rel_sel[-1]
        arrays = {}
        for f, arr in metric_arrays.items():
            arrays[f] = arr.copy()
            arrays[f][[a, b]] = arr[[b, a]]
        return assemble(config, vocab, arrays, rel_sel, vocab_idx)

    monkeypatch.setattr(jax_engine, "_assemble_output", altered)


def _exchange_left_out(monkeypatch):
    """The cross-chip combine returns each chip's own partials for its
    owner block, unsummed."""
    from pipelinedp_tpu.parallel import sharded

    def local(x, axis, dim, replicate, topo=None):
        if replicate:
            return x
        n = jax.lax.psum(1, axis)
        blk = x.shape[dim] // n
        return jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(axis) * blk, blk, dim)

    monkeypatch.setattr(sharded, "combine_shards", local)


def _noise(factor):
    """The release's noise scaled by ``factor`` (0: no noise), where the
    program draws it."""

    def fault(monkeypatch):
        from pipelinedp_tpu import dp_computations
        add = dp_computations._add_random_noise

        def scaled(value, *a, **k):
            noisy = add(value, *a, **k)
            return value + factor * (noisy - value)

        monkeypatch.setattr(dp_computations, "_add_random_noise", scaled)

    fault.__name__ = f"_noise_x{factor:g}"
    return fault


def _selection_skipped(monkeypatch):
    """Private partition selection keeps every populated partition."""
    import numpy as np
    from pipelinedp_tpu import jax_engine
    inputs = jax_engine.selection_inputs

    def keep_all(*a, **k):
        table, threshold, scale, min_count = inputs(*a, **k)
        return np.ones_like(table), threshold, scale, min_count

    monkeypatch.setattr(jax_engine, "selection_inputs", keep_all)


@pytest.mark.parametrize("workload,fault", [
    ("tiny.sum.fresh", _half_batch),
    ("tiny.pct.fresh", _half_batch),
    ("tiny.sum.fresh", _altered_answer),
    ("tiny.pct.fresh", _altered_answer),
    ("tiny.sum.mesh4", _exchange_left_out),
    ("tiny.sum.fresh", _noise(0)),
    ("tiny.sum.fresh", _noise(0.5)),
    ("tiny.sum.fresh", _noise(100)),
    ("tiny.sum.resident", _noise(0)),
    ("tiny.sum.fresh", _selection_skipped),
    ("tiny.pct.fresh", _selection_skipped),
    ("tiny.sum.mesh4", _selection_skipped),
])
def test_broken_timed_path_is_not_correct(bench, workload, fault,
                                          monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()  # no program traced before the fault is reused
    try:
        line, checks = run(bench, workload, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # nor the faulty one after it
    assert not line["correct"], checks


@pytest.mark.parametrize("config", ["tiny", "tiny_pct"])
def test_control_fails_the_limits(bench, config):
    """The reference in bfloat16, in the program's place, fails at least
    one of the configuration's numbers."""
    workload = {"tiny": "tiny.sum.fresh", "tiny_pct": "tiny.pct.fresh"}[config]
    _, cfg, traffic = bench.cell(workload)
    job = bench.module("jobs", traffic["job"]).Job(cfg, traffic, 3)
    numbers = job.control()
    assert any(numbers[k] > lim for k, lim in cfg["limits"].items()), numbers


def test_readings_give_both_ends_of_each_limit(bench):
    """readings.py reads the program through the window's job, a line
    per release, and the control, and sets each number's two readings
    beside its limit."""
    readings = harness.load_module(os.path.join(BENCH, "readings.py"))
    prog = readings.program(bench, "tiny.sum.fresh", [1, 2], 2)
    ctrl = readings.control(bench, "tiny.sum.fresh", [3])
    assert [len(v) for v in prog.values()] == [2, 2]
    got = readings.summary(bench.cell("tiny.sum.fresh")[1]["limits"], prog,
                           ctrl)
    for name, r in got.items():
        assert r["lower"] <= r["limit"], (name, r)
    assert got["noise_scale_err"]["upper"] > got["noise_scale_err"]["limit"]


def test_without_a_tpu_the_command_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ml25m.sum.fresh", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_metric_readers_read_the_record(bench):
    """Each metric of BENCHMARK.json has a reader that returns its number
    from a run's record, and nothing where there is nothing to read."""
    release = {"rows": 100, "timings": {"host_encode_s": 1.0,
                                        "device_s": 2.0,
                                        "host_decode_s": 0.5}}
    trace = {"busy_s": 2.5, "window_s": 10.0, "collective_s": 0.1}
    record = {"releases": [release, release], "rows_released": 200,
              "window_s": 10.0, "setup_s": 30.0, "setup_compile_s": 0.3,
              "trace": trace}
    want = {"release_rows_per_s": 20.0, "setup_s": 30.0, "encode_share": 20.0,
            "engine_device_share": 40.0, "release_share": 10.0,
            "device_idle_share": 75.0, "collective_share": 1.0,
            "setup_compile_s": 0.3}
    names = [m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench.spec[kind]]
    assert set(names) == set(want)
    for name in names:
        reader = bench.module("metrics", name)
        assert reader.read(record) == pytest.approx(want[name]), name
    empty = dict(record, releases=[], rows_released=0, trace=None)
    for name in ("release_rows_per_s", "encode_share", "device_idle_share",
                 "collective_share"):
        assert bench.module("metrics", name).read(empty) is None, name
