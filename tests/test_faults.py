"""Fault-injection integration tests: kill a streamed run mid-flight,
resume from checkpoint, assert BIT-IDENTICAL outputs — the acceptance
oracle for budget-safe retry (same noise draws, same kept-partition set,
one budget charge). Plus the bench's wedged-device degradation path.

Fast and CPU-only throughout — the end-to-end bench subprocess runs in
smoke mode (~20s). ``make faultcheck`` runs this file plus
``test_resilience.py``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.resilience import (CheckpointMismatch, CheckpointStore,
                                       FaultPlan, injected_faults)
from pipelinedp_tpu.resilience.faults import ChunkFailure


@pytest.fixture(autouse=True)
def tiny_chunks(monkeypatch):
    monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "997")


def run_streamed(ds, params, seed=0, eps=5.0, delta=1e-6, public=None,
                 checkpoint=None, mesh=None):
    ds.invalidate_cache()
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed, mesh=mesh,
                                          checkpoint=checkpoint))
    res = engine.aggregate(ds, params, pdp.DataExtractors(),
                           public_partitions=public)
    acc.compute_budgets()
    got = dict(res)
    assert res.timings.get("stream_batches", 0) > 1, (
        "dataset did not stream — the kill/resume path was not exercised")
    return got, res.timings


def make_ds(seed=1, n=9_000, users=2_000, parts=12):
    rng = np.random.default_rng(seed)
    return pdp.ArrayDataset(privacy_ids=rng.integers(0, users, n),
                            partition_keys=rng.integers(0, parts, n),
                            values=rng.uniform(0.0, 10.0, n)), parts


def assert_bit_identical(got_a, got_b):
    """EXACT equality of every released metric — noisy floats included —
    and of the kept-partition sets: the bit-parity contract."""
    assert set(got_a) == set(got_b), (
        f"kept sets differ: {sorted(set(got_a) ^ set(got_b))}")
    for k in got_a:
        ta, tb = got_a[k], got_b[k]
        assert ta._fields == tb._fields
        for f in ta._fields:
            va, vb = getattr(ta, f), getattr(tb, f)
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                          err_msg=f"partition {k}.{f}")


class TestCheckpointResumeBitParity:
    """Kill after chunk k via fault injection, resume, compare against
    the uninterrupted run at MODERATE eps — real noise, real private
    selection, so any key-replay drift shows up as a float mismatch."""

    def test_killed_and_resumed_run_is_bit_identical(self, tmp_path):
        ds, parts = make_ds(seed=1)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        # Ground truth: one uninterrupted run, NO checkpointing at all.
        baseline, _ = run_streamed(ds, params, seed=42)

        # Kill at chunk 3 (checkpoints for chunks 0-1 are on disk; chunk
        # 2's fold is still pending — deliberately mid-pipeline).
        store = CheckpointStore(str(tmp_path / "stream.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=42, checkpoint=store)
        assert store.exists(), "no checkpoint survived the kill"

        # Resume: restores the fold prefix, replays the SAME keys.
        resumed, timings = run_streamed(ds, params, seed=42,
                                        checkpoint=store)
        assert timings["stream_resumed_from"] >= 1
        assert_bit_identical(baseline, resumed)
        # Success cleared the checkpoint: the budget cannot be re-spent
        # by accidentally resuming a finished run.
        assert not store.exists()

    def test_resume_with_private_selection_same_kept_set(self, tmp_path):
        """Selection at modest eps — partitions genuinely on the keep
        boundary — must come out IDENTICAL after kill + resume."""
        rng = np.random.default_rng(9)
        n = 8_000
        pid = np.arange(n)
        pk = np.where(np.arange(n) < 7_600,
                      rng.integers(0, 4, n), 4 + np.arange(n) % 120)
        ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                              values=None)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=1,
            max_contributions_per_partition=1)
        baseline, _ = run_streamed(ds, params, seed=5, eps=5.0,
                                   delta=1e-5)
        store = CheckpointStore(str(tmp_path / "sel.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(4,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=5, eps=5.0, delta=1e-5,
                             checkpoint=store)
        resumed, _ = run_streamed(ds, params, seed=5, eps=5.0,
                                  delta=1e-5, checkpoint=store)
        assert_bit_identical(baseline, resumed)

    def test_resume_with_percentiles_is_bit_identical(self, tmp_path):
        """Percentile configs carry extra checkpoint state (the additive
        device mid-histogram) and a resumed run must disable the pass-B
        device cache (the skipped prefix is not resident) — both paths
        pinned by exact equality against the uninterrupted run."""
        rng = np.random.default_rng(11)
        n = 8_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 2_000, n),
                              partition_keys=rng.integers(0, 4, n),
                              values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90),
                     pdp.Metrics.COUNT],
            max_partitions_contributed=4,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        public = list(range(4))
        baseline, _ = run_streamed(ds, params, seed=13, public=public)
        store = CheckpointStore(str(tmp_path / "pct.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=13, public=public,
                             checkpoint=store)
        resumed, timings = run_streamed(ds, params, seed=13,
                                        public=public, checkpoint=store)
        assert timings["stream_resumed_from"] >= 1
        # The resumed run must have re-streamed pass B (no partial
        # cache), not silently dropped the skipped prefix.
        assert timings["stream_pass_b"] == "reship"
        assert_bit_identical(baseline, resumed)

    def test_kill_on_first_chunk_resumes_from_scratch(self, tmp_path):
        """A kill before ANY fold completes leaves no checkpoint; the
        'resume' is a clean, still bit-identical, restart."""
        ds, parts = make_ds(seed=3)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50)
        baseline, _ = run_streamed(ds, params, seed=7)
        store = CheckpointStore(str(tmp_path / "first.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(0,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=7, checkpoint=store)
        assert not store.exists()
        resumed, timings = run_streamed(ds, params, seed=7,
                                        checkpoint=store)
        assert timings["stream_resumed_from"] == 0
        assert_bit_identical(baseline, resumed)

    def test_checkpoint_requires_fixed_seed(self, tmp_path):
        ds, parts = make_ds(seed=4, n=5_000)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50)
        ds.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(
            rng_seed=None, checkpoint=str(tmp_path / "x.ckpt")))
        res = engine.aggregate(ds, params, pdp.DataExtractors())
        acc.compute_budgets()
        with pytest.raises(ValueError, match="budget is consumed at "
                                             "noise draw"):
            dict(res)

    def test_mismatched_checkpoint_refuses_resume(self, tmp_path):
        """A checkpoint from a DIFFERENT seed must refuse to resume —
        silently restarting would re-draw noise and double-spend."""
        ds, parts = make_ds(seed=6, n=5_000)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50)
        store = CheckpointStore(str(tmp_path / "mismatch.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=1, checkpoint=store)
        assert store.exists()
        ds.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=2,
                                              checkpoint=store))
        res = engine.aggregate(ds, params, pdp.DataExtractors())
        acc.compute_budgets()
        with pytest.raises(CheckpointMismatch):
            dict(res)

    def test_same_shape_different_data_refuses_resume(self, tmp_path):
        """The fingerprint's data component is a CONTENT digest: a
        different dataset with the identical row count / config / seed
        must refuse to resume (splicing two datasets into one release
        would corrupt it silently)."""
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=12,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        ds_a, _ = make_ds(seed=31)
        store = CheckpointStore(str(tmp_path / "data.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds_a, params, seed=4, checkpoint=store)
        assert store.exists()
        ds_b, _ = make_ds(seed=32)  # same shape, different rows
        ds_b.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=4,
                                              checkpoint=store))
        res = engine.aggregate(ds_b, params, pdp.DataExtractors())
        acc.compute_budgets()
        with pytest.raises(CheckpointMismatch):
            dict(res)

    def test_resume_on_mesh_is_bit_identical(self, tmp_path,
                                             monkeypatch):
        """Kill + resume composed with the 8-device CPU mesh: the
        owner-sharded fold restores and replays identically."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu.parallel import make_mesh
        ds, parts = make_ds(seed=8, n=14_000)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        mesh = make_mesh()
        baseline, _ = run_streamed(ds, params, seed=21, mesh=mesh)
        store = CheckpointStore(str(tmp_path / "mesh.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(2,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, seed=21, mesh=mesh,
                             checkpoint=store)
        resumed, timings = run_streamed(ds, params, seed=21, mesh=mesh,
                                        checkpoint=store)
        assert timings["stream_resumed_from"] >= 1
        assert_bit_identical(baseline, resumed)


class TestMegasweepKillResume:
    """Kill the utility-analysis megasweep between config batches via
    ``FaultPlan.fail_sweep_config_chunks``, resume from the ``.sweep``
    sibling checkpoint, and assert the resumed grid is BIT-IDENTICAL to
    an uninterrupted batched run — with zero orphan threads left behind
    (ISSUE-18 acceptance)."""

    GRID = 12
    BATCH = 4  # 12 configs / 4 per batch = 3 sweep chunks

    @staticmethod
    def _run_sweep(checkpoint=None):
        import dataclasses

        from pipelinedp_tpu import analysis, plan as plan_mod
        from pipelinedp_tpu.analysis import data_structures
        rng = np.random.default_rng(31)
        n = 8_000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 600, n),
            partition_keys=rng.integers(0, 40, n),
            values=rng.uniform(0, 10, n))
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=list(range(1, 13)),
            max_contributions_per_partition=[1, 2] * 6)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT],
                max_partitions_contributed=4,
                max_contributions_per_partition=2),
            multi_param_configuration=multi)
        with plan_mod.seam_override("sweep_config_batch",
                                    TestMegasweepKillResume.BATCH):
            res = analysis.perform_utility_analysis(
                ds, JaxBackend(rng_seed=0, checkpoint=checkpoint),
                options, pdp.DataExtractors())
            out = list(res)[0]
        assert len(out) == TestMegasweepKillResume.GRID
        metrics = [dataclasses.asdict(m.count_metrics) for m in out]
        return metrics, res

    @staticmethod
    def _assert_configs_bit_identical(got, ref):
        for ci, (a, b) in enumerate(zip(got, ref)):
            for field in a:
                np.testing.assert_array_equal(
                    np.asarray(a[field]), np.asarray(b[field]),
                    err_msg=f"cfg{ci}.{field}")

    def test_killed_megasweep_resumes_bit_identical(self, tmp_path):
        import threading

        # Ground truth: one uninterrupted batched run, no checkpoint.
        baseline, _ = self._run_sweep()

        # Kill at config chunk 2: chunks 0-1 (8 configs) are already in
        # the ``.sweep`` sibling checkpoint; chunk 2 never dispatched.
        path = str(tmp_path / "ua.ckpt")
        sweep_store = CheckpointStore(path + ".sweep")
        with injected_faults(FaultPlan(fail_sweep_config_chunks=(2,))):
            with pytest.raises(ChunkFailure):
                self._run_sweep(checkpoint=path)
        assert sweep_store.exists(), (
            "no .sweep checkpoint survived the kill")
        orphans = [t.name for t in threading.enumerate()
                   if t.name.startswith("pdp-") and t.is_alive()]
        assert not orphans, f"killed sweep left orphans: {orphans}"

        # Resume: replays only the remaining chunk, bit-identically.
        resumed, res = self._run_sweep(checkpoint=path)
        assert res._resumed_from_chunk == 2
        self._assert_configs_bit_identical(resumed, baseline)
        # Completion cleared the sweep checkpoint — a finished grid
        # cannot be accidentally resumed.
        assert not sweep_store.exists()
        orphans = [t.name for t in threading.enumerate()
                   if t.name.startswith("pdp-") and t.is_alive()]
        assert not orphans, f"resumed sweep left orphans: {orphans}"

    def test_kill_on_first_config_chunk_resumes_from_scratch(
            self, tmp_path):
        baseline, _ = self._run_sweep()
        path = str(tmp_path / "ua0.ckpt")
        with injected_faults(FaultPlan(fail_sweep_config_chunks=(0,))):
            with pytest.raises(ChunkFailure):
                self._run_sweep(checkpoint=path)
        # Nothing was checkpointed — the resume IS a fresh run.
        assert not CheckpointStore(path + ".sweep").exists()
        resumed, res = self._run_sweep(checkpoint=path)
        assert res._resumed_from_chunk == 0
        self._assert_configs_bit_identical(resumed, baseline)


class TestElasticMeshRecovery:
    """Device loss mid-stream is a RECOVERABLE event: the elastic
    wrapper re-forms the mesh from the survivors, resumes from the
    last checkpoint (adopting the original batch assignment regrouped
    onto the smaller mesh), and releases values bit-identical to a
    clean run at the surviving shape — the `mesh.reshard` event on the
    run record. Single-kill AND double-kill-to-single-device."""

    def _params(self, parts):
        return pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)

    def test_single_device_loss_reforms_and_matches_surviving_shape(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu import obs
        from pipelinedp_tpu.parallel import make_mesh
        from pipelinedp_tpu.resilience.faults import DeviceLost
        ds, parts = make_ds(seed=8, n=14_000)
        params = self._params(parts)
        # Clean run at the SURVIVING shape (8 devices halve to 4).
        baseline, _ = run_streamed(ds, params, seed=21,
                                   mesh=make_mesh(4))

        obs.reset()
        store = CheckpointStore(str(tmp_path / "elastic.ckpt"))
        with injected_faults(FaultPlan(lose_device_chunks=(2,))):
            survived, timings = run_streamed(ds, params, seed=21,
                                             mesh=make_mesh(),
                                             checkpoint=store)
        # The run did NOT wedge and did NOT restart from scratch: it
        # re-formed, resumed from the checkpoint, and finished.
        assert timings["stream_mesh_reshards"] == 1
        hist = timings["stream_reshard_history"]
        assert hist[0]["old_devices"] == 8
        assert hist[0]["new_devices"] == 4
        assert hist[0]["reason"] == "device_lost"
        assert timings["stream_resumed_from"] >= 1
        snap = obs.ledger().snapshot()
        reshard_events = [e for e in snap["events"]
                          if e["name"] == "mesh.reshard"]
        assert len(reshard_events) == 1
        assert reshard_events[0]["old_devices"] == 8
        assert reshard_events[0]["new_devices"] == 4
        assert snap["counters"]["checkpoint.elastic_adoptions"] >= 1
        assert_bit_identical(baseline, survived)
        assert not store.exists()  # success cleared the checkpoint

    def test_double_loss_shrinks_to_single_device(self, tmp_path,
                                                  monkeypatch):
        """4 -> 2 -> 1: two participants lost in one run, two reshard
        records, the final single-device mesh still releases values
        bit-identical to a clean 1-device run."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu import obs
        from pipelinedp_tpu.parallel import make_mesh
        ds, parts = make_ds(seed=8, n=9_000)
        params = self._params(parts)
        baseline, _ = run_streamed(ds, params, seed=23,
                                   mesh=make_mesh(1))

        obs.reset()
        store = CheckpointStore(str(tmp_path / "double.ckpt"))
        with injected_faults(FaultPlan(lose_device_chunks=(1, 3))):
            survived, timings = run_streamed(ds, params, seed=23,
                                             mesh=make_mesh(4),
                                             checkpoint=store)
        hist = timings["stream_reshard_history"]
        assert [(h["old_devices"], h["new_devices"]) for h in hist] == [
            (4, 2), (2, 1)]
        assert timings["stream_mesh_reshards"] == 2
        assert_bit_identical(baseline, survived)

    def test_loss_on_last_mesh_reraises(self, monkeypatch):
        """A 1-device mesh has nothing to re-form from: the loss
        propagates instead of looping."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu.parallel import make_mesh
        from pipelinedp_tpu.resilience.faults import DeviceLost
        ds, parts = make_ds(seed=8, n=5_000)
        params = self._params(parts)
        with injected_faults(FaultPlan(lose_device_chunks=(1,))):
            with pytest.raises(DeviceLost):
                run_streamed(ds, params, seed=23, mesh=make_mesh(1))

    def test_loss_without_fixed_seed_reraises(self, monkeypatch):
        """No fixed rng_seed means replay cannot be guaranteed — the
        elastic retry must refuse rather than silently re-draw."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu.parallel import make_mesh
        from pipelinedp_tpu.resilience.faults import DeviceLost
        ds, parts = make_ds(seed=8, n=5_000)
        params = self._params(parts)
        with injected_faults(FaultPlan(lose_device_chunks=(1,))):
            with pytest.raises(DeviceLost):
                run_streamed(ds, params, seed=None, mesh=make_mesh())

    @pytest.mark.parametrize("accumulator", ["fx", "f32"])
    def test_vector_sum_survives_mid_stream_shrink(self, tmp_path,
                                                   monkeypatch,
                                                   accumulator):
        """ISSUE-17 satellite: a VECTOR_SUM workload shrinks 8 -> 4
        mid-stream and resumes matching a clean run at the surviving
        shape. Under 'fx' the match is BIT-identical (int32 lane psum
        + exact per-chunk lanes->steps fold — the same contract the
        scalar metrics hold); under 'f32' it is only
        float-approximate, because the f32 psum's partial-sum grouping
        changes with the device count — the gap the fx accumulator
        exists to close."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        monkeypatch.setenv("PIPELINEDP_TPU_VECTOR_ACCUMULATOR",
                           accumulator)
        from pipelinedp_tpu.parallel import make_mesh
        rng = np.random.default_rng(29)
        n, parts, d = 14_000, 12, 16
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 2_000, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(-1.0, 1.0, (n, d)))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            vector_size=d, vector_max_norm=4.0,
            vector_norm_kind=pdp.NormKind.L2)
        baseline, _ = run_streamed(ds, params, seed=31,
                                   mesh=make_mesh(4))
        store = CheckpointStore(str(tmp_path / "vec_elastic.ckpt"))
        with injected_faults(FaultPlan(lose_device_chunks=(2,))):
            survived, timings = run_streamed(ds, params, seed=31,
                                             mesh=make_mesh(),
                                             checkpoint=store)
        assert timings["stream_mesh_reshards"] == 1
        hist = timings["stream_reshard_history"]
        assert (hist[0]["old_devices"], hist[0]["new_devices"]) == (8, 4)
        assert timings["stream_resumed_from"] >= 1
        if accumulator == "fx":
            assert_bit_identical(baseline, survived)
        else:
            assert set(baseline) == set(survived)
            for k in baseline:
                np.testing.assert_allclose(
                    np.asarray(survived[k].vector_sum),
                    np.asarray(baseline[k].vector_sum), rtol=1e-6)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestNoCpuFallback:
    """The entry points never fall back to the CPU. A wedged device
    probe must yield a NONZERO exit whose stderr explains itself — with
    the heartbeat monitor on, the wedge (held for real via
    ``wedged_hold``) is cancelled at the stall deadline and stderr
    carries the stall diagnosis and the flight-record path, while the
    heartbeat file survives the run. A host without a TPU exits
    nonzero too, and no headline is ever printed."""

    def test_wedged_probe_bench_exits_nonzero_with_stall_diagnosis(
            self, tmp_path):
        ledger_dir = str(tmp_path / "ledger")
        env = dict(os.environ)
        env["PIPELINEDP_TPU_FAULTS"] = "wedged_init=99,wedged_hold=1"
        env["PIPELINEDP_TPU_PROBE_BACKOFF"] = "0.01"  # real clock: tiny
        env["PIPELINEDP_TPU_PROBE_TIMEOUT"] = "30"  # the watchdog cuts it
        env["PIPELINEDP_TPU_PROBE_ATTEMPTS"] = "2"
        env["PIPELINEDP_TPU_HEARTBEAT"] = "1"
        env["PIPELINEDP_TPU_HEARTBEAT_S"] = "0.05"
        env["PIPELINEDP_TPU_STALL_S"] = "0.3"
        env["PIPELINEDP_TPU_LEDGER_DIR"] = ledger_dir
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PYTHONPATH", None)
        proc = subprocess.run(
            [sys.executable, "bench.py", "--smoke", "--flagship-only",
             "--stream-rows", "0"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode != 0, proc.stderr[-3000:]
        assert not proc.stdout.strip(), proc.stdout[-2000:]
        assert "DEVICE UNREACHABLE after 2 probe attempts" in proc.stderr
        # The watchdog, not the 30s probe timeout, ended each attempt.
        assert "cancelled by the stall watchdog" in proc.stderr
        assert "## stall: " in proc.stderr
        flight_path = proc.stderr.split("## flight record: ")[1].split(
            "\n")[0].strip()
        flight = json.load(open(flight_path, encoding="utf-8"))
        assert flight["stall"]["deadline_s"] == 0.3
        # The live heartbeat streamed next to the durable ledger,
        # namespaced by the bench's run name (resident processes
        # sharing a ledger dir must not clobber each other's beat).
        import glob as _glob
        hb_files = _glob.glob(os.path.join(ledger_dir,
                                           "heartbeat-bench-*.json"))
        assert hb_files, os.listdir(ledger_dir)
        hb = json.load(open(hb_files[0], encoding="utf-8"))
        assert hb["phase"]

    def test_probe_helper_exits_nonzero_without_subprocess(
            self, monkeypatch, capsys):
        """The bench probe helper itself (fast, tier-1): wedged probe →
        SystemExit(1) after the env knobs' attempt count, and the
        process platform is left alone."""
        monkeypatch.setenv("PIPELINEDP_TPU_PROBE_ATTEMPTS", "2")
        monkeypatch.setenv("PIPELINEDP_TPU_PROBE_BACKOFF", "0.0")
        platforms = os.environ.get("JAX_PLATFORMS")
        monkeypatch.syspath_prepend(REPO)
        import bench
        with injected_faults(FaultPlan(wedged_init=99)):
            with pytest.raises(SystemExit) as exc:
                bench._require_device()
        assert exc.value.code == 1
        assert ("DEVICE UNREACHABLE after 2 probe attempts"
                in capsys.readouterr().err)
        assert os.environ.get("JAX_PLATFORMS") == platforms
        assert not os.environ.get("PIPELINEDP_TPU_DEGRADED")

    @pytest.mark.parametrize("cmd", [
        ["bench.py", "--smoke", "--flagship-only", "--stream-rows", "0"],
        ["chip_smoke.py"],
    ])
    def test_entry_point_without_tpu_exits_nonzero(self, cmd):
        """On the CPU both entry points refuse within seconds and print
        no result line."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PIPELINEDP_TPU_FAULTS", None)
        proc = subprocess.run([sys.executable] + cmd, cwd=REPO, env=env,
                              capture_output=True, text=True,
                              timeout=120)
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert not proc.stdout.strip().startswith("{")
        assert "TPU" in proc.stderr, proc.stderr[-2000:]

    @pytest.mark.parametrize("held", [False, True])
    def test_probe_child_only_before_the_backend_is_held(
            self, monkeypatch, held):
        """A process that took its backend through ``hold_backend``
        (as JaxBackend and the entry points do) probes in-process: a
        child could not get the chip it holds. Before that, the probe
        is a killable child."""
        from pipelinedp_tpu.resilience import health
        spawned = []

        class NoChild:
            def __init__(self, *a, **kw):
                spawned.append(a)
                raise OSError("no child in this test")

        monkeypatch.setattr(health, "_backend_held", held)
        monkeypatch.setattr(health.subprocess, "Popen", NoChild)
        ok, detail = health.probe_devices(timeout_s=5.0)
        assert bool(spawned) != held
        assert ok == held, detail
