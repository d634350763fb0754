"""Overlapped-ingest executor tests: serial/overlapped bit-parity
(released values, kept-partition sets, checkpoint bytes at every
``ckpt_every`` boundary), fault-kill drain with zero orphan threads,
the O(n) batch assignment, the id-narrowing tiers end-to-end, and the
persistent compile cache. ``make perfcheck`` runs this file plus
``tests/test_faults.py``.
"""

import os
import threading

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import ingest
from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ingest import executor as ingest_executor
from pipelinedp_tpu.resilience import CheckpointStore, FaultPlan, injected_faults
from pipelinedp_tpu.resilience.faults import ChunkFailure


@pytest.fixture(autouse=True)
def tiny_chunks(monkeypatch):
    monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "997")


def ingest_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(ingest.THREAD_PREFIX) and t.is_alive()]


@pytest.fixture(autouse=True)
def no_orphan_threads():
    """EVERY test in this file — including the fault-kill ones — must
    leave zero executor threads behind."""
    yield
    assert not ingest_threads(), (
        f"orphan ingest threads: {[t.name for t in ingest_threads()]}")


def run_streamed(ds, params, *, executor, seed=0, eps=5.0, delta=1e-6,
                 public=None, checkpoint=None, mesh=None,
                 min_batches=2):
    ds.invalidate_cache()
    prev = os.environ.get(ingest_executor.ENV_VAR)
    os.environ[ingest_executor.ENV_VAR] = "1" if executor else "0"
    try:
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=delta)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed, mesh=mesh,
                                              checkpoint=checkpoint))
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=public)
        acc.compute_budgets()
        got = dict(res)
    finally:
        if prev is None:
            os.environ.pop(ingest_executor.ENV_VAR, None)
        else:
            os.environ[ingest_executor.ENV_VAR] = prev
    assert res.timings.get("stream_batches", 0) >= min_batches, (
        "dataset did not stream — executor parity not exercised")
    want = "overlapped" if executor else "serial"
    assert res.timings["stream_executor"] == want
    return got, res.timings


def make_ds(seed=1, n=9_000, users=2_000, parts=12):
    rng = np.random.default_rng(seed)
    return pdp.ArrayDataset(privacy_ids=rng.integers(0, users, n),
                            partition_keys=rng.integers(0, parts, n),
                            values=rng.uniform(0.0, 10.0, n)), parts


def assert_bit_identical(got_a, got_b):
    """EXACT equality of kept sets and every released metric value."""
    assert set(got_a) == set(got_b), (
        f"kept sets differ: {sorted(set(got_a) ^ set(got_b))}")
    for k in got_a:
        ta, tb = got_a[k], got_b[k]
        assert ta._fields == tb._fields
        for f in ta._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ta, f)), np.asarray(getattr(tb, f)),
                err_msg=f"partition {k}.{f}")


class RecordingStore(CheckpointStore):
    """Checkpoint store that snapshots every save — the evidence that
    serial and overlapped runs write IDENTICAL checkpoint files at
    every ``ckpt_every`` boundary."""

    def __init__(self, path):
        super().__init__(path)
        self.snapshots = []

    def save(self, ckpt):
        self.snapshots.append(
            (ckpt.next_batch,
             {k: np.array(v, copy=True) for k, v in ckpt.arrays.items()}))
        super().save(ckpt)


class TestExecutorBitParity:
    """The acceptance oracle: executor on and off produce bit-identical
    releases, kept sets and checkpoint bytes under the same seed."""

    def _params(self, parts):
        return pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)

    def test_single_device_parity_with_checkpoints(self, tmp_path):
        ds, parts = make_ds(seed=1)
        params = self._params(parts)
        stores = {}
        results = {}
        for mode in (False, True):
            stores[mode] = RecordingStore(
                str(tmp_path / f"par_{mode}.ckpt"))
            results[mode], _ = run_streamed(ds, params, executor=mode,
                                            seed=42,
                                            checkpoint=stores[mode])
        assert_bit_identical(results[False], results[True])
        ser, ovl = stores[False].snapshots, stores[True].snapshots
        assert len(ser) == len(ovl) > 1
        for (nb_s, arr_s), (nb_o, arr_o) in zip(ser, ovl):
            assert nb_s == nb_o
            assert sorted(arr_s) == sorted(arr_o)
            for k in arr_s:
                np.testing.assert_array_equal(arr_s[k], arr_o[k],
                                              err_msg=f"ckpt {nb_s}:{k}")
        # Success cleared both stores.
        assert not stores[False].exists() and not stores[True].exists()

    def test_mesh_parity(self, monkeypatch):
        """Same contract on the 8-device CPU mesh (sharded kernels +
        owner-block fetch under the fold worker)."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        from pipelinedp_tpu.parallel import make_mesh
        mesh = make_mesh()
        ds, parts = make_ds(seed=8, n=14_000)
        params = self._params(parts)
        serial, _ = run_streamed(ds, params, executor=False, seed=21,
                                 mesh=mesh)
        overlapped, _ = run_streamed(ds, params, executor=True, seed=21,
                                     mesh=mesh)
        assert_bit_identical(serial, overlapped)

    def test_percentile_two_pass_parity(self):
        """Percentile configs run pass B through the stager too (device
        cache or re-ship) and must stay bit-identical."""
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
        serial, _ = run_streamed(ds, params, executor=False, seed=13,
                                 public=public)
        overlapped, _ = run_streamed(ds, params, executor=True, seed=13,
                                     public=public)
        assert_bit_identical(serial, overlapped)

    def test_percentile_reship_parity(self, monkeypatch):
        """Pass B with the device cache disabled re-streams through a
        fresh BackgroundStager per sweep, staging into the rotating
        StagingRing buffers (fresh-copy retention is only needed while
        feeding the cache — see tests/test_pass_b.py for the staging-
        mode parity matrix)."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CACHE", "0")
        self.test_percentile_two_pass_parity()

    def test_overlap_breakdown_in_timings(self):
        """The executor reports the per-phase breakdown the bench JSON
        emits; phase accounting must cover actual work."""
        ds, parts = make_ds(seed=3)
        params = self._params(parts)
        _, timings = run_streamed(ds, params, executor=True, seed=7)
        for k in ("stream_t_stage", "stream_t_fold", "stream_t_device",
                  "stream_t_total", "stream_overlap_frac"):
            assert k in timings, k
        assert timings["stream_t_stage"] > 0
        assert timings["stream_t_total"] > 0
        assert 0.0 <= timings["stream_overlap_frac"] < 1.0


class TestExecutorFaultDrain:
    """A fault-injected chunk kill must sever the overlapped pipeline at
    the chunk boundary, leave no orphan threads (the autouse fixture
    asserts it after EVERY test here), and resume bit-identically."""

    def test_kill_drains_and_resumes_bit_identically(self, tmp_path):
        ds, parts = make_ds(seed=5)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        baseline, _ = run_streamed(ds, params, executor=True, seed=42)
        store = CheckpointStore(str(tmp_path / "kill.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, executor=True, seed=42,
                             checkpoint=store)
        assert not ingest_threads(), "kill left orphan executor threads"
        assert store.exists(), "no checkpoint survived the kill"
        resumed, timings = run_streamed(ds, params, executor=True,
                                        seed=42, checkpoint=store)
        assert timings["stream_resumed_from"] >= 1
        assert_bit_identical(baseline, resumed)
        assert not store.exists()

    def test_serial_kill_resumes_into_overlapped(self, tmp_path):
        """Cross-mode resume: a checkpoint written by the serial path
        restores into the overlapped path bit-identically (the fold
        prefix is mode-independent monoid state)."""
        ds, parts = make_ds(seed=6)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50)
        baseline, _ = run_streamed(ds, params, executor=False, seed=9)
        store = CheckpointStore(str(tmp_path / "cross.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(4,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, executor=False, seed=9,
                             checkpoint=store)
        resumed, _ = run_streamed(ds, params, executor=True, seed=9,
                                  checkpoint=store)
        assert_bit_identical(baseline, resumed)


class TestExecutorPrimitives:
    """Unit tests for the cancellable worker machinery."""

    def test_stager_orders_and_exhausts(self):
        with ingest.BackgroundStager(lambda c: iter(range(50)),
                                     depth=1) as st:
            assert list(st.items()) == list(range(50))

    def test_stager_propagates_generator_exception(self):
        def gen(cancelled):
            yield 1
            raise RuntimeError("stage boom")

        st = ingest.BackgroundStager(gen, depth=1)
        with pytest.raises(RuntimeError, match="stage boom"):
            list(st.items())
        # close() after the exception was delivered must not re-raise.
        st.close()

    def test_stager_close_unblocks_full_queue(self):
        def gen(cancelled):
            for i in range(10_000):
                yield i

        st = ingest.BackgroundStager(gen, depth=1)
        it = st.items()
        assert next(it) == 0
        st.close()  # generator still had ~10k items queued/pending
        assert not ingest_threads()

    def test_fold_worker_is_ordered_and_drains(self):
        seen = []
        w = ingest.OrderedFoldWorker(seen.append, depth=2)
        for i in range(100):
            w.submit(i)
        w.finish()
        assert seen == list(range(100))

    def test_fold_worker_propagates_exception(self):
        def fold(item):
            raise ValueError("fold boom")

        w = ingest.OrderedFoldWorker(fold, depth=2)
        with pytest.raises(ValueError, match="fold boom"):
            for i in range(100):
                w.submit(i)
            w.finish()
        w.cancel()

    def test_fold_worker_cancel_drops_queue(self):
        release = threading.Event()
        seen = []

        def fold(item):
            release.wait(10.0)
            seen.append(item)

        w = ingest.OrderedFoldWorker(fold, depth=3)
        w.submit(0)
        w.submit(1)
        w.submit(2)
        # Cancel while fold(0) is in progress; only release the fold
        # once the stop flag is visibly set, so the worker observes the
        # cancel deterministically before it could take item 1.
        canceller = threading.Thread(target=w.cancel)
        canceller.start()
        assert w._cancelled.wait(10.0)
        release.set()
        canceller.join(10.0)
        assert not canceller.is_alive()
        # The in-progress fold finishes; queued items are dropped.
        assert seen in ([], [0]), seen
        assert not ingest_threads()

    def test_staging_ring_gates_reuse(self):
        ring = ingest.StagingRing(2)
        ring.acquire()
        ring.acquire()
        cancelled = threading.Event()
        cancelled.set()
        with pytest.raises(ingest.IngestCancelled):
            ring.acquire(cancelled)  # full + cancelled -> aborts
        ring.retire()
        ring.acquire()  # a retire frees a slot


class TestBatchAssignment:
    """The O(n) counting-sort scatter must reproduce the stable argsort
    order exactly (bit-identical batch contents)."""

    @pytest.mark.parametrize("n,cells", [(10_000, 3), (10_000, 96),
                                         (4_096, 1), (20_000, 70_000)])
    def test_matches_stable_argsort(self, n, cells):
        rng = np.random.default_rng(n + cells)
        cell = rng.integers(0, cells, n).astype(np.int64)
        order, counts = ingest.group_rows_by_cell(cell, cells)
        np.testing.assert_array_equal(order,
                                      np.argsort(cell, kind="stable"))
        np.testing.assert_array_equal(counts,
                                      np.bincount(cell, minlength=cells))

    def test_assignment_unchanged_by_rewrite(self):
        """_batch_assignment end-to-end: same (order, counts) contract
        as the seed's argsort implementation, units stay whole."""
        from pipelinedp_tpu import streaming
        rng = np.random.default_rng(77)
        n = 6_000
        pid = rng.integers(0, 500, n)
        enc = je.EncodedData(pid=pid.astype(np.int32),
                             pk=np.zeros(n, np.int32),
                             values=np.zeros(n, np.float32),
                             pk_vocab=[0], n_rows=n)
        config = je.FusedConfig.from_params(
            pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                max_partitions_contributed=1,
                                max_contributions_per_partition=1),
            public=True)
        for n_dev in (1, 8):
            order, counts = streaming._batch_assignment(config, enc, 5,
                                                        321, n_dev)
            assert counts.sum() == n
            # Reference: the seed's explicit stable argsort over cells.
            from pipelinedp_tpu.ops.segment import fmix32
            h = fmix32(pid.astype(np.uint32) ^ np.uint32(321))
            batch = ((h.astype(np.uint64) * np.uint64(5)) >>
                     np.uint64(32)).astype(np.int64)
            cell = batch
            if n_dev > 1:
                cell = batch * n_dev + (fmix32(pid.astype(np.uint32)) %
                                        np.uint32(n_dev)).astype(np.int64)
            np.testing.assert_array_equal(
                order, np.argsort(cell, kind="stable"))


class TestIdNarrowingTiers:
    """Satellite: the three byte-plane tiers, at their boundaries and
    end-to-end through streaming."""

    @pytest.mark.parametrize("max_id,spec", [
        ((1 << 16) - 1, "u16"), (1 << 16, "u8x3"),
        ((1 << 24) - 1, "u8x3"), (1 << 24, "i32"),
    ])
    def test_round_trip_at_tier_boundaries(self, max_id, spec):
        assert je._plane_spec(max_id) == spec
        ids = np.array([0, 1, 255, 256, 65_535, max_id // 2,
                        max_id - 1, max_id], np.int64)
        ids = np.unique(np.clip(ids, 0, max_id)).astype(np.int32)
        planes = je._narrow_ids(ids, spec)
        widened = np.asarray(je._widen_ids(planes))
        np.testing.assert_array_equal(widened, ids)

    @pytest.mark.parametrize("pid_hi,spec", [
        ((1 << 16) - 1, "u16"),
        ((1 << 24) - 1, "u8x3"),
        ((1 << 24) + (1 << 20), "i32"),
    ])
    def test_streaming_end_to_end_per_tier(self, pid_hi, spec):
        """Each tier's ship path must stream exact aggregates. The pid
        column pins the tier: ids pass through encode un-densified, and
        the max is planted so the tier is exactly the one under test."""
        rng = np.random.default_rng(pid_hi % 1000)
        n = 5_000
        pid = rng.integers(max(0, pid_hi - 50_000), pid_hi, n)
        pid[0] = pid_hi  # plant the max: the tier decision is global
        ds = pdp.ArrayDataset(privacy_ids=pid,
                              partition_keys=rng.integers(0, 8, n),
                              values=rng.uniform(0.0, 10.0, n))
        enc = je.encode(ds, pdp.DataExtractors(), None, None)
        assert je._plane_spec(int(enc.pid.max())) == spec
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=8,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        for executor in (False, True):
            got, _ = run_streamed(ds, params, executor=executor,
                                  seed=3, eps=1e12, delta=1e-2,
                                  public=list(range(8)))
            for p in range(8):
                m = ds.partition_keys == p
                assert got[p].count == pytest.approx(m.sum(), abs=0.5)
                assert got[p].sum == pytest.approx(
                    ds.values[m].sum(), rel=1e-5)


class TestSweepCheckpointResume:
    """Satellite: budget-safe chunk-prefix resume of the analysis sweep
    (the ROADMAP open item)."""

    def _setup(self, monkeypatch):
        from pipelinedp_tpu import analysis
        from pipelinedp_tpu.analysis import jax_sweep
        monkeypatch.setattr(jax_sweep, "_CHUNK_CAP", 4)  # force chunks
        rng = np.random.default_rng(1)
        n = 4_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 800, n),
                              partition_keys=rng.integers(0, 10, n),
                              values=rng.uniform(0, 5, n))
        caps = list(range(1, 13))
        multi = analysis.MultiParameterConfiguration(
            max_partitions_contributed=caps,
            max_contributions_per_partition=[2] * len(caps))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=2)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=params,
            multi_param_configuration=multi)

        def run(backend):
            return list(analysis.perform_utility_analysis(
                ds, backend, options, pdp.DataExtractors()))[0]

        return ds, run

    def test_killed_sweep_resumes_bit_identically(self, tmp_path,
                                                  monkeypatch):
        _, run = self._setup(monkeypatch)
        baseline = run(JaxBackend(rng_seed=0))
        store = CheckpointStore(str(tmp_path / "sweep.ckpt"))
        # The sweep writes a SIBLING file (the backend's own checkpoint
        # path belongs to streamed aggregations).
        sweep_file = CheckpointStore(store.path + ".sweep")
        with injected_faults(FaultPlan(fail_chunks=(2,))):
            with pytest.raises(ChunkFailure):
                run(JaxBackend(rng_seed=0, checkpoint=store))
        assert sweep_file.exists(), "no sweep checkpoint survived"
        assert not store.exists(), (
            "the sweep must not touch the stream's checkpoint path")
        resumed = run(JaxBackend(rng_seed=0, checkpoint=store))
        # Success must clear the checkpoint (finished sweeps never
        # resume into a fresh run).
        assert not sweep_file.exists()
        assert len(resumed) == len(baseline) == 12
        for a, b in zip(baseline, resumed):
            assert (a.count_metrics.error_expected ==
                    b.count_metrics.error_expected)
            assert (a.count_metrics.error_quantiles ==
                    b.count_metrics.error_quantiles)
            assert (a.partition_selection_metrics.dropped_partitions_expected
                    == b.partition_selection_metrics
                    .dropped_partitions_expected)

    def test_sweep_and_stream_checkpoints_coexist(self, tmp_path,
                                                  monkeypatch):
        """One backend protecting BOTH features: a killed stream's
        checkpoint must not break (or be destroyed by) a later sweep on
        the same backend — the sweep uses its sibling file."""
        store = CheckpointStore(str(tmp_path / "both.ckpt"))
        ds, parts = make_ds(seed=12, n=6_000)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50)
        with injected_faults(FaultPlan(fail_chunks=(3,))):
            with pytest.raises(ChunkFailure):
                run_streamed(ds, params, executor=True, seed=4,
                             checkpoint=store)
        assert store.exists()
        stream_bytes = open(store.path, "rb").read()
        # The sweep on the same backend runs clean and leaves the
        # stream's resume state untouched.
        _, run = self._setup(monkeypatch)
        assert len(run(JaxBackend(rng_seed=0, checkpoint=store))) == 12
        assert open(store.path, "rb").read() == stream_bytes
        # And the stream still resumes bit-identically afterwards.
        resumed, timings = run_streamed(ds, params, executor=True,
                                        seed=4, checkpoint=store)
        assert timings["stream_resumed_from"] >= 1
        baseline, _ = run_streamed(ds, params, executor=True, seed=4)
        assert_bit_identical(baseline, resumed)

    def test_mismatched_sweep_checkpoint_refuses(self, tmp_path,
                                                 monkeypatch):
        from pipelinedp_tpu.resilience import CheckpointMismatch
        _, run = self._setup(monkeypatch)
        store = CheckpointStore(str(tmp_path / "sweep2.ckpt"))
        with injected_faults(FaultPlan(fail_chunks=(2,))):
            with pytest.raises(ChunkFailure):
                run(JaxBackend(rng_seed=0, checkpoint=store))
        # Different DATA, same shape: the content digest must refuse.
        rng = np.random.default_rng(99)
        n = 4_000
        ds_b = pdp.ArrayDataset(privacy_ids=rng.integers(0, 800, n),
                                partition_keys=rng.integers(0, 10, n),
                                values=rng.uniform(0, 5, n))
        from pipelinedp_tpu import analysis
        caps = list(range(1, 13))
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT],
                noise_kind=pdp.NoiseKind.LAPLACE,
                max_partitions_contributed=4,
                max_contributions_per_partition=2),
            multi_param_configuration=analysis.MultiParameterConfiguration(
                max_partitions_contributed=caps,
                max_contributions_per_partition=[2] * len(caps)))
        with pytest.raises(CheckpointMismatch):
            list(analysis.perform_utility_analysis(
                ds_b, JaxBackend(rng_seed=0, checkpoint=store), options,
                pdp.DataExtractors()))[0]


class TestCompileCache:
    """Satellite: the opt-in persistent XLA compile cache."""

    def test_env_knob_populates_cache_dir(self, tmp_path, monkeypatch):
        from pipelinedp_tpu.ingest import compile_cache
        import jax
        cache_dir = tmp_path / "xla_cache"
        monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
        monkeypatch.setenv(compile_cache.ENV_VAR, str(cache_dir))
        monkeypatch.setattr(compile_cache, "_configured", None)
        try:
            assert (compile_cache.maybe_enable_compile_cache() ==
                    str(cache_dir))
            # Idempotent re-entry (every backend construction calls it).
            assert (compile_cache.maybe_enable_compile_cache() ==
                    str(cache_dir))
            backend = JaxBackend(rng_seed=0)  # engine init wires it
            # Drop the in-process executable caches: earlier tests have
            # already compiled the engine's program shapes, and a jit
            # cache hit never reaches the persistent cache.
            import jax
            jax.clear_caches()
            # Enabling the cache started the process's compile count.
            _, compiles0, _, misses0 = (
                compile_cache.compile_stats().snapshot())
            rng = np.random.default_rng(0)
            ds = pdp.ArrayDataset(
                privacy_ids=rng.integers(0, 50, 3_000),
                partition_keys=rng.integers(0, 5, 3_000),
                values=rng.uniform(0, 1, 3_000))
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                            total_delta=1e-6)
            engine = pdp.DPEngine(acc, backend)
            res = engine.aggregate(
                ds, pdp.AggregateParams(
                    metrics=[pdp.Metrics.COUNT],
                    max_partitions_contributed=5,
                    max_contributions_per_partition=2),
                pdp.DataExtractors(), public_partitions=list(range(5)))
            acc.compute_budgets()
            assert len(dict(res)) == 5
            assert any(cache_dir.iterdir()), (
                "no compiled executables persisted to the cache dir")
            _, compiles1, _, misses1 = (
                compile_cache.compile_stats().snapshot())
            assert compiles1 > compiles0 and misses1 > misses0
        finally:
            # Un-point jax from the tmp dir (deleted after the test).
            jax.config.update("jax_compilation_cache_dir", None)
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc)
            _cc.reset_cache()
            monkeypatch.setattr(compile_cache, "_configured", None)

    def test_unset_env_is_noop(self, monkeypatch):
        from pipelinedp_tpu.ingest import compile_cache
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "_configured", None)
        assert compile_cache.maybe_enable_compile_cache() is None

    def test_jax_env_var_wins_and_sets_no_dir(self, tmp_path,
                                              monkeypatch):
        """``JAX_COMPILATION_CACHE_DIR`` is JAX's own: it beats the
        library knob and the entry point's default, and no directory
        is set in code."""
        from pipelinedp_tpu.ingest import compile_cache
        import jax
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.JAX_ENV_VAR,
                           str(tmp_path / "jax"))
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "pdp"))
        monkeypatch.setattr(compile_cache, "_configured", None)
        try:
            assert (compile_cache.maybe_enable_compile_cache(
                default_dir=str(tmp_path / "default")) ==
                    str(tmp_path / "jax"))
            assert jax.config.jax_compilation_cache_dir == before
        finally:
            monkeypatch.setattr(compile_cache, "_configured", None)

    def test_default_dir_when_no_env(self, tmp_path, monkeypatch):
        """The entry points' fixed in-checkout path applies only when
        neither variable names a directory."""
        from pipelinedp_tpu.ingest import compile_cache
        from jax.experimental.compilation_cache import compilation_cache
        import jax
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "_configured", None)
        default = str(tmp_path / "default")
        try:
            assert (compile_cache.maybe_enable_compile_cache(
                default_dir=default) == default)
            assert jax.config.jax_compilation_cache_dir == default
        finally:
            jax.config.update("jax_compilation_cache_dir", None)
            compilation_cache.reset_cache()
            monkeypatch.setattr(compile_cache, "_configured", None)
