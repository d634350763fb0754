"""Differential tests: the fused JAX plane vs the LocalBackend oracle.

Strategy (SURVEY.md §4/§7): run the same aggregation with huge eps on both
planes — noise vanishes, so the raw bounded aggregates must agree; plus
targeted tests of bounding, selection, public partitions and fallbacks.
Runs on the virtual 8-device CPU mesh configured in conftest.py.
"""

import operator

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ops import noise as noise_ops

BIG_EPS = 1e5


def extractors():
    return pdp.DataExtractors(privacy_id_extractor=operator.itemgetter(0),
                              partition_extractor=operator.itemgetter(1),
                              value_extractor=operator.itemgetter(2))


def run(backend, data, params, public_partitions=None, eps=BIG_EPS,
        delta=1e-10, ext=None):
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, backend)
    result = engine.aggregate(data, params, ext or extractors(),
                              public_partitions=public_partitions)
    acc.compute_budgets()
    return dict(result)


def count_params(**kw):
    base = dict(metrics=[pdp.Metrics.COUNT], max_partitions_contributed=3,
                max_contributions_per_partition=2)
    base.update(kw)
    return pdp.AggregateParams(**base)


class TestWideIdPacking:
    """Ids >= 2^16 ship as 3xuint8 planes over the host link; the pack /
    widen round trip must be exact at every width boundary."""

    @pytest.mark.parametrize("top", [(1 << 16) - 1, 1 << 16, (1 << 16) + 1,
                                     (1 << 24) - 1, 1 << 24])
    def test_roundtrip_at_boundaries(self, top):
        from pipelinedp_tpu import jax_engine as je
        ids = np.array([0, 1, 7, top - 1, top], np.int64)
        enc = je.EncodedData(pid=ids.astype(np.int64),
                             pk=np.arange(len(ids), dtype=np.int32),
                             values=np.zeros(len(ids), np.float32),
                             pk_vocab=list(range(len(ids))),
                             n_rows=len(ids))
        pid, pk, _, valid = je.pad_and_put(enc, None)
        got = np.asarray(pid)[:len(ids)]
        np.testing.assert_array_equal(got, ids)
        assert np.asarray(valid)[:len(ids)].all()

    def test_wide_ids_match_oracle(self):
        # pids and pks both above 2^16: the fused result must equal the
        # LocalBackend oracle partition by partition (caps never bind).
        rng = np.random.default_rng(5)
        n = 4000
        pid = rng.integers(70_000, 120_000, n)
        pk = rng.integers(0, 300, n) + 100_000
        vals = rng.uniform(0, 10, n)
        ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                              values=vals)
        public = sorted(np.unique(pk).tolist())
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=20,
            max_contributions_per_partition=20,
            min_value=0.0, max_value=10.0)
        fused = run(JaxBackend(rng_seed=0), ds, params,
                    public_partitions=public, eps=1e6,
                    ext=pdp.DataExtractors())
        local = run(pdp.LocalBackend(), ds, params,
                    public_partitions=public, eps=1e6,
                    ext=pdp.DataExtractors())
        assert set(fused) == set(local) == set(public)
        for k in public:
            assert round(fused[k].count) == round(local[k].count), k
            assert fused[k].sum == pytest.approx(local[k].sum, abs=0.5), k


class TestFusedEdgeCases:
    """Degenerate shapes through the fused plane."""

    @staticmethod
    def _run(ds, public=None):
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=5,
            max_contributions_per_partition=5,
            min_value=0.0, max_value=10.0)
        return run(JaxBackend(rng_seed=0), ds, params,
                   public_partitions=public, eps=1e12, delta=1e-2,
                   ext=pdp.DataExtractors())

    def test_empty_rejected_like_reference(self):
        ds = pdp.ArrayDataset(privacy_ids=np.array([], np.int64),
                              partition_keys=np.array([], np.int64),
                              values=np.array([], np.float64))
        with pytest.raises(ValueError, match="non-empty"):
            self._run(ds)

    def test_single_row(self):
        got = self._run(
            pdp.ArrayDataset(privacy_ids=np.array([3]),
                             partition_keys=np.array([5]),
                             values=np.array([2.5])), public=[5])
        assert got[5].count == pytest.approx(1.0, abs=1e-3)
        assert got[5].sum == pytest.approx(2.5, abs=1e-3)

    def test_one_pid_one_partition_caps_bind(self):
        # 5000 identical contributions from one user: linf=5 keeps 5.
        got = self._run(
            pdp.ArrayDataset(privacy_ids=np.zeros(5000, np.int64),
                             partition_keys=np.zeros(5000, np.int64),
                             values=np.full(5000, 1.0)), public=[0])
        assert got[0].count == pytest.approx(5.0, abs=1e-2)
        assert got[0].sum == pytest.approx(5.0, abs=1e-2)

    def test_count_without_values_column(self):
        # values=None COUNT: the int32 count column must survive the
        # stacked transfer bit-exactly (on real TPUs, small ints bitcast
        # to float32 are subnormals and get flushed to zero).
        ds = pdp.ArrayDataset(privacy_ids=np.arange(500),
                              partition_keys=np.zeros(500, np.int64),
                              values=None)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=1,
                                     max_contributions_per_partition=1)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e12,
                                        total_delta=1e-2)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=[0])
        acc.compute_budgets()
        assert dict(res)[0].count == pytest.approx(500, abs=0.01)

    def test_negative_keys_roundtrip(self):
        got = self._run(
            pdp.ArrayDataset(privacy_ids=np.array([-5, -5, 7]),
                             partition_keys=np.array([-9, -9, -9]),
                             values=np.array([1.0, 2.0, 3.0])),
            public=[-9])
        assert set(got) == {-9}
        assert got[-9].count == pytest.approx(3.0, abs=1e-2)
        assert got[-9].sum == pytest.approx(6.0, abs=1e-2)


class TestDifferentialVsLocal:

    def test_count(self):
        noise_ops.seed_host_rng(0)
        data = [(u, pk, 1.0) for u in range(50) for pk in ("a", "b", "c")]
        local = run(pdp.LocalBackend(), data, count_params())
        fused = run(JaxBackend(rng_seed=1), data, count_params())
        assert set(local) == set(fused) == {"a", "b", "c"}
        for k in local:
            assert fused[k].count == pytest.approx(local[k].count,
                                                   abs=0.5)

    def test_sum_mean_variance(self):
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(0)
        data = [(u, "p" + str(u % 4), float(v))
                for u, v in enumerate(rng.uniform(0, 10, 400))]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VARIANCE, pdp.Metrics.MEAN,
                     pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=10.0)
        local = run(pdp.LocalBackend(), data, params)
        fused = run(JaxBackend(rng_seed=2), data, params)
        assert set(local) == set(fused)
        for k in local:
            assert fused[k].count == pytest.approx(local[k].count, abs=0.5)
            assert fused[k].sum == pytest.approx(local[k].sum, rel=0.01)
            assert fused[k].mean == pytest.approx(local[k].mean, abs=0.05)
            assert fused[k].variance == pytest.approx(local[k].variance,
                                                      abs=0.2)

    def test_sum_per_partition_bounds(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "a", 100.0) for u in range(20)]  # each user sum 100
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=5, min_sum_per_partition=0.0,
            max_sum_per_partition=10.0)
        fused = run(JaxBackend(rng_seed=3), data, params)
        # 20 users, each clipped to 10 -> 200.
        assert fused["a"].sum == pytest.approx(200.0, rel=0.01)

    def test_privacy_id_count(self):
        noise_ops.seed_host_rng(0)
        # 30 users, each with 5 rows in partition a.
        data = [(u, "a", 1.0) for u in range(30) for _ in range(5)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=1,
            max_contributions_per_partition=1)
        fused = run(JaxBackend(rng_seed=4), data, params)
        assert fused["a"].privacy_id_count == pytest.approx(30, abs=0.5)


class TestFusedBounding:

    def test_linf_caps_rows(self):
        noise_ops.seed_host_rng(0)
        data = [(0, "a", 1.0)] * 100  # one user, 100 rows
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=7)
        fused = run(JaxBackend(rng_seed=5), data, params,
                    public_partitions=["a"])
        assert fused["a"].count == pytest.approx(7, abs=0.5)

    def test_l0_caps_partitions(self):
        noise_ops.seed_host_rng(0)
        pks = [f"p{i}" for i in range(10)]
        data = [(u, pk, 1.0) for u in range(200) for pk in pks]
        params = count_params(max_partitions_contributed=2,
                              max_contributions_per_partition=1)
        fused = run(JaxBackend(rng_seed=6), data, params,
                    public_partitions=pks)
        total = sum(v.count for v in fused.values())
        # Each user contributes to exactly 2 of 10 partitions.
        assert total == pytest.approx(400, rel=0.1)

    def test_sum_clipping(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "a", 100.0) for u in range(10)]  # clipped to 10 each
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=10.0)
        fused = run(JaxBackend(rng_seed=7), data, params)
        assert fused["a"].sum == pytest.approx(100.0, rel=0.01)


class TestFusedSelection:

    def test_small_partition_dropped(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "big", 1.0) for u in range(1000)] + [(5000, "tiny",
                                                          1.0)]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1)
        fused = run(JaxBackend(rng_seed=8), data, params, eps=1.0,
                    delta=1e-6)
        assert "big" in fused
        assert "tiny" not in fused

    @pytest.mark.parametrize("strategy", [
        pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC,
        pdp.PartitionSelectionStrategy.LAPLACE_THRESHOLDING,
        pdp.PartitionSelectionStrategy.GAUSSIAN_THRESHOLDING,
    ])
    def test_strategies(self, strategy):
        noise_ops.seed_host_rng(0)
        data = [(u, "big", 1.0) for u in range(1000)]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1,
                              partition_selection_strategy=strategy)
        fused = run(JaxBackend(rng_seed=9), data, params, eps=1.0,
                    delta=1e-6)
        assert "big" in fused

    def test_pre_threshold(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "mid", 1.0) for u in range(50)]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1,
                              pre_threshold=100)
        fused = run(JaxBackend(rng_seed=10), data, params, eps=BIG_EPS,
                    delta=1e-6)
        assert fused == {}


class TestFusedPublicPartitions:

    def test_empty_partition_injected(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "a", 1.0) for u in range(40)]
        params = count_params()
        fused = run(JaxBackend(rng_seed=11), data, params,
                    public_partitions=["a", "missing"])
        assert fused["a"].count == pytest.approx(40, abs=0.5)
        assert fused["missing"].count == pytest.approx(0, abs=0.5)

    def test_non_public_dropped(self):
        noise_ops.seed_host_rng(0)
        data = [(u, pk, 1.0) for u in range(40) for pk in ("a", "b")]
        fused = run(JaxBackend(rng_seed=12), data, count_params(),
                    public_partitions=["a"])
        assert set(fused) == {"a"}


class TestFusedVectorSum:

    def test_vector_sum_linf(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "a", [1.0, 2.0]) for u in range(50)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1, vector_size=2,
            vector_max_norm=1000.0,
            vector_norm_kind=pdp.NormKind.Linf)
        fused = run(JaxBackend(rng_seed=13), data, params)
        np.testing.assert_allclose(fused["a"].vector_sum, [50.0, 100.0],
                                   atol=1.0)

    def test_vector_sum_l2_clip(self):
        noise_ops.seed_host_rng(0)
        data = [(0, "a", [30.0, 40.0])]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1, vector_size=2,
            vector_max_norm=10.0, vector_norm_kind=pdp.NormKind.L2)
        fused = run(JaxBackend(rng_seed=14), data, params,
                    public_partitions=["a"])
        np.testing.assert_allclose(fused["a"].vector_sum, [6.0, 8.0],
                                   atol=0.1)


class TestBoundsAlreadyEnforcedFused:

    def test_no_pid(self):
        noise_ops.seed_host_rng(0)
        data = [("a", 4.0)] * 100
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=10.0, contribution_bounds_already_enforced=True)
        ext = pdp.DataExtractors(partition_extractor=operator.itemgetter(0),
                                 value_extractor=operator.itemgetter(1))
        fused = run(JaxBackend(rng_seed=15), data, params, ext=ext)
        assert fused["a"].sum == pytest.approx(400.0, rel=0.01)


class TestFusedPercentile:

    def _percentile_params(self, ps, **kw):
        base = dict(metrics=[pdp.Metrics.PERCENTILE(p) for p in ps],
                    max_partitions_contributed=1,
                    max_contributions_per_partition=1, min_value=0.0,
                    max_value=100.0)
        base.update(kw)
        return pdp.AggregateParams(**base)

    def test_matches_local_oracle_at_big_eps(self):
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(1)
        data = [(u, "ab"[u % 2], float(v))
                for u, v in enumerate(rng.uniform(0, 100, 2000))]
        params = self._percentile_params([50, 90])
        local = run(pdp.LocalBackend(), data, params)
        fused = run(JaxBackend(rng_seed=16), data, params)
        assert set(local) == set(fused)
        # Both walks share a tie quirk: when a rank exactly equals a
        # cumulative integer count, the (negligible) noise decides whether
        # the walk stops at a child's right edge or continues into a
        # zero-count sibling — an RNG-dependent jump of up to one child
        # width, identical in kind on both planes but resolved by
        # different RNGs. Hence tolerance ~ level-2 child width, not leaf.
        for k in local:
            true = np.percentile([v for _, p, v in data if p == k],
                                 [50, 90])
            assert fused[k].percentile_50 == pytest.approx(
                local[k].percentile_50, abs=0.5)
            assert fused[k].percentile_90 == pytest.approx(
                local[k].percentile_90, abs=0.5)
            assert fused[k].percentile_50 == pytest.approx(true[0],
                                                           abs=0.5)
            assert fused[k].percentile_90 == pytest.approx(true[1],
                                                           abs=0.5)

    def test_compound_with_other_metrics_field_order(self):
        noise_ops.seed_host_rng(0)
        # 1001 rows: the median's rank (500.5) sits strictly inside the
        # run of 49s (cumulative counts 491 and 501), never on a count
        # boundary where the noise would pick between 49 and 50.
        data = [(u, "a", float(u % 100)) for u in range(1001)]
        params = self._percentile_params(
            [50], metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                           pdp.Metrics.PERCENTILE(50)])
        local = run(pdp.LocalBackend(), data, params)
        fused = run(JaxBackend(rng_seed=17), data, params)
        assert local["a"]._fields == fused["a"]._fields
        assert fused["a"].count == pytest.approx(local["a"].count, abs=0.5)
        assert fused["a"].percentile_50 == pytest.approx(
            local["a"].percentile_50, abs=0.2)
        assert fused["a"].percentile_50 == pytest.approx(49.0, abs=0.2)

    def test_degenerate_clip_range_rejected_at_params(self):
        """A zero-width clip range with percentiles fails at params
        construction with the cause named — not as a trace-time
        ZeroDivisionError or a ctor error deep in the pipeline."""
        with pytest.raises(ValueError, match="min_value < max_value"):
            self._percentile_params([50, 90], min_value=5.0,
                                    max_value=5.0)

    def test_tiny_clip_range_falls_back_to_host_path(self):
        """A valid but pathologically tiny range overflows the fused
        leaf constant in f32 — fusability must route it to the host
        path (f64), which still produces in-range percentiles."""
        from pipelinedp_tpu import jax_engine
        params = self._percentile_params([50], min_value=0.0,
                                         max_value=1e-35)
        assert not jax_engine.params_are_fusable(params)
        data = [(u, "a", 0.5e-35) for u in range(200)]
        fused = run(JaxBackend(rng_seed=29), data, params)
        assert 0.0 <= fused["a"].percentile_50 <= 1e-35

    def test_all_equal_values_hit_compaction_fallback(self):
        """Every row carries the same value, so every kept row lands in
        each walk's chosen subtree — the sub-histogram compaction prefix
        overflows and the lax.cond fallback (full-row scatters) must
        produce the same exact counts."""
        noise_ops.seed_host_rng(0)
        data = [(u, "ab"[u % 2], 42.0) for u in range(5000)]
        params = self._percentile_params([50, 90, 99])
        fused = run(JaxBackend(rng_seed=19), data, params)
        for k in ("a", "b"):
            # All mass at 42: every quantile lands within one leaf width
            # of it.
            assert fused[k].percentile_50 == pytest.approx(42.0, abs=0.1)
            assert fused[k].percentile_99 == pytest.approx(42.0, abs=0.1)

    def test_five_percentiles_cross_packed_group(self):
        """Q=5 exercises the second packed block-id word (4 ids per
        int32)."""
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(7)
        data = [(u, "a", float(v))
                for u, v in enumerate(rng.uniform(0, 100, 4000))]
        params = self._percentile_params([10, 25, 50, 75, 90])
        fused = run(JaxBackend(rng_seed=20), data, params)
        vals = [v for _, _, v in data]
        for p, name in [(10, "percentile_10"), (25, "percentile_25"),
                        (50, "percentile_50"), (75, "percentile_75"),
                        (90, "percentile_90")]:
            assert getattr(fused["a"], name) == pytest.approx(
                np.percentile(vals, p), abs=0.5)

    def test_monotone_across_quantiles_at_small_eps(self):
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(3)
        data = [(u, "a", float(v))
                for u, v in enumerate(rng.uniform(0, 100, 500))]
        params = self._percentile_params([90, 10, 50])
        fused = run(JaxBackend(rng_seed=18), data, params, eps=0.3,
                    delta=1e-6)
        t = fused["a"]
        assert t.percentile_10 <= t.percentile_50 <= t.percentile_90

    def test_deterministic_under_seed(self):
        data = [(u, "a", float(u % 50)) for u in range(300)]
        params = self._percentile_params([25, 75])
        outs = []
        for _ in range(2):
            noise_ops.seed_host_rng(0)
            outs.append(run(JaxBackend(rng_seed=19), data, params, eps=1.0,
                            delta=1e-6)["a"])
        assert outs[0] == outs[1]

    def test_sharded_matches_single_device(self):
        import jax
        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= 8
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(5)
        data = [(u, f"p{u % 3}", float(v))
                for u, v in enumerate(rng.uniform(0, 100, 3000))]
        params = self._percentile_params([50, 99])
        single = run(JaxBackend(rng_seed=20), data, params)
        sharded = run(JaxBackend(mesh=make_mesh(8), rng_seed=20), data,
                      params)
        assert set(single) == set(sharded)
        for k in single:
            assert sharded[k].percentile_50 == pytest.approx(
                single[k].percentile_50, abs=0.5)
            assert sharded[k].percentile_99 == pytest.approx(
                single[k].percentile_99, abs=1.0)


class TestFallbacks:

    def test_noise_actually_added_at_small_eps(self):
        # Two different seeds must give different noisy outputs.
        data = [(u, "a", 1.0) for u in range(2000)]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1)
        outs = []
        for seed in (20, 21):
            noise_ops.seed_host_rng(0)
            fused = run(JaxBackend(rng_seed=seed), data, params, eps=0.5,
                        delta=1e-6)
            outs.append(fused["a"].count)
        assert outs[0] != outs[1]
        # But both near the true count.
        for o in outs:
            assert o == pytest.approx(2000, rel=0.05)


class TestShardedMultiChip:
    """The multi-chip path on the virtual 8-device CPU mesh."""

    def _mesh(self):
        import jax
        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= 8, (
            "conftest must provide 8 virtual devices")
        return make_mesh(8)

    def test_matches_single_device(self):
        noise_ops.seed_host_rng(0)
        data = [(u, f"p{u % 5}", 3.0) for u in range(500)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=10.0)
        single = run(JaxBackend(rng_seed=30), data, params)
        sharded = run(JaxBackend(mesh=self._mesh(), rng_seed=30), data,
                      params)
        assert set(single) == set(sharded)
        for k in single:
            assert sharded[k].count == pytest.approx(single[k].count,
                                                     abs=0.5)
            assert sharded[k].sum == pytest.approx(single[k].sum,
                                                   rel=0.01)

    def test_bounding_across_shards(self):
        noise_ops.seed_host_rng(0)
        # Users contribute to 10 partitions, L0=2: bounding must hold
        # globally even though rows are sharded by pid.
        pks = [f"p{i}" for i in range(10)]
        data = [(u, pk, 1.0) for u in range(160) for pk in pks]
        params = count_params(max_partitions_contributed=2,
                              max_contributions_per_partition=1)
        sharded = run(JaxBackend(mesh=self._mesh(), rng_seed=31), data,
                      params, public_partitions=pks)
        total = sum(v.count for v in sharded.values())
        assert total == pytest.approx(320, rel=0.1)

    def test_selection_on_mesh(self):
        noise_ops.seed_host_rng(0)
        data = [(u, "big", 1.0) for u in range(1000)] + [(5000, "tiny",
                                                          1.0)]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1)
        sharded = run(JaxBackend(mesh=self._mesh(), rng_seed=32), data,
                      params, eps=1.0, delta=1e-6)
        assert "big" in sharded
        assert "tiny" not in sharded


class TestEnforcedBoundsSelectionEstimate:

    def test_rows_divided_by_max_rows_per_user(self):
        # Privacy regression (user-count estimate): with
        # contribution_bounds_already_enforced and linf=5, a partition with
        # 5 rows could be ONE user — selection must see ceil(5/5)=1 user
        # and (almost) never keep it, even though 5 users would often pass.
        noise_ops.seed_host_rng(0)
        data = [("solo", 1.0)] * 5
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], max_partitions_contributed=1,
            max_contributions_per_partition=5,
            contribution_bounds_already_enforced=True)
        ext = pdp.DataExtractors(partition_extractor=operator.itemgetter(0),
                                 value_extractor=operator.itemgetter(1))
        kept = 0
        for seed in range(40):
            fused = run(JaxBackend(rng_seed=100 + seed), data, params,
                        eps=1.0, delta=1e-4, ext=ext)
            kept += "solo" in fused
        # P(keep | 1 user) <= delta = 1e-4: 40 trials should keep ~0.
        assert kept == 0


class TestShardedMultiChipBroad:
    """VARIANCE, VECTOR_SUM, per-partition-bound SUM and
    public partitions on the 8-device mesh, each pinned to the
    single-device output."""

    def _mesh(self):
        import jax
        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= 8
        return make_mesh(8)

    def _both(self, data, params, seed, public=None):
        noise_ops.seed_host_rng(0)
        single = run(JaxBackend(rng_seed=seed), data, params,
                     public_partitions=public)
        noise_ops.seed_host_rng(0)
        sharded = run(JaxBackend(mesh=self._mesh(), rng_seed=seed), data,
                      params, public_partitions=public)
        assert set(single) == set(sharded)
        return single, sharded

    def test_variance_on_mesh(self):
        rng = np.random.default_rng(7)
        data = [(u, f"p{u % 4}", float(v))
                for u, v in enumerate(rng.uniform(0, 10, 2000))]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VARIANCE, pdp.Metrics.MEAN,
                     pdp.Metrics.COUNT],
            max_partitions_contributed=2,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=10.0)
        single, sharded = self._both(data, params, seed=41)
        for k in single:
            assert sharded[k].count == pytest.approx(single[k].count,
                                                     rel=0.02)
            assert sharded[k].mean == pytest.approx(single[k].mean,
                                                    abs=0.3)
            assert sharded[k].variance == pytest.approx(
                single[k].variance, rel=0.2, abs=0.5)

    def test_vector_sum_on_mesh(self):
        rng = np.random.default_rng(8)
        data = [(u, f"p{u % 3}", rng.uniform(-1, 1, 4))
                for u in range(600)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            vector_size=4, vector_max_norm=5.0,
            vector_norm_kind=pdp.NormKind.L2)
        single, sharded = self._both(data, params, seed=42)
        for k in single:
            np.testing.assert_allclose(sharded[k].vector_sum,
                                       single[k].vector_sum, atol=1.0)

    def test_per_partition_bound_sum_on_mesh(self):
        # Each user's per-partition sum is 30, clipped to 10: the clip
        # happens per (pid, pk) segment and must survive sharding.
        data = [(u, f"p{u % 2}", 10.0) for u in range(200) for _ in range(3)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=5,
            min_sum_per_partition=0.0, max_sum_per_partition=10.0)
        single, sharded = self._both(data, params, seed=43)
        for k in single:
            assert single[k].sum == pytest.approx(1000.0, rel=0.02)
            assert sharded[k].sum == pytest.approx(single[k].sum,
                                                   rel=0.02)

    def test_public_partitions_on_mesh(self):
        data = [(u, f"p{u % 3}", 1.0) for u in range(300)]
        public = ["p0", "p1", "p2", "p_empty"]
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1)
        single, sharded = self._both(data, params, seed=44, public=public)
        assert sorted(sharded) == sorted(public)
        for k in public:
            assert sharded[k].count == pytest.approx(single[k].count,
                                                     abs=0.5)
        assert sharded["p_empty"].count == pytest.approx(0.0, abs=0.5)

    def test_max_contributions_on_mesh(self):
        # Total-cap bounding on the mesh: per-pid sampling is shard-local
        # (a pid's rows live on one shard), so sharded == single-device
        # up to the independent sample draw; with a non-binding cap both
        # equal the raw aggregates.
        data = [(u, f"p{i}", 2.0) for u in range(60) for i in range(3)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_contributions=5, min_value=0.0, max_value=10.0)
        single, sharded = self._both(data, params, seed=46,
                                     public=[f"p{i}" for i in range(3)])
        for k in single:
            assert sharded[k].count == pytest.approx(single[k].count,
                                                     abs=0.1)
            assert sharded[k].sum == pytest.approx(single[k].sum, abs=0.5)
            assert single[k].count == pytest.approx(60, abs=0.1)

    def test_uneven_shard_load(self):
        # One privacy id owns half the rows: hashing must still place all
        # its rows on one shard and results must match single-device.
        data = ([(0, "hot", 1.0)] * 500 +
                [(u, f"p{u % 4}", 1.0) for u in range(1, 401)])
        params = count_params(max_partitions_contributed=2,
                              max_contributions_per_partition=600)
        single, sharded = self._both(data, params, seed=45)
        for k in single:
            assert sharded[k].count == pytest.approx(single[k].count,
                                                     rel=0.05)


class TestFusedSelectPartitions:
    """select_partitions on the fused plane vs the host graph."""

    def _run(self, backend, data, l0=2, eps=BIG_EPS, delta=1e-2,
             pre_threshold=None):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=delta)
        engine = pdp.DPEngine(acc, backend)
        ex = pdp.DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1))
        params = pdp.SelectPartitionsParams(
            max_partitions_contributed=l0, pre_threshold=pre_threshold)
        result = engine.select_partitions(data, params, ex)
        acc.compute_budgets()
        return sorted(result)

    def test_matches_local_at_huge_eps(self):
        noise_ops.seed_host_rng(0)
        data = [(u, f"p{u % 4}") for u in range(400)]
        local = self._run(pdp.LocalBackend(), data)
        fused = self._run(JaxBackend(rng_seed=50), data)
        assert local == fused == ["p0", "p1", "p2", "p3"]

    @pytest.mark.parametrize("seed", range(60, 66))
    def test_fuzz_populated_partitions_kept_on_both_planes(self, seed):
        # Random shapes: every partition with >= 40 distinct users must
        # be kept by both planes at huge eps; 1-user partitions must be
        # dropped by both at tiny delta.
        rng = np.random.default_rng(seed)
        n_parts = int(rng.integers(3, 12))
        data = []
        big = set()
        uid = 0
        for p in range(n_parts):
            # The last partition is always a singleton so the must-drop
            # branch below is exercised for every seed.
            users = 1 if p == n_parts - 1 else int(rng.integers(2, 80))
            if users >= 40:
                big.add(f"p{p}")
            for _ in range(users):
                data.append((uid, f"p{p}"))
                uid += 1
        lone = [f"p{n_parts - 1}"]
        noise_ops.seed_host_rng(seed)
        local = set(self._run(pdp.LocalBackend(), data, l0=n_parts,
                              delta=1e-6))
        fused = set(self._run(JaxBackend(rng_seed=seed), data, l0=n_parts,
                              delta=1e-6))
        for k in big:
            assert k in local and k in fused, (seed, k, local, fused)
        for k in lone:
            assert k not in local and k not in fused, (seed, k)

    def test_small_partition_dropped(self):
        data = [(u, "big") for u in range(2000)] + [(9999, "tiny")]
        fused = self._run(JaxBackend(rng_seed=51), data, eps=1.0,
                          delta=1e-6)
        assert "big" in fused and "tiny" not in fused

    def test_l0_bounding_limits_contributions(self):
        # One user in 50 partitions with l0=1: at most 1 partition sees a
        # contribution, so at huge eps at most 1 partition survives a
        # selection that needs >= 1 user.
        data = [(0, f"p{i}") for i in range(50)]
        fused = self._run(JaxBackend(rng_seed=52), data, l0=1)
        assert len(fused) <= 1

    def test_pre_threshold(self):
        data = [(u, "mid") for u in range(30)]
        kept = self._run(JaxBackend(rng_seed=53), data, l0=1,
                         pre_threshold=100)
        assert kept == []  # 30 users < pre_threshold 100

    def test_on_mesh(self):
        from pipelinedp_tpu.parallel import make_mesh
        noise_ops.seed_host_rng(0)
        data = [(u, f"p{u % 3}") for u in range(300)]
        fused = self._run(JaxBackend(mesh=make_mesh(8), rng_seed=54),
                          data)
        assert fused == ["p0", "p1", "p2"]

    def test_duplicate_contributions_counted_once(self):
        # A pid contributing many rows to one partition counts once.
        data = [(0, "a")] * 100 + [(1, "a")] * 100
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=55))
        ex = pdp.DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1))
        result = engine.select_partitions(
            data, pdp.SelectPartitionsParams(max_partitions_contributed=1),
            ex)
        acc.compute_budgets()
        # 2 distinct users: with delta=1e-6 a 2-user partition is
        # (nearly) never kept; 200 rows must not inflate the count.
        assert list(result) == []


class TestFusedSelectMore:
    """Extra fused select_partitions coverage: columnar input, all
    strategies, report stages."""

    def _select(self, data, l0=4, eps=BIG_EPS, delta=1e-2, strategy=None,
                seed=60, ex=None):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=delta)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed))
        kw = dict(max_partitions_contributed=l0)
        if strategy is not None:
            kw["partition_selection_strategy"] = strategy
        result = engine.select_partitions(
            data, pdp.SelectPartitionsParams(**kw),
            ex or pdp.DataExtractors(
                privacy_id_extractor=operator.itemgetter(0),
                partition_extractor=operator.itemgetter(1)))
        acc.compute_budgets()
        return sorted(result), engine

    def test_array_dataset_input(self):
        # pid stride 101 is coprime to the 4 partitions: users genuinely
        # span partitions, exercising columnar cross-partition bounding.
        ds = pdp.ArrayDataset(privacy_ids=np.arange(500) % 101,
                              partition_keys=np.arange(500) % 4)
        kept, _ = self._select(ds, ex=pdp.DataExtractors())
        assert kept == [0, 1, 2, 3]

    @pytest.mark.parametrize("strategy", list(
        pdp.PartitionSelectionStrategy))
    def test_all_strategies(self, strategy):
        data = [(u, "only") for u in range(500)]
        kept, engine = self._select(data, l0=1, eps=1.0, delta=1e-6,
                                    strategy=strategy, seed=61)
        assert kept == ["only"]
        # The configured strategy must actually reach the fused plane.
        report = engine.explain_computations_report()[0]
        assert f"using {strategy.value}" in report

    def test_report_stages(self):
        data = [(u, "a") for u in range(10)]
        kept, engine = self._select(data, l0=2, eps=1.0, delta=1e-6,
                                    seed=62)
        report = engine.explain_computations_report()[0]
        assert "Cross-partition contribution bounding" in report
        assert "Private Partition selection" in report
        assert "eps=" in report


class TestPartitionAxisSharding:
    """The pk axis is sharded over the mesh — per-device
    accumulator state is O(P/n_dev) (owner blocks via psum_scatter), and
    owner-mode selection reproduces the single-chip decisions
    bit-for-bit."""

    def _mesh(self, n=8):
        import jax
        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= n
        return make_mesh(n)

    def test_outputs_are_partition_sharded(self):
        # The returned accumulator arrays must be sharded over the mesh:
        # every device holds exactly its P/n_dev owner block, not a
        # replica of the full axis.
        import jax
        from pipelinedp_tpu import jax_engine as je
        from pipelinedp_tpu.parallel import sharded_fused_aggregate

        mesh = self._mesh()
        P = 1 << 12
        rng = np.random.default_rng(0)
        n = 4096
        pid = rng.integers(0, 500, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        config = je.FusedConfig.from_params(count_params(), public=False)
        keep_table, thr, s_scale, min_count = je.selection_inputs(
            config, 1.0, 1e-6, None)
        keep, out = sharded_fused_aggregate(
            mesh, config, P, pid, pk, None, np.zeros(0, np.float32),
            keep_table, thr, s_scale, min_count, 1.0,
            jax.random.PRNGKey(0))
        for arr in [keep] + list(out.values()):
            shard_shapes = {s.data.shape for s in arr.addressable_shards}
            assert shard_shapes == {(P // 8,)}, (
                f"expected owner blocks of {P // 8}, got {shard_shapes}")

    def test_selection_bit_parity_with_single_chip(self):
        # Same seed, bounding that never binds => the mesh's selection
        # decisions (drawn globally, sliced per owner) must EQUAL the
        # single-chip ones, and the int count accumulators exactly too.
        noise_ops.seed_host_rng(0)
        rng = np.random.default_rng(3)
        data = [(u, f"p{rng.integers(0, 200)}", 1.0) for u in range(3000)]
        params = count_params(max_partitions_contributed=64,
                              max_contributions_per_partition=8)
        single = run(JaxBackend(rng_seed=77), data, params, eps=1.0,
                     delta=1e-6)
        noise_ops.seed_host_rng(0)
        sharded = run(JaxBackend(mesh=self._mesh(), rng_seed=77), data,
                      params, eps=1.0, delta=1e-6)
        assert set(single) == set(sharded)

    def test_large_partition_axis_on_mesh(self):
        # A pk axis of 2^20 partitions: per-device owner blocks are 2^17
        # — the dense axis never materializes replicated per device
        # (pre-r3 the full 2^20-vector was psum'd to every chip).
        import jax
        from pipelinedp_tpu import jax_engine as je
        from pipelinedp_tpu.parallel import sharded_fused_aggregate

        mesh = self._mesh()
        P = 1 << 20
        rng = np.random.default_rng(1)
        n = 1 << 15
        pid = rng.integers(0, 2000, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        config = je.FusedConfig.from_params(
            count_params(max_partitions_contributed=1 << 20,
                         max_contributions_per_partition=8), public=False)
        keep_table, thr, s_scale, min_count = je.selection_inputs(
            config, BIG_EPS, 1e-6, None)
        keep, out = sharded_fused_aggregate(
            mesh, config, P, pid, pk, None, np.zeros(0, np.float32),
            keep_table, thr, s_scale, min_count, 1.0,
            jax.random.PRNGKey(5))
        assert {s.data.shape for s in out["count"].addressable_shards
                } == {(P // 8,)}
        counts = np.asarray(out["count"])
        expected = np.bincount(pk, minlength=P)
        np.testing.assert_array_equal(counts, expected)

    def test_reshard_fallback_is_bit_identical(self, monkeypatch):
        # The host re-shard runs as a native counting scatter, or as
        # numpy's radix grouping where the library cannot load: the
        # same inputs give the same selection and accumulators both
        # ways, and the ledger counts which path ran.
        import jax
        from pipelinedp_tpu import jax_engine as je
        from pipelinedp_tpu import native, obs
        from pipelinedp_tpu.parallel import sharded_fused_aggregate

        mesh = self._mesh()
        P = 1 << 10
        rng = np.random.default_rng(4)
        n = 20_000
        pid = rng.integers(0, 3000, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        values = rng.uniform(0, 5, n).astype(np.float32)
        config = je.FusedConfig.from_params(
            pdp.AggregateParams(metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                                max_partitions_contributed=4,
                                max_contributions_per_partition=2,
                                min_value=0.0, max_value=5.0),
            public=False)
        keep_table, thr, s_scale, min_count = je.selection_inputs(
            config, 3.0, 1e-6, None)

        def release():
            keep, out = sharded_fused_aggregate(
                mesh, config, P, pid, pk, values, np.zeros(0, np.float32),
                keep_table, thr, s_scale, min_count, 1.0,
                jax.random.PRNGKey(9))
            return np.asarray(keep), {k: np.asarray(v)
                                      for k, v in out.items()}

        def counters():
            c = obs.ledger().snapshot()["counters"]
            return c.get("reshard.native", 0), c.get("reshard.fallback", 0)

        def unavailable():
            raise native.NativeUnavailableError("no toolchain")

        # Without g++ the first release falls back too.
        try:
            native._shard_lib()
            first = (1, 0)
        except native.NativeUnavailableError:
            first = (0, 1)
        n0, f0 = counters()
        keep_first, out_first = release()
        assert counters() == (n0 + first[0], f0 + first[1])
        monkeypatch.setattr(native, "_shard_lib", unavailable)
        keep_numpy, out_numpy = release()
        assert counters() == (n0 + first[0], f0 + first[1] + 1)
        assert 0 < keep_first.sum() < P
        np.testing.assert_array_equal(keep_first, keep_numpy)
        assert out_first.keys() == out_numpy.keys()
        for k in out_first:
            np.testing.assert_array_equal(out_first[k], out_numpy[k])


class TestFixedPointAccumulation:
    """Value partials accumulate as exact
    fixed-point int32 lanes on device (``_fixedpoint_layout``), leaving
    only the per-row quantization error (bound/2^23, independent of
    partition size). A partition of ~10^7 identical values is where a
    monolithic f32 segment_sum provably drifts (f32 addition of 1.0
    saturates outright at 2^24 = 16777216); the fused release must match
    the float64 oracle bit-close."""

    def test_huge_identical_partition_sum(self):
        import jax
        import jax.numpy as jnp

        n = 1 << 23  # 8.4M rows, one partition — past f32 saturation
        vals = jnp.ones(n, jnp.float32) * 1.5
        ids = jnp.zeros(n, jnp.int32)
        # The monolithic f32 segment_sum demonstrably drifts here...
        plain = float(np.asarray(jax.ops.segment_sum(vals, ids, 4))[0])
        assert abs(plain - 1.5 * n) > 1000
        # ...while the fused engine's release is quantization-accurate.
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n) % (1 << 20),
                              partition_keys=np.zeros(n, np.int64),
                              values=np.full(n, 1.5))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=8,
            max_contributions_per_partition=8, min_value=0.0,
            max_value=10.0)
        fused = run(JaxBackend(rng_seed=0), ds, params,
                    ext=pdp.DataExtractors())
        assert fused[0].sum == pytest.approx(1.5 * n, rel=1e-6)

    def test_fused_mean_variance_at_scale_matches_oracle(self):
        # End-to-end: one hot partition with 2^21 rows of the same value;
        # huge eps so noise vanishes. The f64 oracle mean is exactly the
        # value and the variance 0 — pre-compensation the fused f32
        # accumulation drifted both.
        n = 1 << 21
        rng = np.random.default_rng(0)
        ds = pdp.ArrayDataset(
            privacy_ids=np.arange(n) % (1 << 20),
            partition_keys=np.zeros(n, np.int64),
            values=np.full(n, 7.25, np.float64))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.MEAN, pdp.Metrics.VARIANCE,
                     pdp.Metrics.SUM],
            max_partitions_contributed=8,
            max_contributions_per_partition=8,
            min_value=0.0, max_value=10.0)
        fused = run(JaxBackend(rng_seed=0), ds, params,
                    ext=pdp.DataExtractors())
        got = fused[0]
        assert got.sum == pytest.approx(7.25 * n, rel=1e-7)
        assert got.mean == pytest.approx(7.25, abs=1e-6)
        assert got.variance == pytest.approx(0.0, abs=1e-4)


class TestAdaptiveLanePlan:
    """The fixed-point lane width adapts to the global row count: small
    datasets ride 2 wide lanes, huge ones 6 narrow lanes; every plan's
    int32 lane accumulators stay exact (n * (2^bits - 1) < 2^31)."""

    @pytest.mark.parametrize("n,bits,lanes", [
        (1 << 10, 12, 2), (1 << 19, 12, 2), (1 << 20, 11, 3),
        (1 << 23, 8, 3), (1 << 24, 7, 4), (1 << 26, 5, 5),
        (1 << 27, 4, 6),
    ])
    def test_plan(self, n, bits, lanes):
        from pipelinedp_tpu import jax_engine as je
        got_bits, got_lanes = je._fx_plan(n)
        assert (got_bits, got_lanes) == (bits, lanes)
        assert n * ((1 << got_bits) - 1) < (1 << 31)

    def test_beyond_capacity_raises(self):
        from pipelinedp_tpu import jax_engine as je
        with pytest.raises(NotImplementedError, match="2\\^27"):
            je._fx_plan(1 << 28)

    def test_no_value_columns_skip_the_plan(self, monkeypatch):
        """COUNT/PRIVACY_ID_COUNT-only pipelines use no fixed-point
        lanes, so the lane-capacity plan (and its row cap) must never
        run for them — counts are exact int32 to 2^31 rows."""
        from pipelinedp_tpu import jax_engine as je

        def boom(n):
            raise AssertionError("_fx_plan must not run for count-only")

        monkeypatch.setattr(je, "_fx_plan", boom)
        ds = pdp.ArrayDataset(privacy_ids=np.arange(100) % 10,
                              partition_keys=np.arange(100) % 5,
                              values=None)
        params = count_params(max_partitions_contributed=2,
                              max_contributions_per_partition=2)
        fused = run(JaxBackend(rng_seed=0), ds, params, eps=1e6,
                    delta=1e-2, ext=pdp.DataExtractors())
        assert len(fused) == 5


class TestCompactFetchFallback:
    """Private selection keeping more partitions than the packed-fetch
    cap (8192) must fall back to the full fetch and still release every
    kept partition."""

    def test_many_kept_partitions(self):
        n_parts = 10_000
        users_per = 3
        pid = np.arange(n_parts * users_per)  # every row its own user
        pk = np.repeat(np.arange(n_parts), users_per)
        ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                              values=None)
        params = count_params(max_partitions_contributed=1,
                              max_contributions_per_partition=1)
        fused = run(JaxBackend(rng_seed=0), ds, params, eps=1e6,
                    delta=1e-2, ext=pdp.DataExtractors())
        # With eps huge every 3-user partition passes selection.
        assert len(fused) == n_parts
        assert fused[0].count == pytest.approx(3, abs=0.3)
        assert fused[n_parts - 1].count == pytest.approx(3, abs=0.3)

    def test_past_cap_release_matches_streamed(self, monkeypatch):
        """Past the cap the single-batch release still draws host noise
        over the kept rows only, in ascending pk order: a streamed run
        with the same seed releases the same values bit for bit."""
        from pipelinedp_tpu import jax_engine as je
        monkeypatch.setattr(je, "_COMPACT_FETCH_CAP", 8)
        rng = np.random.default_rng(5)
        n = 3_000
        # One row per privacy id: bounding drops nothing on either path.
        # Partitions 0..39 hold ~75 ids each and are kept; every
        # partition from 40 up holds one id and is dropped, so the kept
        # rows are not a prefix of the pk axis.
        pk = np.concatenate([rng.integers(0, 40, n - 20),
                             40 + np.arange(20)])
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n),
                              partition_keys=pk,
                              values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VARIANCE, pdp.Metrics.PERCENTILE(90)],
            max_partitions_contributed=3,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=10.0)
        single = run(JaxBackend(rng_seed=3), ds, params, eps=1e3,
                     delta=1e-6, ext=pdp.DataExtractors())
        assert 8 < len(single) < 60  # past the (shrunken) cap
        ds.invalidate_cache()
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "1000")
        streamed = run(JaxBackend(rng_seed=3), ds, params, eps=1e3,
                       delta=1e-6, ext=pdp.DataExtractors())
        assert streamed == single


class TestLanePlanBoundary:
    """End-to-end coverage of the non-default lane plans: row counts just
    past a plan boundary switch the kernel to narrower lanes, whose
    released sums must still match the exact float64 oracle within the
    quantization bound (n * bound / 2^23)."""

    @pytest.mark.parametrize("n", [(1 << 19) - 8, 525_000])
    def test_sum_across_plan_boundary(self, n):
        from pipelinedp_tpu import jax_engine as je
        bits, lanes = je._fx_plan(n)
        assert (bits, lanes) == ((12, 2) if n < 524_417 else (11, 3))
        rng = np.random.default_rng(n)
        vals = rng.uniform(0.0, 10.0, n)
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n) % (1 << 18),
                              partition_keys=np.zeros(n, np.int64),
                              values=vals)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=4,
            max_contributions_per_partition=4, min_value=0.0,
            max_value=10.0)
        fused = run(JaxBackend(rng_seed=0), ds, params, eps=1e12,
                    delta=1e-2, ext=pdp.DataExtractors())
        exact = float(np.sum(vals))
        # Quantization bound: every row rounds on a bound/2^23 grid (the
        # inputs also pass through float32 encode, same error scale).
        bound = n * (10.0 / (1 << 23)) + n * 10.0 * 2**-24 + 1.0
        assert abs(fused[0].sum - exact) < bound
