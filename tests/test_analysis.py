"""Tests for the utility-analysis package — error models vs closed-form
expectations, Poisson-binomial exactness, histograms, the full sweep and
tuning E2E (mirrors the reference's ``analysis/tests/`` strategy)."""

import math
import operator

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import analysis
from pipelinedp_tpu.analysis import combiners as ua_combiners
from pipelinedp_tpu.analysis import data_structures, histograms, metrics
from pipelinedp_tpu.analysis import poisson_binomial
from pipelinedp_tpu.budget_accounting import MechanismSpec
from pipelinedp_tpu.combiners import CombinerParams
from pipelinedp_tpu.aggregate_params import MechanismType


def extractors():
    return pdp.DataExtractors(privacy_id_extractor=operator.itemgetter(0),
                              partition_extractor=operator.itemgetter(1),
                              value_extractor=operator.itemgetter(2))


def count_params(l0=1, linf=1, **kw):
    base = dict(metrics=[pdp.Metrics.COUNT], max_partitions_contributed=l0,
                max_contributions_per_partition=linf)
    base.update(kw)
    return pdp.AggregateParams(**base)


class TestPoissonBinomial:

    def test_exact_pmf_matches_binomial(self):
        # All p equal -> binomial distribution.
        from scipy.stats import binom
        p = 0.3
        pmf = poisson_binomial.compute_pmf([p] * 10)
        expected = binom.pmf(np.arange(11), 10, p)
        np.testing.assert_allclose(pmf.probabilities, expected, atol=1e-12)

    def test_approximation_close_to_exact(self):
        rng = np.random.default_rng(0)
        probs = rng.uniform(0.2, 0.8, 200).tolist()
        exact = poisson_binomial.compute_pmf(probs)
        exp, std, skew = poisson_binomial.compute_exp_std_skewness(probs)
        approx = poisson_binomial.compute_pmf_approximation(
            exp, std, skew, len(probs))
        # Compare a central slice of the distributions.
        for v in range(int(exp - std), int(exp + std)):
            pe = exact.probabilities[v - exact.start]
            pa = approx.probabilities[v - approx.start]
            assert pe == pytest.approx(pa, abs=2e-3)

    def test_zero_sigma(self):
        pmf = poisson_binomial.compute_pmf_approximation(5.0, 0.0, 0.0, 10)
        assert pmf.start == 5
        assert pmf.probabilities.tolist() == [1.0]


class TestProbabilityComputations:
    """MC quantiles of the Laplace+Gaussian convolution vs analytically
    computed expectations (reference
    ``analysis/tests/probability_computations_test.py``: the expected
    values there are derived analytically to 1e-10; the distribution is
    symmetric, so q and 1-q must be mirror images)."""

    @pytest.mark.parametrize("b,sigma,qs,expected", [
        (1.0, 2.0, [0.1, 0.5, 0.9], [-3.0874, 0.0, 3.0874]),
        (1.01, 0.55, [0.5, 0.7, 0.9, 0.99],
         [0.0, 0.63892, 1.77515, 4.10093]),
    ])
    def test_quantiles_match_analytic(self, b, sigma, qs, expected):
        from pipelinedp_tpu.analysis import probability_computations as pc
        got = pc.compute_sum_laplace_gaussian_quantiles(
            b, sigma, qs, 4 * 10**6, rng=np.random.default_rng(0))
        np.testing.assert_allclose(got, expected, atol=0.02)

    def test_symmetry(self):
        from pipelinedp_tpu.analysis import probability_computations as pc
        got = pc.compute_sum_laplace_gaussian_quantiles(
            2.0, 1.0, [0.05, 0.25, 0.75, 0.95], 10**6,
            rng=np.random.default_rng(1))
        assert got[0] == pytest.approx(-got[3], abs=0.05)
        assert got[1] == pytest.approx(-got[2], abs=0.05)

    def test_batch_matches_scalar(self):
        from pipelinedp_tpu.analysis import probability_computations as pc
        qs = [0.1, 0.5, 0.9]
        batch = pc.compute_sum_laplace_gaussian_quantiles_batch(
            np.array([1.0, 3.0]), np.array([2.0, 0.5]), qs, 10**6,
            rng=np.random.default_rng(2))
        for i, (b, s) in enumerate([(1.0, 2.0), (3.0, 0.5)]):
            scalar = pc.compute_sum_laplace_gaussian_quantiles(
                b, s, qs, 10**6, rng=np.random.default_rng(3))
            np.testing.assert_allclose(batch[i], scalar, atol=0.05)


class TestAnalysisContributionBounders:
    """The analysis bounders record, not enforce (reference
    ``analysis/tests/contribution_bounders_test.py``)."""

    def _bound(self, rows, prob=1.0):
        from pipelinedp_tpu.analysis.contribution_bounders import (
            SamplingL0LinfContributionBounder)
        backend = pdp.LocalBackend()
        out = SamplingL0LinfContributionBounder(prob).bound_contributions(
            rows, count_params(), backend, None, lambda x: x)
        return dict(out)

    def test_emits_count_sum_npartitions_per_pid_pk(self):
        rows = [("u1", "a", 1.0), ("u1", "a", 2.0), ("u1", "b", 5.0),
                ("u2", "a", 7.0)]
        got = self._bound(rows)
        # No bounding happens regardless of tiny caps in params.
        assert got[("u1", "a")] == (2, 3.0, 2)
        assert got[("u1", "b")] == (1, 5.0, 2)
        assert got[("u2", "a")] == (1, 7.0, 1)

    def test_n_partitions_counts_pre_sampling_partitions(self):
        # Partition sampling drops partitions deterministically but
        # n_partitions still reflects the privacy id's full spread.
        rows = [("u1", pk, 1.0) for pk in range(200)]
        got = self._bound(rows, prob=0.5)
        assert 0 < len(got) < 200  # some partitions sampled away
        assert all(v == (1, 1.0, 200) for v in got.values())
        # Deterministic: same keys kept on a second run.
        assert got == self._bound(rows, prob=0.5)

    def test_noop_bounder_preaggregated(self):
        from pipelinedp_tpu.analysis.contribution_bounders import (
            NoOpContributionBounder)
        rows = [("a", (2, 3.0, 4)), ("b", (1, 1.0, 4))]
        out = dict(NoOpContributionBounder().bound_contributions(
            rows, count_params(), pdp.LocalBackend(), None, lambda x: x))
        assert out == {(None, "a"): (2, 3.0, 4), (None, "b"): (1, 1.0, 4)}


class TestMultiParameterConfiguration:

    def test_validation(self):
        with pytest.raises(ValueError, match="at least 1"):
            data_structures.MultiParameterConfiguration()
        with pytest.raises(ValueError, match="same length"):
            data_structures.MultiParameterConfiguration(
                max_partitions_contributed=[1, 2],
                max_contributions_per_partition=[1])

    def test_get_aggregate_params(self):
        mpc = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2],
            max_contributions_per_partition=[10, 11])
        base = count_params(l0=5, linf=5)
        p0 = mpc.get_aggregate_params(base, 0)
        p1 = mpc.get_aggregate_params(base, 1)
        assert (p0.max_partitions_contributed,
                p0.max_contributions_per_partition) == (1, 10)
        assert (p1.max_partitions_contributed,
                p1.max_contributions_per_partition) == (2, 11)


class TestAnalysisCombiners:

    def _params(self, agg_params, eps=1.0, delta=1e-6):
        spec = MechanismSpec(MechanismType.LAPLACE, _eps=eps, _delta=delta)
        return CombinerParams(spec, agg_params)

    def test_count_combiner_error_model(self):
        # One user contributes 5 rows, linf=3 -> linf error = -2;
        # n_partitions=2, l0=1 -> keep prob 0.5 ->
        # expected l0 error = -3*0.5, var = 9*0.25.
        params = self._params(count_params(l0=1, linf=3))
        c = ua_combiners.CountCombiner(params)
        acc = c.create_accumulator(
            (np.array([5]), np.array([0.0]), np.array([2])))
        m = c.compute_metrics(acc)
        assert m.sum == 5
        assert m.per_partition_error_max == -2
        assert m.expected_cross_partition_error == pytest.approx(-1.5)
        assert m.std_cross_partition_error == pytest.approx(1.5)
        assert m.std_noise > 0

    def test_privacy_id_count_combiner(self):
        params = self._params(count_params(l0=2, linf=1))
        c = ua_combiners.PrivacyIdCountCombiner(params)
        acc = c.create_accumulator(
            (np.array([7, 0]), np.array([0.0, 0.0]), np.array([4, 4])))
        m = c.compute_metrics(acc)
        assert m.sum == 1.0  # only one user has counts > 0
        assert m.expected_cross_partition_error == pytest.approx(-0.5)

    def test_sum_combiner_clipping_errors(self):
        agg = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_sum_per_partition=0.0, max_sum_per_partition=10.0)
        c = ua_combiners.SumCombiner(self._params(agg))
        acc = c.create_accumulator(
            (None, np.array([15.0, -5.0]), np.array([1, 1])))
        m = c.compute_metrics(acc)
        assert m.sum == 10.0
        assert m.per_partition_error_max == -5.0  # 15 clipped to 10
        assert m.per_partition_error_min == 5.0  # -5 clipped to 0

    def test_partition_selection_combiner_probability(self):
        params = self._params(count_params(l0=1, linf=1), eps=1.0,
                              delta=1e-5)
        c = ua_combiners.PartitionSelectionCombiner(params)
        # 200 users each contributing to this partition only -> all keep
        # probability 1 -> partition almost surely kept.
        acc = c.create_accumulator(
            (np.ones(200), np.zeros(200), np.ones(200)))
        prob = c.compute_metrics(acc)
        assert prob == pytest.approx(1.0, abs=1e-3)

    def test_sparse_to_dense_switch(self):
        params = self._params(count_params())
        compound = ua_combiners.CompoundCombiner(
            [ua_combiners.CountCombiner(params)], return_named_tuple=False)
        acc = compound.create_accumulator((1, 1.0, 1))
        # Merge many: should flip to dense (2 * 1 combiner = 2 max sparse).
        for _ in range(5):
            acc = compound.merge_accumulators(
                acc, compound.create_accumulator((1, 1.0, 1)))
        sparse, dense = acc
        assert sparse is None
        assert dense is not None

    def test_moments_merge_beyond_cap(self):
        probs = [0.5] * (ua_combiners.MAX_PROBABILITIES_IN_ACCUMULATOR + 1)
        acc1 = (probs[:60], None)
        acc2 = (probs[:60], None)
        merged = ua_combiners._merge_partition_selection_accumulators(
            acc1, acc2)
        assert merged[0] is None
        assert merged[1].count == 120
        assert merged[1].expectation == pytest.approx(60.0)


class TestHistograms:

    def test_bin_lower(self):
        assert histograms._to_bin_lower(123) == 123
        assert histograms._to_bin_lower(1234) == 1230
        assert histograms._to_bin_lower(12345) == 12300

    def test_dataset_histograms(self):
        # 3 users: u0 -> 2 partitions (1 row each); u1 -> 1 partition with
        # 3 rows; u2 -> 1 partition 1 row.
        data = ([(0, "a", 1.0), (0, "b", 1.0)] + [(1, "a", 1.0)] * 3 +
                [(2, "b", 1.0)])
        backend = pdp.LocalBackend()
        result = analysis.compute_dataset_histograms(
            data, extractors(), backend)
        hist = list(result)[0]
        assert hist.l0_contributions_histogram.total_count() == 3
        assert hist.l0_contributions_histogram.max_value == 2
        assert hist.linf_contributions_histogram.max_value == 3
        assert hist.count_per_partition_histogram.total_count() == 2
        assert hist.count_privacy_id_per_partition.max_value == 2

    def test_quantiles(self):
        bins = [
            histograms.FrequencyBin(lower=i, count=10, sum=10 * i, max=i)
            for i in range(1, 11)
        ]
        h = histograms.Histogram(histograms.HistogramType.L0_CONTRIBUTIONS,
                                 bins)
        q = h.quantiles([0.05, 0.5, 0.95])
        assert q[0] == 1
        assert q[1] in (5, 6)
        assert q[2] == 10


class TestPerformUtilityAnalysis:

    def test_count_analysis_private_partitions(self):
        n_users = 60
        data = [(u, pk, 1.0) for u in range(n_users)
                for pk in ("a", "b")]
        backend = pdp.LocalBackend()
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-5,
            aggregate_params=count_params(l0=2, linf=1))
        result = list(
            analysis.perform_utility_analysis(data, backend, options,
                                              extractors()))[0]
        assert len(result) == 1
        am = result[0]
        assert am.count_metrics is not None
        assert am.partition_selection_metrics is not None
        assert am.partition_selection_metrics.num_partitions == 2
        # No contribution bounding error (bounds are not binding).
        assert am.count_metrics.error_expected == pytest.approx(0.0,
                                                                abs=1e-6)
        assert am.count_metrics.noise_std > 0

    def test_multi_configuration_sweep(self):
        data = [(u, "a", 1.0) for u in range(30) for _ in range(4)]
        backend = pdp.LocalBackend()
        mpc = analysis.MultiParameterConfiguration(
            max_contributions_per_partition=[1, 2, 4])
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-5,
            aggregate_params=count_params(l0=1, linf=1),
            multi_param_configuration=mpc)
        result = list(
            analysis.perform_utility_analysis(data, backend, options,
                                              extractors()))[0]
        assert len(result) == 3
        # linf=1 truncates 3/4 of rows; linf=4 keeps all.
        err1 = result[0].count_metrics.error_linf_expected
        err4 = result[2].count_metrics.error_linf_expected
        assert err1 == pytest.approx(-90.0)  # 30 users * (1 - 4)
        assert err4 == pytest.approx(0.0)

    def test_public_partitions(self):
        data = [(u, "a", 1.0) for u in range(20)]
        backend = pdp.LocalBackend()
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-5, aggregate_params=count_params())
        result = list(
            analysis.perform_utility_analysis(
                data, backend, options, extractors(),
                public_partitions=["a", "b"]))[0]
        am = result[0]
        assert am.partition_selection_metrics is None
        assert am.count_metrics is not None


class TestPreAggregation:

    def test_preaggregate_output(self):
        data = [(0, "a", 2.0), (0, "a", 3.0), (0, "b", 1.0), (1, "a", 4.0)]
        backend = pdp.LocalBackend()
        result = sorted(
            analysis.preaggregate(data, backend, extractors()),
            key=repr)
        # (pk, (count, sum, n_partitions))
        assert ("a", (2, 5.0, 2)) in result
        assert ("b", (1, 1.0, 2)) in result
        assert ("a", (1, 4.0, 1)) in result

    def test_analysis_on_preaggregated(self):
        data = [(0, "a", 1.0), (1, "a", 1.0), (2, "a", 1.0)]
        backend = pdp.LocalBackend()
        pre = list(analysis.preaggregate(data, backend, extractors()))
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-5, aggregate_params=count_params(),
            pre_aggregated_data=True)
        pre_extractors = analysis.PreAggregateExtractors(
            partition_extractor=operator.itemgetter(0),
            preaggregate_extractor=operator.itemgetter(1))
        result = list(
            analysis.perform_utility_analysis(pre, backend, options,
                                              pre_extractors))[0]
        assert result[0].count_metrics is not None


class TestTune:

    def test_tune_count(self):
        rng = np.random.default_rng(0)
        # Users with varying contribution counts across partitions.
        data = []
        for u in range(100):
            n_parts = rng.integers(1, 6)
            for pk in rng.choice(20, n_parts, replace=False):
                for _ in range(rng.integers(1, 4)):
                    data.append((u, int(pk), 1.0))
        backend = pdp.LocalBackend()
        hist = list(
            analysis.compute_dataset_histograms(data, extractors(),
                                                backend))[0]
        options = analysis.TuneOptions(
            epsilon=2.0, delta=1e-5,
            aggregate_params=count_params(),
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True,
                max_contributions_per_partition=True))
        result = list(
            analysis.tune(data, backend, hist, options, extractors()))[0]
        assert isinstance(result, analysis.TuneResult)
        n_configs = result.utility_analysis_parameters.size
        assert len(result.utility_analysis_results) == n_configs
        assert 0 <= result.index_best < n_configs

    def test_tune_sum(self):
        # Exceeds the reference (its tuner rejects SUM outright,
        # reference parameter_tuning.py:255-270): the L0 bound tunes for
        # SUM under supplied per-partition clip bounds, on both planes.
        from pipelinedp_tpu.backends import JaxBackend
        rng = np.random.default_rng(1)
        data = []
        for u in range(150):
            # A wide L0 spread (heavy tail) so the histogram quantiles
            # yield several distinct candidates.
            n_parts = 1 + min(int(rng.pareto(1.0) * 3), 40)
            for pk in rng.choice(50, n_parts, replace=False):
                data.append((u, int(pk), float(rng.uniform(0, 5))))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_sum_per_partition=0.0, max_sum_per_partition=10.0)
        options = analysis.TuneOptions(
            epsilon=1.0, delta=1e-5, aggregate_params=params,
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True))
        hist = list(analysis.compute_dataset_histograms(
            data, extractors(), pdp.LocalBackend()))[0]
        for backend in (pdp.LocalBackend(), JaxBackend()):
            result = list(analysis.tune(data, backend, hist, options,
                                        extractors()))[0]
            n_configs = result.utility_analysis_parameters.size
            assert n_configs > 1
            assert 0 <= result.index_best < n_configs
            best = result.utility_analysis_results[result.index_best]
            assert best.sum_metrics is not None

    def test_tune_sum_requires_clip_bounds(self):
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=1.0)
        with pytest.raises(ValueError, match="min/max_sum_per_partition"):
            analysis.tune([1], pdp.LocalBackend(), None,
                          analysis.TuneOptions(
                              epsilon=1.0, delta=1e-5,
                              aggregate_params=params,
                              function_to_minimize=(
                                  analysis.MinimizingFunction.ABSOLUTE_ERROR),
                              parameters_to_tune=analysis.ParametersToTune(
                                  max_partitions_contributed=True)),
                          extractors())

    def test_tune_rejects_unsupported(self):
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM], max_partitions_contributed=1,
            max_contributions_per_partition=1, vector_size=2,
            vector_max_norm=1.0, vector_norm_kind=pdp.NormKind.L2)
        with pytest.raises(NotImplementedError):
            analysis.tune([1], pdp.LocalBackend(), None,
                          analysis.TuneOptions(
                              epsilon=1.0, delta=1e-5,
                              aggregate_params=params,
                              function_to_minimize=(
                                  analysis.MinimizingFunction.ABSOLUTE_ERROR),
                              parameters_to_tune=analysis.ParametersToTune(
                                  max_partitions_contributed=True)),
                          extractors())


class TestUtilityAnalysisEngineValidation:

    def test_aggregate_raises(self):
        acc = pdp.NaiveBudgetAccountant(1.0, 1e-6)
        engine = analysis.UtilityAnalysisEngine(acc, pdp.LocalBackend())
        with pytest.raises(ValueError, match="can't be called"):
            engine.aggregate([1], count_params(), extractors())

    def test_unsupported_metrics_rejected(self):
        acc = pdp.NaiveBudgetAccountant(1.0, 1e-6)
        engine = analysis.UtilityAnalysisEngine(acc, pdp.LocalBackend())
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=pdp.AggregateParams(
                metrics=[pdp.Metrics.MEAN], max_partitions_contributed=1,
                max_contributions_per_partition=1, min_value=0.0,
                max_value=1.0))
        with pytest.raises(NotImplementedError):
            engine.analyze([(0, "a", 1.0)], options, extractors())


class TestFusedSweep:
    """Differential tests: the on-device configuration-axis sweep
    (``analysis/jax_sweep.py``) against the host oracle graph.

    Tolerances reflect the documented approximation contract: the device
    path always uses the moment approximation for P(keep) where the host
    uses exact PMF convolution below 100 users, and Laplace error
    quantiles come from a quantile table instead of per-partition
    Monte-Carlo."""

    @staticmethod
    def _dataset(n=4000, users=300, parts=25, seed=0):
        rng = np.random.default_rng(seed)
        return pdp.ArrayDataset(
            privacy_ids=rng.integers(0, users, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(0, 5, n).astype(np.float64))

    @staticmethod
    def _run_both(ds, options, public=None):
        from pipelinedp_tpu.backends import JaxBackend
        ex = pdp.DataExtractors()
        host = list(analysis.perform_utility_analysis(
            ds, pdp.LocalBackend(), options, ex, public_partitions=public))
        fused_result = analysis.perform_utility_analysis(
            ds, JaxBackend(), options, ex, public_partitions=public)
        from pipelinedp_tpu.analysis import jax_sweep
        assert isinstance(fused_result, jax_sweep.LazySweepResult), (
            "fused backend must dispatch to the device sweep")
        return host[0], list(fused_result)[0]

    @staticmethod
    def _assert_metrics_close(h, f, rtol=0.05, atol=0.5):
        for field in ("error_l0_expected", "error_linf_expected",
                      "error_expected", "error_variance",
                      "ratio_data_dropped_l0", "ratio_data_dropped_linf",
                      "error_expected_w_dropped_partitions", "noise_std"):
            hv, fv = getattr(h, field), getattr(f, field)
            assert fv == pytest.approx(hv, rel=rtol, abs=atol), (
                field, hv, fv)
        # Quantiles: the host path Monte-Carlos Laplace quantiles with only
        # 1k samples, so compare at the scale of the whole error
        # distribution, not of each (possibly near-zero) quantile.
        spread = max(abs(q) for q in h.error_quantiles) or 1.0
        for hq, fq in zip(h.error_quantiles, f.error_quantiles):
            scale = max(1.0, abs(hq), 0.1 * spread)
            assert abs(hq - fq) / scale < 0.15, (h.error_quantiles,
                                                 f.error_quantiles)

    def test_count_multi_config_truncated_geometric(self):
        ds = self._dataset()
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 3, 9, 27],
            max_contributions_per_partition=[1, 2, 4, 8])
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-6,
            aggregate_params=count_params(l0=4, linf=2),
            multi_param_configuration=multi)
        host, fused = self._run_both(ds, options)
        assert len(host) == len(fused) == 4
        for h, f in zip(host, fused):
            self._assert_metrics_close(h.count_metrics, f.count_metrics)
            hp = h.partition_selection_metrics
            fp = f.partition_selection_metrics
            assert fp.num_partitions == hp.num_partitions
            assert fp.dropped_partitions_expected == pytest.approx(
                hp.dropped_partitions_expected, rel=0.05, abs=0.3)

    def test_all_metrics_gaussian(self):
        ds = self._dataset(seed=1)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            noise_kind=pdp.NoiseKind.GAUSSIAN,
            max_partitions_contributed=3,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=5.0,
            min_sum_per_partition=None, max_sum_per_partition=None)
        # SUM analysis uses per-partition sum bounds.
        params.min_sum_per_partition = 0.0
        params.max_sum_per_partition = 20.0
        params.min_value = params.max_value = None
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=params)
        host, fused = self._run_both(ds, options)
        h, f = host[0], fused[0]
        self._assert_metrics_close(h.count_metrics, f.count_metrics)
        self._assert_metrics_close(h.sum_metrics, f.sum_metrics)
        self._assert_metrics_close(h.privacy_id_count_metrics,
                                   f.privacy_id_count_metrics)

    def test_public_partitions_with_empty(self):
        ds = self._dataset(parts=10, seed=2)
        public = list(range(14))  # 4 empty public partitions
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=2))
        host, fused = self._run_both(ds, options, public=public)
        assert fused[0].partition_selection_metrics is None
        self._assert_metrics_close(host[0].count_metrics,
                                   fused[0].count_metrics)

    @pytest.mark.parametrize("strategy", [
        pdp.PartitionSelectionStrategy.LAPLACE_THRESHOLDING,
        pdp.PartitionSelectionStrategy.GAUSSIAN_THRESHOLDING,
    ])
    def test_thresholding_strategies(self, strategy):
        ds = self._dataset(seed=3)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(
                l0=3, linf=2, partition_selection_strategy=strategy))
        host, fused = self._run_both(ds, options)
        hp = host[0].partition_selection_metrics
        fp = fused[0].partition_selection_metrics
        assert fp.dropped_partitions_expected == pytest.approx(
            hp.dropped_partitions_expected, rel=0.05, abs=0.3)
        self._assert_metrics_close(host[0].count_metrics,
                                   fused[0].count_metrics)

    def test_chunked_configs_match_single_chunk(self, monkeypatch):
        from pipelinedp_tpu.analysis import jax_sweep
        from pipelinedp_tpu.backends import JaxBackend
        ds = self._dataset(n=1000, users=100, parts=8)
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2, 3, 4, 5],
            max_contributions_per_partition=[1, 1, 2, 2, 3])
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=1),
            multi_param_configuration=multi)
        ex = pdp.DataExtractors()
        one = list(analysis.perform_utility_analysis(
            ds, JaxBackend(), options, ex))[0]
        monkeypatch.setattr(jax_sweep, "_CHUNK_CAP", 2)
        chunked = list(analysis.perform_utility_analysis(
            ds, JaxBackend(), options, ex))[0]
        for a, b in zip(one, chunked):
            assert b.count_metrics.error_expected == pytest.approx(
                a.count_metrics.error_expected, rel=1e-5)
            assert b.count_metrics.error_variance == pytest.approx(
                a.count_metrics.error_variance, rel=1e-5)

    def test_host_fallback_paths(self):
        # Pre-aggregated data and per-partition results use the host
        # graph; partition sampling is fused (TestFusedSweepSampling).
        from pipelinedp_tpu.analysis import jax_sweep
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=1),
            partitions_sampling_prob=0.5)
        assert jax_sweep.sweep_is_supported(options, None, False)
        options2 = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=1))
        # return_per_partition runs fused too since r4 (byte-capped).
        assert jax_sweep.sweep_is_supported(options2, None, True)
        assert jax_sweep.sweep_is_supported(options2, None, False)
        pre = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=1),
            pre_aggregated_data=True)
        # Pre-aggregated input runs fused since r3 (stage A skipped).
        assert jax_sweep.sweep_is_supported(pre, None, False)


class TestAnalysisErrorModelClosedForm:
    """Closed-form checks of the per-partition error model and the
    cross-partition aggregation — a representative subset of the
    reference's ``analysis/tests/combiners_test.py`` matrix."""

    def _params(self, agg_params, eps=1.0, delta=1e-6):
        spec = MechanismSpec(MechanismType.LAPLACE, _eps=eps, _delta=delta)
        return CombinerParams(spec, agg_params)

    @pytest.mark.parametrize(
        "counts,n_parts,l0,linf,exp_sum,exp_min,exp_max,exp_l0,exp_var",
        [
            # Single user under all caps: no errors at all.
            ([2], [1], 4, 4, 2.0, 0.0, 0.0, 0.0, 0.0),
            # linf clip only: 7 -> 3, keep prob 1 (n_parts <= l0).
            ([7], [1], 4, 3, 7.0, 0.0, -4.0, 0.0, 0.0),
            # l0 drop only: contribution 2 kept w.p. 1/2.
            ([2], [2], 1, 4, 2.0, 0.0, 0.0, -1.0, 1.0),
            # Both: clip 9->2, keep prob 1/4 -> E=-2*(3/4), Var=4*3/16.
            ([9], [4], 1, 2, 9.0, 0.0, -7.0, -1.5, 0.75),
            # Two users sum their independent errors.
            ([9, 1], [4, 1], 1, 2, 10.0, 0.0, -7.0, -1.5, 0.75),
        ])
    def test_count_error_decomposition(self, counts, n_parts, l0, linf,
                                       exp_sum, exp_min, exp_max, exp_l0,
                                       exp_var):
        c = ua_combiners.CountCombiner(
            self._params(count_params(l0=l0, linf=linf)))
        m = c.compute_metrics(
            c.create_accumulator((np.array(counts), np.zeros(len(counts)),
                                  np.array(n_parts))))
        assert m.sum == exp_sum
        assert m.per_partition_error_min == pytest.approx(exp_min)
        assert m.per_partition_error_max == pytest.approx(exp_max)
        assert m.expected_cross_partition_error == pytest.approx(exp_l0)
        assert m.std_cross_partition_error**2 == pytest.approx(exp_var)
        # Documented invariant (metrics.py): E[bounded sum] decomposition.
        e_bounded = (m.sum + m.per_partition_error_min +
                     m.per_partition_error_max +
                     m.expected_cross_partition_error)
        clipped = np.clip(counts, 0, linf)
        probs = np.minimum(1, l0 / np.array(n_parts))
        assert e_bounded == pytest.approx(float((clipped * probs).sum()))

    @pytest.mark.parametrize("sums,bounds,exp_min,exp_max", [
        ([15.0], (0.0, 10.0), 0.0, -5.0),
        ([-5.0], (0.0, 10.0), 5.0, 0.0),
        ([-5.0, 15.0, 3.0], (0.0, 10.0), 5.0, -5.0),
        ([2.0], (-1.0, 1.0), 0.0, -1.0),
    ])
    def test_sum_clip_errors(self, sums, bounds, exp_min, exp_max):
        agg = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=4,
            max_contributions_per_partition=4,
            min_sum_per_partition=bounds[0], max_sum_per_partition=bounds[1])
        c = ua_combiners.SumCombiner(self._params(agg))
        m = c.compute_metrics(c.create_accumulator(
            (None, np.array(sums), np.ones(len(sums), int))))
        assert m.per_partition_error_min == pytest.approx(exp_min)
        assert m.per_partition_error_max == pytest.approx(exp_max)

    def test_merge_is_elementwise_addition(self):
        c = ua_combiners.CountCombiner(
            self._params(count_params(l0=1, linf=2)))
        a1 = c.create_accumulator((np.array([5]), np.zeros(1), np.array([2])))
        a2 = c.create_accumulator((np.array([1]), np.zeros(1), np.array([1])))
        merged = c.merge_accumulators(a1, a2)
        assert merged == tuple(x + y for x, y in zip(a1, a2))

    def test_partition_selection_exact_pmf_vs_moments(self):
        """Below MAX_PROBABILITIES the calculator uses the exact PMF; the
        moment approximation must agree closely for homogeneous probs."""
        from pipelinedp_tpu.aggregate_params import (
            PartitionSelectionStrategy)
        probs = [0.7] * 80
        exact = ua_combiners.PartitionSelectionCalculator(
            probabilities=list(probs))
        approx = ua_combiners.PartitionSelectionCalculator(
            moments=ua_combiners._probabilities_to_moments(probs))
        for strat in (PartitionSelectionStrategy.TRUNCATED_GEOMETRIC,
                      PartitionSelectionStrategy.LAPLACE_THRESHOLDING):
            pe = exact.compute_probability_to_keep(strat, 1.0, 1e-6, 2)
            pa = approx.compute_probability_to_keep(strat, 1.0, 1e-6, 2)
            assert pa == pytest.approx(pe, abs=2e-3)

    def test_aggregate_error_combiner_weights_by_keep_probability(self):
        comb = ua_combiners.SumAggregateErrorMetricsCombiner(
            metrics.AggregateMetricType.COUNT, [0.5])
        sm = metrics.SumMetrics(
            sum=10.0, per_partition_error_min=0.0,
            per_partition_error_max=-2.0,
            expected_cross_partition_error=-3.0,
            std_cross_partition_error=2.0, std_noise=1.0,
            noise_kind=pdp.NoiseKind.GAUSSIAN)
        acc = comb.create_accumulator(sm, prob_to_keep=0.5)
        assert acc.kept_partitions_expected == 0.5
        assert acc.error_l0_expected == pytest.approx(0.5 * -3.0)
        assert acc.error_linf_max_expected == pytest.approx(0.5 * -2.0)
        assert acc.error_l0_variance == pytest.approx(0.5 * 4.0)
        assert acc.error_variance == pytest.approx(0.5 * (4.0 + 1.0))
        # Data dropped by selection: (1-p) * surviving contribution.
        assert acc.data_dropped_partition_selection == pytest.approx(
            0.5 * (10.0 - 3.0 - 2.0))
        assert acc.error_expected_w_dropped_partitions == pytest.approx(
            0.5 * (-3.0 - 2.0) + 0.5 * -10.0)
        # Gaussian quantile: closed-form normal ppf at inverted levels.
        import scipy.stats
        want = scipy.stats.norm.ppf(0.5, loc=-3.0,
                                    scale=math.sqrt(4.0 + 1.0))
        assert acc.error_quantiles[0] == pytest.approx(
            0.5 * (want + (-2.0)))

    def test_aggregate_error_metrics_normalization(self):
        comb = ua_combiners.SumAggregateErrorMetricsCombiner(
            metrics.AggregateMetricType.COUNT, [0.5])
        sm = metrics.SumMetrics(
            sum=10.0, per_partition_error_min=0.0,
            per_partition_error_max=0.0,
            expected_cross_partition_error=-4.0,
            std_cross_partition_error=0.0, std_noise=1.0,
            noise_kind=pdp.NoiseKind.GAUSSIAN)
        acc = comb.merge_accumulators(
            comb.create_accumulator(sm, prob_to_keep=1.0),
            comb.create_accumulator(sm, prob_to_keep=0.5))
        m = comb.compute_metrics(acc)
        # Averages over EXPECTED kept partitions (1.5), except the
        # dropped-partition-aware error which averages over all (2).
        assert m.error_l0_expected == pytest.approx(
            (1.0 * -4.0 + 0.5 * -4.0) / 1.5)
        assert m.error_expected_w_dropped_partitions == pytest.approx(
            ((1.0 * -4.0 + 0.0) + (0.5 * -4.0 + 0.5 * -10.0)) / 2.0)
        # Global drop ratios divide by the total true aggregate.
        assert m.ratio_data_dropped_l0 == pytest.approx((4.0 + 4.0) / 20.0)

    def test_compound_uses_each_configs_own_keep_probability(self):
        """Regression: the reference scored every configuration with the
        FIRST configuration's keep probability (reference
        ``analysis/combiners.py:470-483``); each configuration must use
        its own."""
        sel = ua_combiners.PrivatePartitionSelectionAggregateErrorMetricsCombiner(
            [0.5])
        mk = ua_combiners.SumAggregateErrorMetricsCombiner(
            metrics.AggregateMetricType.COUNT, [0.5])
        compound = ua_combiners.AggregateErrorMetricsCompoundCombiner(
            [sel, mk, sel, mk], return_named_tuple=False)
        sm = metrics.SumMetrics(
            sum=10.0, per_partition_error_min=0.0,
            per_partition_error_max=0.0,
            expected_cross_partition_error=-4.0,
            std_cross_partition_error=0.0, std_noise=1.0,
            noise_kind=pdp.NoiseKind.GAUSSIAN)
        _, accs = compound.create_accumulator((1.0, sm, 0.25, sm))
        assert accs[1].kept_partitions_expected == 1.0
        assert accs[3].kept_partitions_expected == 0.25
        assert accs[3].error_l0_expected == pytest.approx(0.25 * -4.0)


class TestFusedSweepMultiSumBounds:
    """Per-configuration sum-bound VECTORS (MultiParameterConfiguration
    .min/max_sum_per_partition) through the device sweep."""

    def test_sum_bound_vectors_match_host(self):
        from pipelinedp_tpu.ops import noise as noise_ops
        noise_ops.seed_host_rng(0)  # host MC quantiles: reproducible draws
        ds = TestFusedSweep._dataset(n=3000, users=150, parts=20, seed=11)
        multi = data_structures.MultiParameterConfiguration(
            min_sum_per_partition=[0.0, 0.0, 0.0],
            max_sum_per_partition=[2.0, 10.0, 60.0])
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=3,
            max_contributions_per_partition=2,
            min_sum_per_partition=0.0, max_sum_per_partition=5.0)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=params,
            multi_param_configuration=multi)
        host, fused = TestFusedSweep._run_both(ds, options)
        assert len(host) == len(fused) == 3
        for h, f in zip(host, fused):
            TestFusedSweep._assert_metrics_close(h.sum_metrics,
                                                 f.sum_metrics)
        # Tighter clip bounds must produce larger (more negative)
        # expected clipping error.
        errs = [f.sum_metrics.error_linf_max_expected for f in fused]
        assert errs[0] <= errs[1] <= errs[2] <= 0.0


class TestFusedSweepSampling:
    """partitions_sampling_prob on the device sweep: both planes use the
    same deterministic SHA1 sampler, so they analyze the same subset."""

    @pytest.mark.parametrize("public", [False, True])
    def test_sampling_matches_host(self, public):
        from pipelinedp_tpu.ops import noise as noise_ops
        noise_ops.seed_host_rng(0)
        ds = TestFusedSweep._dataset(n=3000, users=200, parts=30, seed=9)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=3, linf=2),
            partitions_sampling_prob=0.5)
        pub = (sorted(np.unique(ds.partition_keys).tolist())
               if public else None)
        host, fused = TestFusedSweep._run_both(ds, options, public=pub)
        h, f = host[0], fused[0]
        TestFusedSweep._assert_metrics_close(h.count_metrics,
                                             f.count_metrics)
        if not public:
            assert (f.partition_selection_metrics.num_partitions ==
                    h.partition_selection_metrics.num_partitions)
            # Sampling at 0.5 must actually have dropped partitions.
            assert h.partition_selection_metrics.num_partitions < 30

    def test_sampling_prob_one_unchanged(self):
        ds = TestFusedSweep._dataset(n=1000, users=100, parts=10, seed=10)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=2),
            partitions_sampling_prob=1)
        host, fused = TestFusedSweep._run_both(ds, options)
        assert (fused[0].partition_selection_metrics.num_partitions ==
                host[0].partition_selection_metrics.num_partitions == 10)


class TestFusedSweepFuzz:
    """Randomized sweep configurations, device vs host — the sweep
    counterpart of ``tests/test_differential_fuzz.py``. Reuses the
    dataset/compare helpers; fixed seeds keep failures reproducible."""

    _dataset = staticmethod(TestFusedSweep._dataset)
    _run_both = staticmethod(TestFusedSweep._run_both)
    _assert_metrics_close = staticmethod(TestFusedSweep._assert_metrics_close)

    @pytest.mark.parametrize("seed", range(9))
    def test_random_config(self, seed):
        from pipelinedp_tpu.ops import noise as noise_ops
        # The host oracle Monte-Carlos its Laplace error quantiles from
        # the module-level host RNG; seed it so failures reproduce.
        noise_ops.seed_host_rng(seed)
        rng = np.random.default_rng(1000 + seed)
        ds = self._dataset(n=int(rng.integers(500, 4000)),
                           users=int(rng.integers(30, 400)),
                           parts=int(rng.integers(5, 40)),
                           seed=seed)
        metric = [pdp.Metrics.COUNT, pdp.Metrics.PRIVACY_ID_COUNT,
                  pdp.Metrics.SUM][int(rng.integers(0, 3))]
        kw = dict(metrics=[metric],
                  noise_kind=(pdp.NoiseKind.LAPLACE if rng.random() < 0.5
                              else pdp.NoiseKind.GAUSSIAN),
                  max_partitions_contributed=int(rng.integers(1, 6)),
                  max_contributions_per_partition=int(rng.integers(1, 4)),
                  partition_selection_strategy=list(
                      pdp.PartitionSelectionStrategy)[
                          int(rng.integers(0, 3))])
        if metric == pdp.Metrics.SUM:
            kw.update(min_sum_per_partition=0.0,
                      max_sum_per_partition=float(rng.uniform(2, 30)))
        params = pdp.AggregateParams(**kw)
        n_cfg = int(rng.integers(1, 5))
        multi = None
        if n_cfg > 1:
            # Per-config mechanism vectors (fused since r3) are drawn
            # too: mixed noise kinds and mixed selection strategies.
            kinds = None
            if rng.random() < 0.4:
                kinds = [list(pdp.NoiseKind)[int(i)]
                         for i in rng.integers(0, 2, n_cfg)]
            strategies = None
            if rng.random() < 0.4:
                strategies = [list(pdp.PartitionSelectionStrategy)[int(i)]
                              for i in rng.integers(0, 3, n_cfg)]
            multi = data_structures.MultiParameterConfiguration(
                max_partitions_contributed=sorted(
                    int(x) for x in rng.integers(1, 12, n_cfg)),
                max_contributions_per_partition=[
                    int(x) for x in rng.integers(1, 5, n_cfg)],
                noise_kind=kinds,
                partition_selection_strategy=strategies)
        options = analysis.UtilityAnalysisOptions(
            epsilon=float(rng.uniform(0.3, 5.0)),
            delta=float(10.0**-rng.integers(4, 9)),
            aggregate_params=params,
            multi_param_configuration=multi,
            partitions_sampling_prob=(
                1 if rng.random() < 0.5 else float(rng.uniform(0.3, 0.9))))
        public = (sorted(np.unique(ds.partition_keys).tolist())
                  if rng.random() < 0.4 else None)
        host, fused = self._run_both(ds, options, public=public)
        assert len(host) == len(fused) == (multi.size if multi else 1)
        field = {pdp.Metrics.COUNT: "count_metrics",
                 pdp.Metrics.PRIVACY_ID_COUNT: "privacy_id_count_metrics",
                 pdp.Metrics.SUM: "sum_metrics"}[metric]
        for h, f in zip(host, fused):
            self._assert_metrics_close(getattr(h, field),
                                       getattr(f, field))
            if public is None:
                hp = h.partition_selection_metrics
                fp = f.partition_selection_metrics
                assert fp.num_partitions == hp.num_partitions
                assert fp.dropped_partitions_expected == pytest.approx(
                    hp.dropped_partitions_expected, rel=0.07, abs=0.5)


class TestFusedSweepPerPartition:
    """``return_per_partition=True`` runs fused too: the
    per-(partition, config) SumMetrics rows fetched from stage B must
    match the host oracle's per-partition rows; past the fetch byte cap
    the sweep reroutes itself to the host graph and still returns the
    same rows."""

    _dataset = staticmethod(TestFusedSweep._dataset)

    @staticmethod
    def _run_both_pp(ds, options, public=None, backend=None):
        from pipelinedp_tpu.backends import JaxBackend
        ex = pdp.DataExtractors()
        _, host_pp = analysis.perform_utility_analysis(
            ds, pdp.LocalBackend(), options, ex, public_partitions=public,
            return_per_partition=True)
        fused_res, fused_pp = analysis.perform_utility_analysis(
            ds, backend or JaxBackend(), options, ex,
            public_partitions=public, return_per_partition=True)
        return dict(host_pp), dict(fused_pp), fused_res

    @staticmethod
    def _assert_rows_match(host, fused, private):
        assert set(host) == set(fused)
        for k in host:
            h, f = host[k], fused[k]
            assert len(h) == len(f), (k, len(h), len(f))
            for hv, fv in zip(h, f):
                if isinstance(hv, float):  # p_keep
                    # Device: moment approximation; host: exact PMF below
                    # 100 users (documented contract).
                    assert abs(hv - fv) < 0.06, (k, hv, fv)
                else:
                    assert hv.noise_kind == fv.noise_kind
                    for fld in ("sum", "per_partition_error_min",
                                "per_partition_error_max",
                                "expected_cross_partition_error",
                                "std_cross_partition_error", "std_noise"):
                        a, b = getattr(hv, fld), getattr(fv, fld)
                        assert abs(a - b) <= 1e-3 * max(1.0, abs(a)), (
                            k, fld, a, b)

    def test_matches_host_rows_private(self):
        ds = self._dataset(n=2000, users=150, parts=8, seed=3)
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 3],
            max_contributions_per_partition=[2, 4])
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-6,
            aggregate_params=count_params(l0=4, linf=2),
            multi_param_configuration=multi)
        host, fused, fused_res = self._run_both_pp(ds, options)
        from pipelinedp_tpu.analysis import jax_sweep
        assert isinstance(fused_res, jax_sweep.LazySweepResult)
        self._assert_rows_match(host, fused, private=True)

    def test_matches_host_rows_public_with_empty_partition(self):
        ds = self._dataset(n=1500, users=100, parts=6, seed=4)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.5, delta=1e-6,
            aggregate_params=pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                max_partitions_contributed=3,
                max_contributions_per_partition=2,
                min_sum_per_partition=0.0, max_sum_per_partition=8.0))
        public = list(range(8))  # 6 and 7 are empty -> pseudo rows
        host, fused, _ = self._run_both_pp(ds, options, public=public)
        assert set(fused) == set(range(8))
        self._assert_rows_match(host, fused, private=False)

    def test_matches_host_rows_on_mesh(self, monkeypatch):
        """return_per_partition stays FUSED on a multi-device mesh:
        the config-axis-sharded [P, C] blocks gather
        to the same rows the host oracle produces."""
        import jax
        from pipelinedp_tpu.backends import JaxBackend
        from pipelinedp_tpu.parallel import make_mesh
        from pipelinedp_tpu.analysis import jax_sweep
        # A 1-device mesh would take the single-device branch and make
        # everything below vacuous.
        assert len(jax.devices()) >= 8
        # Fail LOUDLY if the mesh run reroutes to the host graph — the
        # rows would trivially match the oracle and mask the regression.
        monkeypatch.setattr(
            jax_sweep.LazySweepResult, "_host_fallback",
            lambda self: (_ for _ in ()).throw(AssertionError(
                "mesh + return_per_partition took the host fallback")))
        ds = self._dataset(n=2000, users=150, parts=8, seed=6)
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=list(range(1, 9)),
            max_contributions_per_partition=[2] * 8)
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-6,
            aggregate_params=count_params(l0=4, linf=2),
            multi_param_configuration=multi)
        host, fused, fused_res = self._run_both_pp(
            ds, options, backend=JaxBackend(mesh=make_mesh(8)))
        assert isinstance(fused_res, jax_sweep.LazySweepResult), (
            "mesh + return_per_partition fell back to the host graph")
        self._assert_rows_match(host, fused, private=True)

    def test_byte_cap_falls_back_to_host(self, monkeypatch):
        from pipelinedp_tpu.analysis import jax_sweep
        monkeypatch.setattr(jax_sweep, "_PP_BYTE_CAP", 64)
        ds = self._dataset(n=800, users=80, parts=5, seed=5)
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=2))
        host, fused, _ = self._run_both_pp(ds, options)
        # Fallback produces the HOST rows: exact equality.
        for k in host:
            assert host[k] == fused[k], k


class TestFusedSweepMixedMechanisms:
    """Per-config ``noise_kind`` /
    ``partition_selection_strategy`` vectors run FUSED (previously host
    fallback), matching the host oracle per configuration."""

    _run_both = staticmethod(TestFusedSweep._run_both)
    _assert_metrics_close = staticmethod(TestFusedSweep._assert_metrics_close)
    _dataset = staticmethod(TestFusedSweep._dataset)

    def test_per_config_mechanism_vectors(self):
        from pipelinedp_tpu.ops import noise as noise_ops
        noise_ops.seed_host_rng(7)
        ds = self._dataset(n=3000, users=150, parts=20, seed=7)
        S = pdp.PartitionSelectionStrategy
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 3, 5, 8],
            max_contributions_per_partition=[2, 2, 1, 3],
            noise_kind=[pdp.NoiseKind.LAPLACE, pdp.NoiseKind.GAUSSIAN,
                        pdp.NoiseKind.GAUSSIAN, pdp.NoiseKind.LAPLACE],
            partition_selection_strategy=[
                S.TRUNCATED_GEOMETRIC, S.LAPLACE_THRESHOLDING,
                S.GAUSSIAN_THRESHOLDING, S.TRUNCATED_GEOMETRIC])
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-6,
            aggregate_params=count_params(l0=2, linf=2),
            multi_param_configuration=multi)
        host, fused = self._run_both(ds, options)
        assert len(host) == len(fused) == 4
        for h, f in zip(host, fused):
            self._assert_metrics_close(h.count_metrics, f.count_metrics)
            assert (f.partition_selection_metrics.dropped_partitions_expected
                    == pytest.approx(
                        h.partition_selection_metrics
                        .dropped_partitions_expected, rel=0.07, abs=0.5))


class TestFusedSweepPreAggregated:
    """Pre-aggregated input runs fused (stage A skipped);
    results must match the host graph on the same pre-aggregated rows."""

    _assert_metrics_close = staticmethod(TestFusedSweep._assert_metrics_close)

    @pytest.mark.parametrize("metric", ["COUNT", "SUM"])
    def test_matches_host(self, metric):
        import operator

        from pipelinedp_tpu.backends import JaxBackend
        from pipelinedp_tpu.analysis import jax_sweep
        from pipelinedp_tpu.ops import noise as noise_ops

        noise_ops.seed_host_rng(11)
        rng = np.random.default_rng(11)
        rows = [(int(u), f"p{rng.integers(0, 12)}", float(rng.uniform(0, 5)))
                for u in range(120) for _ in range(rng.integers(1, 6))]
        raw_ex = pdp.DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1),
            value_extractor=operator.itemgetter(2))
        pre_rows = list(analysis.preaggregate(
            rows, pdp.LocalBackend(), raw_ex))
        ex = analysis.PreAggregateExtractors(
            partition_extractor=operator.itemgetter(0),
            preaggregate_extractor=operator.itemgetter(1))
        kw = dict(metrics=[getattr(pdp.Metrics, metric)],
                  max_partitions_contributed=3,
                  max_contributions_per_partition=2)
        if metric == "SUM":
            kw.update(min_sum_per_partition=0.0,
                      max_sum_per_partition=6.0)
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2, 6])
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.5, delta=1e-6,
            aggregate_params=pdp.AggregateParams(**kw),
            multi_param_configuration=multi,
            pre_aggregated_data=True)
        host = list(analysis.perform_utility_analysis(
            pre_rows, pdp.LocalBackend(), options, ex))[0]
        fused_result = analysis.perform_utility_analysis(
            pre_rows, JaxBackend(), options, ex)
        assert isinstance(fused_result, jax_sweep.LazySweepResult)
        fused = list(fused_result)[0]
        assert len(host) == len(fused) == 3
        field = "count_metrics" if metric == "COUNT" else "sum_metrics"
        for h, f in zip(host, fused):
            self._assert_metrics_close(getattr(h, field),
                                       getattr(f, field))


class TestFusedSweepSharded:
    """The configuration-axis sweep over the 8-device virtual mesh:
    each device analyzes its slice of the parameter grid; results must
    match the single-device sweep."""

    def test_sharded_matches_single_device(self):
        import jax
        from pipelinedp_tpu.backends import JaxBackend
        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= 8
        rng = np.random.default_rng(5)
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 200, 3000),
            partition_keys=rng.integers(0, 20, 3000),
            values=rng.uniform(0, 5, 3000))
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=list(range(1, 17)),
            max_contributions_per_partition=[2] * 16)
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=4, linf=2),
            multi_param_configuration=multi)
        ex = pdp.DataExtractors()
        single = list(analysis.perform_utility_analysis(
            ds, JaxBackend(), options, ex))[0]
        sharded = list(analysis.perform_utility_analysis(
            ds, JaxBackend(mesh=make_mesh(8)), options, ex))[0]
        assert len(single) == len(sharded) == 16
        for s, m in zip(single, sharded):
            a, b = s.count_metrics, m.count_metrics
            assert b.error_expected == pytest.approx(a.error_expected,
                                                     rel=1e-4, abs=1e-4)
            assert b.error_variance == pytest.approx(a.error_variance,
                                                     rel=1e-4)
            sp = s.partition_selection_metrics
            mp = m.partition_selection_metrics
            assert mp.dropped_partitions_expected == pytest.approx(
                sp.dropped_partitions_expected, rel=1e-4, abs=1e-5)


class TestMegasweepWidthParity:
    """PARITY row 41: the config-batched megasweep is bit-identical per
    config at EVERY batch width — walked (chunk=1) through batched
    (chunk=K), including widths that do not divide the grid (the padded
    tail repeats the last config and must not leak into real configs).
    The width knob is dp-safe precisely because of this invariance."""

    GRID = 16

    @staticmethod
    def _ds():
        rng = np.random.default_rng(23)
        n = 12_000
        return pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 800, n),
            partition_keys=(rng.zipf(1.3, n) % 120).astype(np.int64),
            values=rng.uniform(0, 10, n))

    @classmethod
    def _options(cls):
        side = int(math.isqrt(cls.GRID))
        pairs = [(a, b) for a in range(1, side + 1)
                 for b in range(1, side + 1)]
        multi = data_structures.MultiParameterConfiguration(
            max_partitions_contributed=[p[0] for p in pairs],
            max_contributions_per_partition=[p[1] for p in pairs])
        return analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(
                l0=4, linf=2, noise_kind=pdp.NoiseKind.LAPLACE),
            multi_param_configuration=multi)

    @classmethod
    def _run(cls, width, mesh=None):
        import dataclasses

        from pipelinedp_tpu import plan as plan_mod
        from pipelinedp_tpu.backends import JaxBackend
        with plan_mod.seam_override("sweep_config_batch", width):
            out = list(analysis.perform_utility_analysis(
                cls._ds(), JaxBackend(rng_seed=0, mesh=mesh),
                cls._options(), pdp.DataExtractors()))[0]
        assert len(out) == cls.GRID
        return [dataclasses.asdict(m.count_metrics) for m in out]

    @staticmethod
    def _assert_bit_identical(got, ref, label):
        for ci, (a, b) in enumerate(zip(got, ref)):
            assert set(a) == set(b)
            for field in a:
                np.testing.assert_array_equal(
                    np.asarray(a[field]), np.asarray(b[field]),
                    err_msg=f"{label} cfg{ci}.{field}")

    def test_walked_vs_batched_bit_identical_single_device(self):
        """chunk=1 (the walked A/B leg) and every intermediate width
        against the full-grid batch, every AggregateErrorMetrics field
        EXACT — width 3, 5 and 7 leave a padded tail, so padding
        invariance rides the same assertion."""
        ref = self._run(self.GRID)
        for width in (1, 3, 5, 7, 8):
            self._assert_bit_identical(self._run(width), ref,
                                       f"width {width}")

    def test_walked_vs_batched_bit_identical_on_mesh(self):
        """The same invariance over the 8-device CPU mesh (the sharded
        kernel rounds widths to a device multiple, so 8 IS the mesh's
        walked mode: one config per device per dispatch)."""
        import jax

        from pipelinedp_tpu.parallel import make_mesh
        assert len(jax.devices()) >= 8
        ref = self._run(self.GRID, mesh=make_mesh(8))
        got = self._run(8, mesh=make_mesh(8))
        self._assert_bit_identical(got, ref, "mesh width 8")


class TestFusedHistograms:
    """Device dataset histograms vs the host graph, bin by bin."""

    @staticmethod
    def _hists(backend, data):
        ex = extractors()
        return list(histograms.compute_dataset_histograms(
            data, ex, backend))[0]

    @staticmethod
    def _assert_equal(a, b):
        assert len(a.bins) == len(b.bins), (a.bins, b.bins)
        for x, y in zip(a.bins, b.bins):
            assert (x.lower, x.count, x.sum, x.max) == (
                y.lower, y.count, y.sum, y.max)

    def test_matches_host_graph(self):
        from pipelinedp_tpu.backends import JaxBackend
        rng = np.random.default_rng(11)
        data = [(int(u), int(p), 1.0)
                for u, p in zip(rng.integers(0, 60, 4000),
                                rng.integers(0, 25, 4000))]
        # Heavy-hitter user and a hot partition to spread bin decades.
        data += [(999, 7, 1.0)] * 2500
        host = self._hists(pdp.LocalBackend(), data)
        fused = self._hists(JaxBackend(), data)
        self._assert_equal(host.l0_contributions_histogram,
                           fused.l0_contributions_histogram)
        self._assert_equal(host.linf_contributions_histogram,
                           fused.linf_contributions_histogram)
        self._assert_equal(host.count_per_partition_histogram,
                           fused.count_per_partition_histogram)
        self._assert_equal(host.count_privacy_id_per_partition,
                           fused.count_privacy_id_per_partition)

    def test_bin_lower_roundtrip(self):
        from pipelinedp_tpu.analysis import jax_sweep
        import jax.numpy as jnp
        vals = np.array([1, 2, 999, 1000, 1001, 1010, 9999, 10000, 10001,
                         123456, 9876543, 2**30], np.int32)
        ids = np.asarray(jax_sweep._bin_ids(jnp.asarray(vals)))
        lowers = jax_sweep._bin_lower_of_id(ids)
        expected = [histograms._to_bin_lower(int(v)) for v in vals]
        assert lowers.tolist() == expected

    def test_quantiles_drive_tuning(self):
        # tune() consumes the histograms; check quantiles agree too.
        from pipelinedp_tpu.backends import JaxBackend
        rng = np.random.default_rng(12)
        data = [(int(u), int(p), 1.0)
                for u, p in zip(rng.integers(0, 100, 3000),
                                rng.zipf(1.5, 3000) % 40)]
        host = self._hists(pdp.LocalBackend(), data)
        fused = self._hists(JaxBackend(), data)
        qs = [0.9, 0.95, 0.99]
        assert (host.l0_contributions_histogram.quantiles(qs) ==
                fused.l0_contributions_histogram.quantiles(qs))
        assert (host.linf_contributions_histogram.quantiles(qs) ==
                fused.linf_contributions_histogram.quantiles(qs))

    def test_value_1000_shares_bin_with_1001(self):
        # Regression: 1000 and 1003 must merge into one lower-1000 bin on
        # both planes (host _to_bin_lower(1000) == _to_bin_lower(1003)).
        from pipelinedp_tpu.backends import JaxBackend
        data = ([(u, 0, 1.0) for u in range(1000)] +
                [(u, 1, 1.0) for u in range(1003)])
        host = self._hists(pdp.LocalBackend(), data)
        fused = self._hists(JaxBackend(), data)
        hb = host.count_per_partition_histogram.bins
        fb = fused.count_per_partition_histogram.bins
        assert [(b.lower, b.count, b.sum, b.max) for b in hb] == \
               [(b.lower, b.count, b.sum, b.max) for b in fb]
        assert len(fb) == 1 and fb[0].lower == 1000 and fb[0].count == 2


class TestUtilityReport:
    """The richer report schema, wired via to_utility_report (the
    reference carries the schema but never wires it)."""

    def _analysis(self):
        rng = np.random.default_rng(20)
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 200, 3000),
            partition_keys=rng.integers(0, 20, 3000),
            values=rng.uniform(0, 5, 3000))
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=count_params(l0=3, linf=2))
        return list(analysis.perform_utility_analysis(
            ds, pdp.LocalBackend(), options, pdp.DataExtractors(
                privacy_id_extractor=operator.itemgetter(0),
                partition_extractor=operator.itemgetter(1),
                value_extractor=operator.itemgetter(2))))[0][0]

    def test_conversion_structure(self):
        agg = self._analysis()
        report = analysis.to_utility_report(agg)
        assert report.input_aggregate_params is agg.input_aggregate_params
        assert len(report.metric_errors) == 1
        mu = report.metric_errors[0]
        assert mu.metric == pdp.Metrics.COUNT
        m = agg.count_metrics
        assert mu.noise_std == m.noise_std
        assert mu.ratio_data_dropped.l0 == m.ratio_data_dropped_l0
        ae = mu.absolute_error
        assert ae.bias == m.error_expected
        assert ae.variance == m.error_variance
        assert ae.rmse == pytest.approx(m.absolute_rmse())
        assert ae.bounding_errors.l0.mean == m.error_l0_expected
        assert ae.bounding_errors.linf == m.error_linf_expected
        assert ae.l1 >= abs(ae.bias) - 1e-9  # E|X| >= |E X|
        re = mu.relative_error
        assert re.rmse == pytest.approx(m.relative_rmse())
        sel = report.partition_selection_metrics
        assert sel is not None
        assert sel.num_partitions == (
            agg.partition_selection_metrics.num_partitions)
        assert sel.dropped_partitions.mean == (
            agg.partition_selection_metrics.dropped_partitions_expected)

    def test_l1_gaussian_identity(self):
        # Zero bias: E|N(0, s^2)| = s*sqrt(2/pi).
        from pipelinedp_tpu.analysis.metrics import _value_errors
        agg = self._analysis()
        m = agg.count_metrics
        import dataclasses as dc
        m0 = dc.replace(m, error_expected=0.0, rel_error_expected=0.0)
        v = _value_errors(m0, relative=False)
        assert v.l1 == pytest.approx(
            math.sqrt(m0.error_variance) * math.sqrt(2 / math.pi))
