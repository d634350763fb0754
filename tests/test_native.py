"""Tests of the native secure-noise library (C++/ctypes): build, CSPRNG
stream quality, snapping mechanism invariants (Mironov 2012), discrete
Laplace exactness, and the opt-in wiring through the host noise path."""

import math

import numpy as np
import pytest

native = pytest.importorskip("pipelinedp_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable")


class TestCSPRNG:

    def test_deterministic_under_seed(self):
        native.seed(42)
        a = native.uniform(1000)
        native.seed(42)
        b = native.uniform(1000)
        np.testing.assert_array_equal(a, b)
        native.seed(43)
        c = native.uniform(1000)
        assert not np.array_equal(a, c)

    def test_uniform_range_and_moments(self):
        native.seed(0)
        u = native.uniform(200_000)
        assert u.min() > 0.0 and u.max() <= 1.0
        assert u.mean() == pytest.approx(0.5, abs=0.005)
        assert u.var() == pytest.approx(1 / 12, rel=0.02)

    def test_os_seeding_differs(self):
        native.seed_from_os()
        a = native.uniform(64)
        native.seed_from_os()
        b = native.uniform(64)
        assert not np.array_equal(a, b)


class TestSnappingLaplace:

    def test_outputs_are_multiples_of_lambda(self):
        native.seed(1)
        scale = 3.0  # Lambda = 4
        out = native.snapping_laplace(np.zeros(5000), scale)
        lam = 4.0
        np.testing.assert_allclose(out / lam, np.round(out / lam),
                                   atol=1e-12)

    def test_statistics_match_laplace(self):
        native.seed(2)
        scale = 2.0
        out = native.snapping_laplace(np.full(200_000, 10.0), scale)
        noise = out - 10.0
        # Snapping adds <= Lambda/2 rounding, preserving the moments.
        assert noise.mean() == pytest.approx(0.0, abs=0.05)
        assert noise.std() == pytest.approx(scale * math.sqrt(2),
                                            rel=0.02)

    def test_clamping(self):
        native.seed(3)
        with pytest.warns(UserWarning, match="clamp bound"):
            out = native.snapping_laplace(np.array([1e9, -1e9]), 1.0,
                                          bound=100.0)
        assert out[0] == 100.0 and out[1] == -100.0

    def test_value_plus_noise_not_raw_float(self):
        # The release must NOT equal value + ieee-laplace noise bit
        # pattern: its mantissa below Lambda is zero.
        native.seed(4)
        out = native.snapping_laplace(np.full(100, math.pi), 1.0)
        lam = 1.0
        assert np.all(out == np.round(out / lam) * lam)


class TestDiscreteLaplace:

    def test_integer_noise_distribution(self):
        native.seed(5)
        b = 2.0
        out = native.discrete_laplace(np.zeros(200_000, np.int64), b)
        assert out.dtype == np.int64
        q = math.exp(-1.0 / b)
        # Two-sided geometric: Var = 2q/(1-q)^2.
        assert out.mean() == pytest.approx(0.0, abs=0.05)
        assert out.var() == pytest.approx(2 * q / (1 - q)**2, rel=0.03)
        # P(0) = (1-q)/(1+q).
        p0 = (out == 0).mean()
        assert p0 == pytest.approx((1 - q) / (1 + q), abs=0.01)


class TestDiscreteGaussian:

    def test_integer_noise_distribution(self):
        native.seed(9)
        sigma = 7.5
        out = native.discrete_gaussian(np.zeros(200_000, np.int64), sigma)
        assert out.dtype == np.int64
        # For sigma >> 1 the discrete Gaussian's moments match the
        # continuous one's to O(exp(-2 pi^2 sigma^2)) — far below the
        # sampling error here.
        assert out.mean() == pytest.approx(0.0, abs=0.08)
        assert out.std() == pytest.approx(sigma, rel=0.02)
        # P(0) ~ 1 / (sqrt(2 pi) sigma).
        p0 = (out == 0).mean()
        assert p0 == pytest.approx(1.0 / (math.sqrt(2 * math.pi) * sigma),
                                   abs=0.005)

    def test_small_sigma(self):
        native.seed(10)
        out = native.discrete_gaussian(np.zeros(100_000, np.int64), 0.3)
        # Heavily concentrated at 0; variance matches the theta-function
        # sum, computed directly.
        ks = np.arange(-20, 21)
        w = np.exp(-(ks**2) / (2 * 0.3**2))
        var = float((w * ks**2).sum() / w.sum())
        assert out.var() == pytest.approx(var, rel=0.05)

    def test_sigma_bounds(self):
        with pytest.raises(ValueError):
            native.discrete_gaussian(np.array([0]), 0.0)
        with pytest.raises(ValueError):
            native.discrete_gaussian(np.array([0]), 2.0**41)


class TestSecureGaussian:

    def test_outputs_on_granularity_grid(self):
        native.seed(11)
        sigma = 2.0
        out = native.secure_gaussian(np.full(5000, math.pi), sigma)
        g = 2.0 * 2.0**-40  # lambda_for(2.0) = 2 -> g = 2 * 2^-40
        np.testing.assert_allclose(out / g, np.round(out / g), atol=1e-6)

    def test_statistics_match_gaussian(self):
        native.seed(12)
        sigma = 3.25
        out = native.secure_gaussian(np.full(100_000, 10.0), sigma)
        noise = out - 10.0
        assert noise.mean() == pytest.approx(0.0, abs=0.05)
        assert noise.std() == pytest.approx(sigma, rel=0.02)
        # Normality probe: fourth standardized moment (kurtosis) = 3.
        z = noise / noise.std()
        assert np.mean(z**4) == pytest.approx(3.0, abs=0.15)

    def test_clamping_and_warning(self):
        native.seed(13)
        with pytest.warns(UserWarning, match="clamp bound"):
            out = native.secure_gaussian(np.array([1e9, -1e9]), 1.0,
                                         bound=50.0)
        # Inputs clamp to +/-50 BEFORE noise; the release stays within
        # the bound and within a few sigma of it.
        assert np.all(np.abs(out) <= 50.0)
        assert out[0] == pytest.approx(50.0, abs=6.0)
        assert out[1] == pytest.approx(-50.0, abs=6.0)


class TestHostPathWiring:

    def test_secure_laplace_release_is_snapped(self):
        import pipelinedp_tpu as pdp
        from pipelinedp_tpu import dp_computations
        from pipelinedp_tpu.ops import noise as noise_ops

        params = dp_computations.ScalarNoiseParams(
            eps=1.0, delta=0.0, min_value=0.0, max_value=1.0,
            min_sum_per_partition=None, max_sum_per_partition=None,
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            noise_kind=pdp.NoiseKind.LAPLACE)
        noise_ops.set_secure_host_noise(True)
        try:
            native.seed(6)
            # Integer query (count): exact discrete Laplace — the release
            # is an integer, not a float with noise bits.
            out = dp_computations.compute_dp_count(1000, params)
            assert out == int(out)
            assert out == pytest.approx(1000, abs=30)
            # Float query (sum): snapping mechanism — multiples of Lambda.
            native.seed(7)
            sums = dp_computations.compute_dp_sum(
                np.full(50, 123.456), dp_computations.ScalarNoiseParams(
                    eps=1.0, delta=0.0, min_value=0.0, max_value=200.0,
                    min_sum_per_partition=None, max_sum_per_partition=None,
                    max_partitions_contributed=1,
                    max_contributions_per_partition=1,
                    noise_kind=pdp.NoiseKind.LAPLACE))
            lam = 256.0  # scale = 200 -> Lambda = 256
            np.testing.assert_allclose(np.asarray(sums) / lam,
                                       np.round(np.asarray(sums) / lam),
                                       atol=1e-9)
        finally:
            noise_ops.set_secure_host_noise(False)

    def test_secure_gaussian_release_is_hardened(self):
        import pipelinedp_tpu as pdp
        from pipelinedp_tpu import dp_computations
        from pipelinedp_tpu.ops import noise as noise_ops

        params = dp_computations.ScalarNoiseParams(
            eps=1.0, delta=1e-6, min_value=0.0, max_value=1.0,
            min_sum_per_partition=None, max_sum_per_partition=None,
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            noise_kind=pdp.NoiseKind.GAUSSIAN)
        noise_ops.set_secure_host_noise(True)
        try:
            native.seed(14)
            # Integer query (count): exact discrete Gaussian — integer
            # release.
            out = dp_computations.compute_dp_count(1000, params)
            assert out == int(out)
            assert out == pytest.approx(1000, abs=60)
            # Float query: granularity-snapped discrete Gaussian.
            native.seed(15)
            sums = np.asarray(dp_computations.compute_dp_sum(
                np.full(50, 123.456), dp_computations.ScalarNoiseParams(
                    eps=1.0, delta=1e-6, min_value=0.0, max_value=200.0,
                    min_sum_per_partition=None, max_sum_per_partition=None,
                    max_partitions_contributed=1,
                    max_contributions_per_partition=1,
                    noise_kind=pdp.NoiseKind.GAUSSIAN)))
            sigma = noise_ops.gaussian_sigma(1.0, 1e-6, 200.0)
            g = 2.0**math.ceil(math.log2(sigma)) * 2.0**-40
            np.testing.assert_allclose(sums / g, np.round(sums / g),
                                       atol=1e-5)
        finally:
            noise_ops.set_secure_host_noise(False)

    @pytest.mark.parametrize("noise_kind", ["LAPLACE", "GAUSSIAN"])
    def test_secure_mode_fused_engine_matches_oracle(self, noise_kind,
                                                     monkeypatch):
        """Secure host noise enabled end to end on the fused plane, both
        noise kinds: at huge eps the hardened release still matches the
        exact aggregates (the snapping/granularity grids shrink with the
        noise scale, so no precision is lost). The engine must run with
        rng_seed=None — a seeded reproducible rng bypasses the hardened
        path by design — so the test also counts the native calls to
        prove the hardened samplers actually released the metrics."""
        import pipelinedp_tpu as pdp
        from pipelinedp_tpu.backends import JaxBackend
        from pipelinedp_tpu.ops import noise as noise_ops

        calls = {"int": 0, "float": 0}
        int_fn = (native.discrete_laplace if noise_kind == "LAPLACE"
                  else native.discrete_gaussian)
        float_fn = (native.snapping_laplace if noise_kind == "LAPLACE"
                    else native.secure_gaussian)

        def count_int(vals_, scale, **kw):
            calls["int"] += 1
            return int_fn(vals_, scale, **kw)

        def count_float(vals_, scale, **kw):
            calls["float"] += 1
            return float_fn(vals_, scale, **kw)

        monkeypatch.setattr(
            native,
            "discrete_laplace" if noise_kind == "LAPLACE"
            else "discrete_gaussian", count_int)
        monkeypatch.setattr(
            native,
            "snapping_laplace" if noise_kind == "LAPLACE"
            else "secure_gaussian", count_float)

        rng = np.random.default_rng(16)
        n = 2000
        vals = rng.uniform(0.0, 10.0, n)
        pk = rng.integers(0, 5, n)
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n),
                              partition_keys=pk, values=vals)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN],
            max_partitions_contributed=5,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=10.0,
            noise_kind=getattr(pdp.NoiseKind, noise_kind))
        noise_ops.set_secure_host_noise(True)
        try:
            native.seed(16)
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e12,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend())
            res = engine.aggregate(ds, params, pdp.DataExtractors(),
                                   public_partitions=list(range(5)))
            acc.compute_budgets()
            got = dict(res)
        finally:
            noise_ops.set_secure_host_noise(False)
        # COUNT releases through the integer sampler, SUM (and MEAN's
        # normalized sum) through the float one.
        assert calls["int"] >= 1 and calls["float"] >= 1
        for p in range(5):
            mask = pk == p
            assert got[p].count == pytest.approx(mask.sum(), rel=1e-3)
            assert got[p].sum == pytest.approx(vals[mask].sum(), rel=1e-3)
            assert got[p].mean == pytest.approx(vals[mask].mean(),
                                                rel=1e-3)

    def test_clamp_warning_on_oversized_release(self):
        with pytest.warns(UserWarning, match="clamp bound"):
            native.snapping_laplace(np.array([1e20]), 1e-6)

    def test_small_scale_keeps_large_release_range(self):
        # scale 1e-6 must not shrink the clamp below realistic values.
        native.seed(8)
        out = native.snapping_laplace(np.array([2.0e8]), 1e-6)
        assert out[0] == pytest.approx(2.0e8, rel=1e-6)

    def test_disabled_by_default(self):
        from pipelinedp_tpu.ops import noise as noise_ops
        assert not noise_ops.secure_host_noise_enabled()


class TestFactorize:
    """The native hash factorizer must be bit-identical to
    np.unique(return_inverse=True)."""

    @pytest.mark.skipif(not native.encode_available(),
                        reason="native toolchain unavailable")
    @pytest.mark.parametrize("gen", [
        lambda rng: rng.integers(-1000, 1000, 10_000),
        lambda rng: rng.integers(0, 2**62, 10_000),       # wide range
        lambda rng: rng.integers(0, 50, 100_000),         # heavy duplicates
        lambda rng: rng.integers(0, 2**62, 2_000_000),    # big + wide
        lambda rng: np.array([7]),                        # single element
        lambda rng: np.array([5, 5, 5, 5]),               # one unique
    ])
    def test_matches_np_unique(self, gen):
        rng = np.random.default_rng(0)
        arr = gen(rng).astype(np.int64)
        uniq, inv = native.factorize_i64(arr)
        exp_uniq, exp_inv = np.unique(arr, return_inverse=True)
        np.testing.assert_array_equal(uniq, exp_uniq)
        np.testing.assert_array_equal(inv, exp_inv)
        np.testing.assert_array_equal(uniq[inv], arr)

    @pytest.mark.skipif(not native.encode_available(),
                        reason="native toolchain unavailable")
    def test_empty(self):
        uniq, inv = native.factorize_i64(np.array([], np.int64))
        assert uniq.size == 0 and inv.size == 0

    @pytest.mark.skipif(not native.encode_available(),
                        reason="native toolchain unavailable")
    def test_uint64_above_int64_max_rejected(self):
        with pytest.raises(ValueError, match="wrap"):
            native.factorize_i64(np.array([2**63 + 5, 3], np.uint64))

    def test_unique_inverse_helper_matches(self):
        # The engine helper must agree with np.unique regardless of
        # whether the native path engaged.
        from pipelinedp_tpu.jax_engine import _unique_inverse
        rng = np.random.default_rng(1)
        for arr in (rng.integers(0, 2**40, 50_000),
                    rng.integers(-5, 5, 1000).astype(np.int32),
                    np.array([2**63 + 5, 3, 2**63 + 5], np.uint64),
                    rng.random(1000)):  # float: always numpy path
            uniq, inv = _unique_inverse(np.asarray(arr))
            exp_u, exp_i = np.unique(arr, return_inverse=True)
            np.testing.assert_array_equal(uniq, exp_u)
            np.testing.assert_array_equal(inv, exp_i)
            assert inv.dtype == np.int32


def _shard_available():
    try:
        native._shard_lib()
        return True
    except native.NativeUnavailableError:
        return False


def _shard_rows_reference(pid, columns, n_dev):
    """What the mesh re-shard did before the counting scatter: hash,
    stable argsort of the shard ids, then per-shard padded copies."""
    from pipelinedp_tpu.jax_engine import _pad_rows
    from pipelinedp_tpu.ops.segment import fmix32
    shard = (fmix32(pid.astype(np.uint32)) % np.uint32(n_dev)).astype(
        np.int32)
    order = np.argsort(shard, kind="stable")
    counts = np.bincount(shard, minlength=n_dev)
    per_shard = _pad_rows(int(counts.max()) if len(pid) else 1)
    out = []
    for col in columns:
        fill = False if col.dtype == bool else 0
        dst = np.full((n_dev * per_shard,) + col.shape[1:], fill, col.dtype)
        offset = 0
        for d in range(n_dev):
            rows = order[offset:offset + counts[d]]
            dst[d * per_shard:d * per_shard + counts[d]] = col[rows]
            offset += counts[d]
        out.append(dst)
    return out, counts, per_shard


@pytest.mark.skipif(not _shard_available(),
                    reason="native toolchain unavailable")
class TestShardRows:
    """The native counting scatter of the mesh re-shard must be
    bit-identical to a stable argsort of the shard ids followed by
    per-shard padded copies, pads and counts included."""

    @staticmethod
    def _columns(pid, rng):
        n = len(pid)
        return [pid,
                rng.integers(0, 17770, n).astype(np.int32),
                rng.random(n).astype(np.float32),
                rng.random(n) < 0.7,
                rng.random((n, 3)).astype(np.float32)]

    def _check(self, pid, n_dev, rng):
        from pipelinedp_tpu.jax_engine import _pad_rows
        columns = self._columns(pid, rng)
        got, counts, per_shard = native.shard_rows(pid, columns, n_dev,
                                                   _pad_rows)
        want, want_counts, want_per_shard = _shard_rows_reference(
            pid, columns, n_dev)
        assert per_shard == want_per_shard
        np.testing.assert_array_equal(counts, want_counts)
        assert counts.sum() == len(pid)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        for g in got:  # the pad tail of every shard is zero / False
            for d in range(n_dev):
                tail = g[d * per_shard + counts[d]:(d + 1) * per_shard]
                assert not tail.any()
        return counts

    @pytest.mark.parametrize("n", [0, 1, 8191, 8192, 8193, 100_003])
    @pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
    def test_matches_stable_argsort(self, n_dev, n):
        rng = np.random.default_rng(n * 10 + n_dev)
        pid = rng.integers(0, 480_189, n).astype(np.int32)
        self._check(pid, n_dev, rng)

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_one_pid_fills_one_shard(self, n_dev):
        rng = np.random.default_rng(n_dev)
        pid = np.full(20_000, 12345, np.int32)
        counts = self._check(pid, n_dev, rng)
        assert sorted(counts.tolist())[-1] == 20_000

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_shared_residue_class_spreads(self, n_dev):
        # Ids that are all multiples of n_dev still spread: the hash
        # runs before the modulo.
        rng = np.random.default_rng(7)
        pid = (rng.integers(0, 100_000, 50_000) * n_dev).astype(np.int32)
        counts = self._check(pid, n_dev, rng)
        assert (counts > 0).all()

    def test_negative_and_wide_pids(self):
        # Negative int32 ids read as their uint32 bits; int64 ids wrap
        # to uint32, as the reference's astype does.
        rng = np.random.default_rng(11)
        self._check(rng.integers(-2**31, 2**31 - 1, 30_000,
                                 dtype=np.int64).astype(np.int32), 4, rng)
        self._check(rng.integers(0, 2**40, 30_000), 4, rng)

    def test_more_than_256_shards_refused(self):
        from pipelinedp_tpu.jax_engine import _pad_rows
        pid = np.arange(10, dtype=np.int32)
        with pytest.raises(native.NativeUnavailableError, match="256"):
            native.shard_rows(pid, [pid], 257, _pad_rows)


@pytest.mark.parametrize("n_dev", [1, 4, 257])
def test_reshard_numpy_fallback_matches(n_dev, monkeypatch):
    # The re-shard's numpy path (library unavailable, or more shards
    # than a byte holds) gives the same arrays. It needs no toolchain.
    from pipelinedp_tpu.parallel.sharded import reshard_rows

    def unavailable():
        raise native.NativeUnavailableError("no toolchain")

    rng = np.random.default_rng(n_dev)
    pid = rng.integers(0, 480_189, 40_000).astype(np.int32)
    columns = TestShardRows._columns(pid, rng)
    want, want_counts, want_per_shard = _shard_rows_reference(
        pid, columns, n_dev)
    monkeypatch.setattr(native, "_shard_lib", unavailable)
    got, counts, per_shard = reshard_rows(pid, columns, n_dev)
    assert per_shard == want_per_shard
    np.testing.assert_array_equal(counts, want_counts)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
