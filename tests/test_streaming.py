"""Streaming (multi-batch) fused-plane tests.

Strategy: force tiny chunks via the ``PIPELINEDP_TPU_STREAM_CHUNK`` env
knob so ordinary-size datasets stream through many batches, then apply
the same differential discipline as ``test_jax_engine``: at huge eps the
streamed result must match the LocalBackend oracle / exact aggregates
partition by partition, across metric combinations, bounding modes and
selection regimes. The chunked execution must be observable
(``timings["stream_batches"] > 1``) so these tests can't silently pass
through the single-batch path.
"""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import streaming
from pipelinedp_tpu.backends import JaxBackend

BIG_EPS = 1e12


@pytest.fixture(autouse=True)
def tiny_chunks(monkeypatch):
    monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "997")


def run_streamed(ds, params, public=None, eps=BIG_EPS, delta=1e-2,
                 seed=0):
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed))
    res = engine.aggregate(ds, params, pdp.DataExtractors(),
                           public_partitions=public)
    acc.compute_budgets()
    got = dict(res)
    assert res.timings.get("stream_batches", 0) > 1, (
        "dataset did not stream — test is not covering the chunked path")
    return got


def make_ds(rng, n=12_000, users=2_000, parts=15):
    return pdp.ArrayDataset(privacy_ids=rng.integers(0, users, n),
                            partition_keys=rng.integers(0, parts, n),
                            values=rng.uniform(0.0, 10.0, n)), parts


class TestStreamedDifferential:
    """Huge-eps, non-binding caps: streamed == exact, per partition."""

    def test_count_sum_mean_variance_pid_count(self):
        rng = np.random.default_rng(1)
        ds, parts = make_ds(rng)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                     pdp.Metrics.VARIANCE, pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params)
        pk = ds.partition_keys
        vals = ds.values
        pid = ds.privacy_ids
        assert len(got) == parts
        for p in range(parts):
            m = pk == p
            assert got[p].count == pytest.approx(m.sum(), abs=0.5)
            assert got[p].sum == pytest.approx(vals[m].sum(), rel=1e-5)
            assert got[p].mean == pytest.approx(vals[m].mean(), abs=1e-4)
            assert got[p].variance == pytest.approx(vals[m].var(),
                                                    abs=1e-2)
            assert got[p].privacy_id_count == pytest.approx(
                len(np.unique(pid[m])), abs=0.5)

    def test_matches_single_batch_aggregates(self):
        """Same dataset through the single-batch kernel (big chunk) and
        the streamed path: deterministic aggregates identical at huge
        eps, regardless of the different batch structure."""
        rng = np.random.default_rng(2)
        ds, parts = make_ds(rng, n=8_000)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        streamed = run_streamed(ds, params, public=list(range(parts)))

        import os
        os.environ["PIPELINEDP_TPU_STREAM_CHUNK"] = str(1 << 26)
        ds.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=BIG_EPS,
                                        total_delta=1e-2)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=list(range(parts)))
        acc.compute_budgets()
        single = dict(res)
        for p in range(parts):
            assert streamed[p].count == pytest.approx(single[p].count,
                                                      abs=1e-3)
            assert streamed[p].sum == pytest.approx(single[p].sum,
                                                    rel=1e-5)

    def test_per_partition_bounds_mode(self):
        rng = np.random.default_rng(3)
        ds, parts = make_ds(rng)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_sum_per_partition=0.0, max_sum_per_partition=100.0)
        got = run_streamed(ds, params)
        pk, vals = ds.partition_keys, ds.values
        for p in range(parts):
            m = pk == p
            # Quantization grid is bound/2^23 per SEGMENT — keep the
            # clip bound realistic or the grid dominates the check.
            assert got[p].sum == pytest.approx(vals[m].sum(), rel=1e-4)

    def test_total_cap_bounding_invariants(self):
        """max_contributions binding: the per-pid sample differs between
        planes, so check invariants — global kept rows = sum over pids of
        min(rows, cap)."""
        rng = np.random.default_rng(4)
        n = 10_000
        pid = rng.integers(0, 300, n)  # ~33 rows/pid, cap at 10
        ds = pdp.ArrayDataset(privacy_ids=pid,
                              partition_keys=rng.integers(0, 8, n),
                              values=rng.uniform(0, 10, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], max_contributions=10)
        got = run_streamed(ds, params, public=list(range(8)))
        expect = sum(min(c, 10) for c in np.bincount(pid))
        total = sum(m.count for m in got.values())
        assert total == pytest.approx(expect, rel=1e-3)

    def test_bounds_already_enforced(self):
        rng = np.random.default_rng(5)
        n = 9_000
        pk = rng.integers(0, 6, n)
        vals = rng.uniform(0, 5, n)
        ds = pdp.ArrayDataset(privacy_ids=None, partition_keys=pk,
                              values=vals)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=6,
            max_contributions_per_partition=3,
            min_value=0.0, max_value=5.0,
            contribution_bounds_already_enforced=True)
        got = run_streamed(ds, params, public=list(range(6)))
        for p in range(6):
            m = pk == p
            assert got[p].count == pytest.approx(m.sum(), abs=0.5)
            assert got[p].sum == pytest.approx(vals[m].sum(), rel=1e-5)

    def test_vector_sum(self):
        rng = np.random.default_rng(6)
        n = 6_000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 1000, n),
            partition_keys=rng.integers(0, 4, n),
            values=rng.uniform(-1, 1, (n, 3)))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=4,
            max_contributions_per_partition=20,
            vector_size=3, vector_max_norm=100.0,
            vector_norm_kind=pdp.NormKind.Linf)
        got = run_streamed(ds, params, public=list(range(4)))
        for p in range(4):
            m = ds.partition_keys == p
            np.testing.assert_allclose(np.asarray(got[p].vector_sum),
                                       ds.values[m].sum(axis=0),
                                       rtol=1e-4, atol=1e-3)

    def test_private_selection_drops_small_partitions(self):
        """Selection statistics survive the streamed nseg accumulation:
        big partitions kept, single-user partitions dropped at modest
        eps."""
        rng = np.random.default_rng(7)
        n = 8_000
        pid = np.arange(n)  # every row its own user
        pk = np.where(np.arange(n) < 7_800,
                      rng.integers(0, 4, n), 4 + np.arange(n) % 150)
        ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                              values=None)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=1,
            max_contributions_per_partition=1)
        got = run_streamed(ds, params, eps=5.0, delta=1e-5)
        kept = set(got)
        assert {0, 1, 2, 3} <= kept  # ~1950 users each: always kept
        # the ~150 single/double-user partitions are overwhelmingly
        # dropped
        assert len(kept - {0, 1, 2, 3}) < 20


class TestStreamedPercentiles:
    """Percentiles stream in two passes (mid histogram + chosen-subtree
    leaf histograms, both additive across batches); the walk math and
    PRNG node-noise keying are shared with the single-batch kernel."""

    def test_matches_exact_at_huge_eps(self):
        rng = np.random.default_rng(20)
        n = 18_000
        vals = rng.uniform(0, 10, n)
        pk = rng.integers(0, 6, n)
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 4_000, n),
                              partition_keys=pk, values=vals)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90),
                     pdp.Metrics.VARIANCE, pdp.Metrics.COUNT],
            max_partitions_contributed=6,
            max_contributions_per_partition=30,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params)
        for p in range(6):
            m = pk == p
            e50, e90 = np.percentile(vals[m], [50, 90])
            assert got[p].percentile_50 == pytest.approx(e50, abs=0.15)
            assert got[p].percentile_90 == pytest.approx(e90, abs=0.15)
            assert got[p].variance == pytest.approx(vals[m].var(),
                                                    abs=0.05)
            assert got[p].count == pytest.approx(m.sum(), abs=0.5)

    def test_histograms_are_exactly_additive(self, monkeypatch):
        """The precision claim ("the streamed walk sees the same exact
        histograms") asserted EXACTLY: the mid-level histogram and the
        subtree leaf histograms accumulated over many tiny batches equal
        the single-batch computation bit-for-bit (non-binding caps, so
        bounding keeps every row on both sides)."""
        import jax
        import jax.numpy as jnp
        from pipelinedp_tpu import jax_engine as je
        from pipelinedp_tpu import streaming as sm

        rng = np.random.default_rng(30)
        n = 6_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 1_500, n),
                              partition_keys=rng.integers(0, 4, n),
                              values=rng.uniform(0, 10, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50),
                     pdp.Metrics.PERCENTILE(90)],
            max_partitions_contributed=4,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        config = je.FusedConfig.from_params(params, public=True)
        encoded = je._encode_arrays(ds, None, list(range(4)),
                                    require_pid=True)
        P_pad = je._pad_pow2(len(encoded.pk_vocab))
        key = jax.random.PRNGKey(5)
        _, _, n_mid, span = sm._tree_consts()
        sub_start = jnp.asarray(
            (np.arange(P_pad)[:, None] % 4 * span * np.ones(
                (1, 2))).astype(np.int32))

        def run_chunks(chunk):
            # batch count derived directly; this test drives the kernels
            # below the engine's env-var chunk mechanism
            n_batches = max(1, -(-n // chunk))
            order, counts = sm._batch_assignment(config, encoded,
                                                 n_batches, 5)
            pad_rows = je._pad_rows(int(counts.max()))
            mid_acc = None
            sub_acc = None
            offset = 0
            for b in range(n_batches):
                cnt = int(counts[b, 0])
                rows = (slice(offset, offset + cnt) if order is None
                        else order[offset:offset + cnt])
                offset += cnt
                pid_b = np.zeros(pad_rows, np.int32)
                pk_b = np.zeros(pad_rows, np.int32)
                pid_b[:cnt] = encoded.pid[rows]
                pk_b[:cnt] = encoded.pk[rows]
                vals_b = np.zeros(pad_rows, np.float32)
                vals_b[:cnt] = encoded.values[rows]
                planes = (je._narrow_ids(pid_b, "u16") +
                          je._narrow_ids(pk_b, "u16"))
                kb = jax.random.fold_in(jax.random.PRNGKey(5), b)
                _, _, mid = sm._partials_kernel(
                    config, P_pad, planes, jnp.asarray(vals_b),
                    jnp.int32(cnt), kb, 12, n_pid_planes=len(planes) - 1)
                sub = sm._pct_sub_kernel(
                    config, P_pad, planes, jnp.asarray(vals_b),
                    jnp.int32(cnt), kb, 12,
                    n_pid_planes=len(planes) - 1, sub_start=sub_start,
                    p_offset=jnp.int32(0), n_block=P_pad)
                mid_acc = mid if mid_acc is None else mid_acc + mid
                sub_acc = sub if sub_acc is None else sub_acc + sub
            return np.asarray(mid_acc), np.asarray(sub_acc)

        # Caps non-binding -> bounding keeps every row regardless of the
        # per-batch sampling keys, so one batch and 10 batches must
        # produce IDENTICAL integer histograms.
        mid_many, sub_many = run_chunks(599)
        mid_one, sub_one = run_chunks(1 << 26)
        np.testing.assert_array_equal(mid_many, mid_one)
        np.testing.assert_array_equal(sub_many, sub_one)
        assert int(mid_one.sum()) == n  # every row counted exactly once

    def test_walk_parity_with_single_batch(self, monkeypatch):
        """Same seed, non-binding caps: the streamed walk sees the same
        exact histograms (pinned bit-exactly by
        ``test_histograms_are_exactly_additive``) and the same
        (pk, node)-keyed noise as the single-batch walk. The two walks
        are separate XLA programs whose codegen (FMA fusion) may differ
        in the last float32 bit; when a noisy rank comparison sits
        within an ulp of a child boundary that last bit can flip the
        picked child — the same tie quirk ``TestFusedPercentile``
        documents — and a flip at the top level diverges by up to a
        4096-leaf parent width (~0.63 on [0, 10]), hence the loose
        value tolerance here; the precision burden lives in the
        histogram test above."""
        rng = np.random.default_rng(21)
        n = 10_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 2_500, n),
                              partition_keys=rng.integers(0, 4, n),
                              values=rng.uniform(0, 10, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50),
                     pdp.Metrics.PERCENTILE(95)],
            max_partitions_contributed=4,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)

        def run_with_chunk(chunk):
            monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", str(chunk))
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=3.0,
                                            total_delta=1e-6)
            eng = pdp.DPEngine(acc, JaxBackend(rng_seed=7))
            res = eng.aggregate(ds, params, pdp.DataExtractors(),
                                public_partitions=list(range(4)))
            acc.compute_budgets()
            return dict(res), res.timings.get("stream_batches", 0)

        streamed, nb = run_with_chunk(997)
        single, nb2 = run_with_chunk(1 << 26)
        assert nb > 5 and nb2 == 0
        for p in range(4):
            assert streamed[p].percentile_50 == pytest.approx(
                single[p].percentile_50, abs=0.7)
            assert streamed[p].percentile_95 == pytest.approx(
                single[p].percentile_95, abs=0.7)

    def test_tiny_subhist_cap_chunks_quantiles(self, monkeypatch):
        """Past _SUBHIST_BYTE_CAP, pass B walks quantile GROUPS instead
        of refusing — and because node noise is a pure function of
        (partition, node id), the chunked walk must be BIT-IDENTICAL to
        the unchunked one."""
        from pipelinedp_tpu import jax_engine as je
        rng = np.random.default_rng(88)
        n = 5_000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 1_200, n),
            partition_keys=rng.integers(0, 5, n),
            values=rng.uniform(0.0, 20.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(25), pdp.Metrics.PERCENTILE(50),
                     pdp.Metrics.PERCENTILE(75), pdp.Metrics.PERCENTILE(95)],
            max_partitions_contributed=5,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=20.0)

        def run(want_rounds):
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=BIG_EPS,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=7))
            res = engine.aggregate(ds, params, pdp.DataExtractors(),
                                   public_partitions=list(range(5)))
            acc.compute_budgets()
            got = dict(res)
            assert res.timings["stream_batches"] > 1
            # Guard against the test going vacuous: the chunking must
            # actually have happened (or actually not have).
            assert res.timings["stream_pass_b_rounds"] == want_rounds
            return got

        full = run(want_rounds=1)
        # Cap fits exactly ONE quantile's [P_pad, 1, span] block ->
        # 4 pass-B rounds.
        _, _, _, span = streaming._tree_consts()
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 8 * span * 4)
        chunked = run(want_rounds=4)
        for p in range(5):
            for f in ("percentile_25", "percentile_50", "percentile_75",
                      "percentile_95"):
                assert getattr(chunked[p], f) == getattr(full[p], f), (
                    p, f)
        # A cap below even ONE quantile's [P_pad, 1, span] block now
        # partition-block-chunks instead of refusing: blocks of 4
        # partitions (P_pad = 8) x 4 single-quantile groups = 8 rounds,
        # still bit-identical (node noise is keyed by the GLOBAL
        # partition id).
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 4 * span * 4)
        p_chunked = run(want_rounds=8)
        for p in range(5):
            for f in ("percentile_25", "percentile_50", "percentile_75",
                      "percentile_95"):
                assert getattr(p_chunked[p], f) == getattr(full[p], f), (
                    p, f)
        # Only a cap below a single [1, 1, span] block is refused.
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 4)
        with pytest.raises(NotImplementedError, match="subtree block"):
            run(want_rounds=0)

    def test_pass_b_reship_matches_device_cache(self, monkeypatch):
        """Pass B over the device-resident batch cache and pass B
        re-shipping every batch must produce IDENTICAL percentiles
        (same (b, arrays) -> same kernels), and both sources must be
        observable in timings."""
        rng = np.random.default_rng(77)
        n = 6_000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 1_500, n),
            partition_keys=rng.integers(0, 6, n),
            values=rng.uniform(0.0, 50.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(95)],
            max_partitions_contributed=6,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=50.0)

        def run(cache_bytes):
            monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CACHE",
                               str(cache_bytes))
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=BIG_EPS,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=3))
            res = engine.aggregate(ds, params, pdp.DataExtractors(),
                                   public_partitions=list(range(6)))
            acc.compute_budgets()
            got = dict(res)
            assert res.timings["stream_batches"] > 1
            return got, res.timings["stream_pass_b"]

        cached, src_c = run(1 << 30)
        reshipped, src_r = run(0)
        assert src_c == "device_cache" and src_r == "reship"
        for p in range(6):
            assert cached[p].percentile_50 == reshipped[p].percentile_50
            assert cached[p].percentile_95 == reshipped[p].percentile_95

    def test_private_selection_with_percentiles(self):
        rng = np.random.default_rng(22)
        n = 8_000
        pk = rng.integers(0, 5, n)
        vals = rng.uniform(0, 10, n)
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n),
                              partition_keys=pk, values=vals)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50)],
            max_partitions_contributed=5,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params, eps=1e6, delta=1e-3)
        assert set(got) == set(range(5))
        for p in range(5):
            assert got[p].percentile_50 == pytest.approx(
                np.percentile(vals[pk == p], 50), abs=0.2)


class TestStreamedSelectPartitions:

    def test_select_partitions_streams(self):
        rng = np.random.default_rng(10)
        n = 9_000
        pk = np.concatenate([rng.integers(0, 6, n - 60),
                             6 + np.arange(60) % 30])
        ds = pdp.ArrayDataset(privacy_ids=np.arange(n),
                              partition_keys=pk, values=None)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
        res = engine.select_partitions(
            ds, pdp.SelectPartitionsParams(max_partitions_contributed=3),
            pdp.DataExtractors())
        acc.compute_budgets()
        kept = set(res)
        # ~1500-user partitions always keep; 1-2-user tails drop.
        assert {0, 1, 2, 3, 4, 5} <= kept
        assert len(kept - {0, 1, 2, 3, 4, 5}) < 10


class TestStreamedFuzz:
    """Randomized parameter points through the streamed path (the
    streaming analogue of test_differential_fuzz): huge eps, non-binding
    caps, public partitions — streamed results must equal the exact
    aggregates partition by partition."""

    @pytest.mark.parametrize("seed", range(6))
    def test_random_config(self, seed):
        rng = np.random.default_rng(2000 + seed)
        n = int(rng.integers(4_000, 15_000))
        users = int(rng.integers(200, 3_000))
        parts = int(rng.integers(3, 25))
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, users, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(0.0, 10.0, n))
        combos = [
            [pdp.Metrics.COUNT],
            [pdp.Metrics.SUM, pdp.Metrics.COUNT],
            [pdp.Metrics.MEAN, pdp.Metrics.VARIANCE],
            [pdp.Metrics.PRIVACY_ID_COUNT, pdp.Metrics.SUM],
        ]
        metrics = combos[int(rng.integers(0, len(combos)))]
        params = pdp.AggregateParams(
            metrics=metrics,
            noise_kind=(pdp.NoiseKind.LAPLACE if rng.random() < 0.5
                        else pdp.NoiseKind.GAUSSIAN),
            max_partitions_contributed=parts,
            max_contributions_per_partition=200,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params, public=list(range(parts)),
                           seed=seed)
        pk, vals = ds.partition_keys, ds.values
        for p in range(parts):
            m = pk == p
            if pdp.Metrics.COUNT in metrics:
                assert got[p].count == pytest.approx(m.sum(), abs=0.5)
            if pdp.Metrics.SUM in metrics:
                assert got[p].sum == pytest.approx(vals[m].sum(),
                                                   rel=1e-4, abs=0.1)
            if pdp.Metrics.MEAN in metrics:
                assert got[p].mean == pytest.approx(vals[m].mean(),
                                                    abs=1e-3)
            if pdp.Metrics.VARIANCE in metrics:
                assert got[p].variance == pytest.approx(vals[m].var(),
                                                        abs=0.05)
            if pdp.Metrics.PRIVACY_ID_COUNT in metrics:
                assert got[p].privacy_id_count == pytest.approx(
                    len(np.unique(ds.privacy_ids[m])), abs=0.5)


class TestStreamedOnMesh:
    """Streaming composed with the device mesh: chunks
    shard over the 8-device CPU mesh, owner-block partials fold into
    the same host accumulators, results match the oracle."""

    def _mesh_backend(self, seed=0):
        from pipelinedp_tpu.parallel import make_mesh
        return JaxBackend(rng_seed=seed, mesh=make_mesh())

    def run_mesh_streamed(self, ds, params, public=None, eps=BIG_EPS,
                          min_batches=3):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                        total_delta=1e-2)
        engine = pdp.DPEngine(acc, self._mesh_backend())
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=public)
        acc.compute_budgets()
        got = dict(res)
        assert res.timings.get("stream_batches", 0) >= min_batches, (
            "dataset did not stream over enough chunks on the mesh")
        return got

    def test_matches_exact_on_mesh(self, monkeypatch):
        """≥3 chunks over the 8-device mesh match the exact aggregates
        (the verdict's Done criterion)."""
        # Mesh chunk budget is per device: 8 devices x 500 rows/chunk
        # over 23k rows -> >= 5 batches.
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        rng = np.random.default_rng(40)
        n, parts = 23_000, 15
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 3_000, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        got = self.run_mesh_streamed(ds, params)
        pk, vals, pid = ds.partition_keys, ds.values, ds.privacy_ids
        assert len(got) == parts
        for p in range(parts):
            m = pk == p
            assert got[p].count == pytest.approx(m.sum(), abs=0.5)
            assert got[p].sum == pytest.approx(vals[m].sum(), rel=1e-5)
            assert got[p].mean == pytest.approx(vals[m].mean(), abs=1e-4)
            assert got[p].privacy_id_count == pytest.approx(
                len(np.unique(pid[m])), abs=0.5)

    def test_percentiles_stream_on_mesh(self, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "400")
        rng = np.random.default_rng(41)
        n = 10_000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 2_000, n),
            partition_keys=rng.integers(0, 4, n),
            values=rng.uniform(0.0, 100.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90)],
            max_partitions_contributed=4,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=100.0)
        got = self.run_mesh_streamed(ds, params, public=list(range(4)))
        for p in range(4):
            true = np.percentile(ds.values[ds.partition_keys == p],
                                 [50, 90])
            assert got[p].percentile_50 == pytest.approx(true[0], abs=0.5)
            assert got[p].percentile_90 == pytest.approx(true[1], abs=0.5)

    def test_private_selection_with_percentiles_on_mesh(self,
                                                        monkeypatch):
        """PRIVATE selection + two-pass percentiles, streamed over the
        mesh: the selection kernel runs (not the public bypass) and the
        kept partitions carry accurate medians. At huge eps selection
        keeps everything it sees — the DROPPING behavior on the mesh
        stream is pinned at moderate eps by
        ``TestStreamedOnMesh.test_select_partitions_streams_on_mesh``."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "400")
        rng = np.random.default_rng(45)
        n = 9_000
        pid = rng.integers(0, 2_500, n)
        pk = np.where(np.arange(n) % 20 < 19, rng.integers(0, 4, n),
                      4 + (np.arange(n) % 150))
        ds = pdp.ArrayDataset(privacy_ids=pid,
                              partition_keys=pk.astype(np.int64),
                              values=rng.uniform(0.0, 40.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50)],
            max_partitions_contributed=4,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=40.0)
        got = self.run_mesh_streamed(ds, params, eps=1e6)
        assert set(range(4)) <= set(got)
        for p in range(4):
            m = pk == p
            true = float(np.percentile(ds.values[m], 50))
            assert got[p].percentile_50 == pytest.approx(true, abs=1.0)

    def test_vector_sum_streams_on_mesh(self, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "300")
        rng = np.random.default_rng(42)
        n, d = 6_000, 3
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 1_500, n),
            partition_keys=rng.integers(0, 5, n),
            values=rng.normal(0.0, 1.0, (n, d)).astype(np.float32))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM], vector_size=d,
            vector_max_norm=1e9,
            vector_norm_kind=pdp.NormKind.Linf,
            max_partitions_contributed=5,
            max_contributions_per_partition=50)
        got = self.run_mesh_streamed(ds, params, public=list(range(5)))
        for p in range(5):
            true = ds.values[ds.partition_keys == p].sum(axis=0)
            np.testing.assert_allclose(got[p].vector_sum, true,
                                       rtol=1e-4, atol=1e-2)

    def test_select_partitions_streams_on_mesh(self, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        rng = np.random.default_rng(43)
        n = 12_000
        # 5 heavy partitions + a long tail of SINGLE-user partitions
        # (every tail row is its own partition): selection at moderate
        # eps must keep the heavy ones AND drop the tail — both sides
        # asserted, so a selection regression that keeps everything
        # cannot pass.
        pid = rng.integers(0, 4_000, n)
        pk = np.where(np.arange(n) % 10 < 9, rng.integers(0, 5, n),
                      5 + np.arange(n))
        ds = pdp.ArrayDataset(privacy_ids=pid,
                              partition_keys=pk.astype(np.int64),
                              values=None)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=10.0,
                                        total_delta=1e-4)
        engine = pdp.DPEngine(acc, self._mesh_backend())
        params = pdp.SelectPartitionsParams(max_partitions_contributed=3)
        res = engine.select_partitions(ds, params, pdp.DataExtractors())
        acc.compute_budgets()
        kept = set(res)
        assert set(range(5)) <= kept
        tail_kept = [p for p in kept if p >= 5]
        # ~1200 single-user partitions; DP selection at eps=10 keeps a
        # single-user partition with vanishing probability (measured: 0
        # kept for this seed; allow a handful of probabilistic strays).
        assert len(tail_kept) <= 5, tail_kept

    def test_mesh_streamed_matches_single_device_streamed(self,
                                                          monkeypatch):
        """Same seed, same dataset: mesh streaming and single-device
        streaming agree exactly on the deterministic aggregates at huge
        eps with non-binding caps (different bounding subsample is
        irrelevant when nothing is dropped)."""
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "600")
        rng = np.random.default_rng(44)
        n, parts = 11_000, 8
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 2_500, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        mesh_got = self.run_mesh_streamed(ds, params,
                                          public=list(range(parts)))
        ds.invalidate_cache()
        single_got = run_streamed(ds, params, public=list(range(parts)))
        for p in range(parts):
            assert mesh_got[p].count == pytest.approx(
                single_got[p].count, abs=1e-3)
            assert mesh_got[p].sum == pytest.approx(
                single_got[p].sum, rel=1e-5)


class TestStreamingInternals:

    def test_pid_batches_are_disjoint(self):
        """Every privacy unit's rows land in exactly one batch."""
        from pipelinedp_tpu import jax_engine as je
        rng = np.random.default_rng(8)
        n = 5_000
        pid = rng.integers(0, 400, n)
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
            order, counts = streaming._batch_assignment(config, enc, 7,
                                                        123, n_dev)
            assert counts.shape == (7, n_dev)
            seen = {}
            offset = 0
            for cell, c in enumerate(counts.ravel()):
                # A unit's rows must stay within ONE (batch, shard) cell.
                cell_pids = set(
                    pid[order[offset:offset + int(c)]].tolist())
                for u in cell_pids:
                    assert seen.setdefault(u, cell) == cell
                offset += int(c)
            assert offset == n

    def test_wide_id_space_streams_exactly(self):
        """Privacy ids >= 2^24 force the "i32" plane spec, whose narrow
        planes ARE the reused staging buffer — the ship path must copy
        them (the delayed fold means the previous batch's kernel may
        still be reading its input when the next batch stages)."""
        from pipelinedp_tpu import jax_engine as je
        rng = np.random.default_rng(55)
        n = 9_000
        pid = rng.integers((1 << 24) + 1, 1 << 30, n)
        ds = pdp.ArrayDataset(
            privacy_ids=pid,
            partition_keys=rng.integers(0, 10, n),
            values=rng.uniform(0.0, 10.0, n))
        enc = je.encode(ds, pdp.DataExtractors(), None, None)
        # The guard must hold on the ENCODED ids (what ships): if a
        # future encode densifies pids this test must fail loudly
        # rather than silently stop covering the i32 path.
        assert je._plane_spec(int(enc.pid.max())) == "i32"
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=10,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params, public=list(range(10)))
        for p in range(10):
            m = ds.partition_keys == p
            assert got[p].count == pytest.approx(m.sum(), abs=0.5)
            assert got[p].sum == pytest.approx(ds.values[m].sum(),
                                               rel=1e-5)

    def test_chunk_target_capped_by_lane_capacity(self, monkeypatch):
        """A big mesh must not scale value-config batches past the
        global fixed-point lane capacity (the psum makes lane capacity
        a per-batch GLOBAL bound) — and the capped target must itself
        be plannable."""
        from pipelinedp_tpu import jax_engine as je
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", str(1 << 26))
        value_config = je.FusedConfig.from_params(
            pdp.AggregateParams(
                metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
                max_contributions_per_partition=1, min_value=0.0,
                max_value=1.0), public=True)
        count_config = je.FusedConfig.from_params(
            pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT],
                max_partitions_contributed=1,
                max_contributions_per_partition=1), public=True)
        capped = streaming.chunk_target_rows(value_config, 8)
        assert capped <= je._fx_max_rows() < (1 << 26) * 8
        je._fx_plan(capped)  # must not raise
        assert streaming.chunk_target_rows(count_config, 8) == (1 << 26) * 8
        # Count columns are int32 psums: a giant mesh must not form a
        # batch that could wrap them.
        assert streaming.chunk_target_rows(count_config, 64) < (1 << 31)
        # And therefore: every row count above the single-batch lane cap
        # streams on a mesh — no dead zone between the caps.
        class _FakeMesh:
            class devices:
                size = 8
        assert streaming.should_stream(value_config,
                                       je._fx_max_rows() + 1, _FakeMesh)

    def test_exact_lane_accumulation_across_batches(self):
        """Adversarial equal values summed across many batches stay
        exact (float32 single-batch accumulation would drift)."""
        n = 30_000
        ds = pdp.ArrayDataset(
            privacy_ids=np.arange(n) % 5_000,
            partition_keys=np.zeros(n, np.int64),
            values=np.full(n, 7.25))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=1,
            max_contributions_per_partition=10,
            min_value=0.0, max_value=10.0)
        got = run_streamed(ds, params, public=[0])
        assert got[0].sum == pytest.approx(7.25 * n, rel=1e-6)

    def test_count_only_streams_past_lane_plan(self, monkeypatch):
        """Streaming must never consult the single-batch lane plan for
        pipelines with no fixed-point columns."""
        from pipelinedp_tpu import jax_engine as je
        monkeypatch.setattr(
            je, "_fx_plan",
            lambda n: (_ for _ in ()).throw(AssertionError("no plan")))
        rng = np.random.default_rng(9)
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 500, 4_000),
                              partition_keys=rng.integers(0, 5, 4_000),
                              values=None)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], max_partitions_contributed=5,
            max_contributions_per_partition=20)
        got = run_streamed(ds, params, public=list(range(5)))
        assert len(got) == 5
