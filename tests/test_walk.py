"""Quantile-walk fast-path tests (PR 3).

Covers the counter-based node-noise generator (``ops/counter_rng.py``):
correctness against JAX's own threefry, purity in the (partition, node)
indices, calibrated statistical moments; the three-way bit-parity of
the single-batch, owner-sharded-mesh and streamed walks; the
partition-block-chunked walks (single-batch and streamed, straddling a
shrunken ``_SUBHIST_BYTE_CAP``); the extreme-scale guard cliffs at
their EXACT boundaries via the injectable cap seams; and the lint
banning new ``vmap(...fold_in...)`` per-element key constructions.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu import streaming
from pipelinedp_tpu.aggregate_params import NoiseKind
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ops import counter_rng

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCounterRng:
    """The counter-based generator itself."""

    def test_threefry_matches_jax_internal(self):
        """Our batched Threefry-2x32 must be the SAME cipher JAX's own
        key system uses (same rotation schedule, same key injection) —
        pinned against the internal reference implementation."""
        from jax._src import prng as jax_prng

        rng = np.random.default_rng(0)
        k = rng.integers(0, 2**32, 2, dtype=np.uint32)
        c = rng.integers(0, 2**32, 64, dtype=np.uint32)
        ref = np.asarray(jax_prng.threefry_2x32(jnp.asarray(k),
                                                jnp.asarray(c)))
        h0, h1 = counter_rng.threefry2x32(
            jnp.uint32(k[0]), jnp.uint32(k[1]),
            jnp.asarray(c[:32]), jnp.asarray(c[32:]))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(h0), np.asarray(h1)]), ref)

    def test_node_noise_pure_in_indices(self):
        """The memoization contract: a (partition, node) pair draws the
        same noise wherever and however often it appears — sliced
        blocks, duplicated node ids across quantiles, and the root
        broadcast are all bit-exact restructurings."""
        key = jax.random.PRNGKey(3)
        P, Q, b = 32, 3, 16
        rng = np.random.default_rng(1)
        node_ids = jnp.asarray(
            rng.integers(0, 69904, (P, Q, b)).astype(np.int32))
        full = np.asarray(je._node_noise(NoiseKind.LAPLACE, key,
                                         node_ids))
        # Partition blocks with explicit global pk_index == full slice.
        for p0 in (0, 8, 24):
            blk = np.asarray(je._node_noise(
                NoiseKind.LAPLACE, key, node_ids[p0:p0 + 8],
                pk_index=jnp.arange(p0, p0 + 8, dtype=jnp.uint32)))
            np.testing.assert_array_equal(blk, full[p0:p0 + 8])
        # Duplicated node ids across the Q axis draw identical noise.
        dup = jnp.broadcast_to(node_ids[:, :1, :], node_ids.shape)
        out = np.asarray(je._node_noise(NoiseKind.LAPLACE, key, dup))
        np.testing.assert_array_equal(out, np.broadcast_to(
            out[:, :1, :], out.shape))

    @pytest.mark.parametrize("kind,var", [(NoiseKind.LAPLACE, 2.0),
                                          (NoiseKind.GAUSSIAN, 1.0)])
    def test_unit_moments(self, kind, var):
        """The generator's raw draws are unit-scale: Laplace(b=1) has
        variance 2, the Gaussian variance 1."""
        key = jax.random.PRNGKey(11)
        ids = jnp.arange(1 << 19, dtype=jnp.int32).reshape(1 << 15, 1, 16)
        draws = np.asarray(je._node_noise(kind, key, ids)).ravel()
        assert abs(draws.mean()) < 0.01
        assert draws.var() == pytest.approx(var, rel=0.02)

    def test_walk_noise_matches_calibrated_scale(self):
        """Through ``_noise_scales`` + the walk's ``raw + noise * scale``
        arithmetic, per-node noise must still carry the calibrated
        per-level scale (the statistical-moments acceptance check)."""
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50)],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=10.0)
        config = je.FusedConfig.from_params(params, public=True)

        class _Spec:
            eps, delta = 0.5, 1e-6

        scale = float(je._noise_scales(config, {"percentile": _Spec})[0])
        # eps/level = 0.5/4, l1 sensitivity = l0 * linf = 8 -> b = 64.
        assert scale == pytest.approx(8 / (0.5 / 4), rel=1e-5)
        key = jax.random.PRNGKey(4)
        ids = jnp.arange(1 << 19, dtype=jnp.int32).reshape(1 << 15, 1, 16)
        draws = np.asarray(
            je._node_noise(NoiseKind.LAPLACE, key, ids)).ravel() * scale
        assert draws.var() == pytest.approx(2.0 * scale**2, rel=0.02)


def _walk_params(percentiles=(50, 90), hi=10.0, **kw):
    kw.setdefault("max_partitions_contributed", 40)
    kw.setdefault("max_contributions_per_partition", 200)
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.PERCENTILE(p) for p in percentiles] +
        [pdp.Metrics.COUNT],
        noise_kind=pdp.NoiseKind.LAPLACE,
        min_value=0.0, max_value=hi, **kw)


def _percentile_fields(got):
    return [f for f in got[next(iter(got))]._fields
            if f.startswith("percentile_") or f == "count"]


class TestThreeWayBitParity:
    """Single-batch, 8-device owner-sharded mesh and streamed quantile
    walks must produce BIT-IDENTICAL released values and kept-partition
    sets for the same seed: the counter-based node noise is keyed by
    the GLOBAL (partition, node id), the mesh/streamed key splits now
    mirror the single-chip 3-way split, and the streamed host release
    draws over the kept set in the same order as the single-batch
    compact fetch. Caps are non-binding so bounding keeps every row on
    all three paths (binding caps legitimately sample per-path)."""

    def _dataset(self):
        rng = np.random.default_rng(42)
        n = 20_000
        return pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 2_000, n),
            partition_keys=(rng.zipf(1.6, n) % 40).astype(np.int64),
            values=rng.uniform(0, 10, n))

    def _run(self, ds, backend, chunk=None, monkeypatch=None):
        if chunk is not None:
            monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", str(chunk))
        else:
            monkeypatch.delenv("PIPELINEDP_TPU_STREAM_CHUNK",
                               raising=False)
        ds.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=4.0,
                                        total_delta=1e-4)
        engine = pdp.DPEngine(acc, backend)
        res = engine.aggregate(ds, _walk_params(), pdp.DataExtractors())
        acc.compute_budgets()
        return dict(res), res.timings

    def test_three_way_bit_identical(self, monkeypatch):
        from pipelinedp_tpu.parallel import make_mesh

        ds = self._dataset()
        single, _ = self._run(ds, JaxBackend(rng_seed=11),
                              monkeypatch=monkeypatch)
        mesh, _ = self._run(ds, JaxBackend(mesh=make_mesh(8),
                                           rng_seed=11),
                            monkeypatch=monkeypatch)
        streamed, t = self._run(ds, JaxBackend(rng_seed=11), chunk=997,
                                monkeypatch=monkeypatch)
        assert t["stream_batches"] > 5  # really streamed
        assert len(single) > 5  # non-trivial kept set
        assert set(single) == set(mesh) == set(streamed)
        for k in single:
            for f in _percentile_fields(single):
                v = getattr(single[k], f)
                assert getattr(mesh[k], f) == v, (k, f, "mesh")
                assert getattr(streamed[k], f) == v, (k, f, "streamed")


class TestPartitionBlockChunkedWalk:
    """Past ``_SUBHIST_BYTE_CAP`` the bottom walk chunks the partition
    axis into blocks — bit-identical to the unchunked walk (node noise
    is a pure function of the GLOBAL (partition, node id))."""

    def _run_public(self, ds, params, parts, backend=None, chunk=None,
                    monkeypatch=None):
        if monkeypatch is not None:
            if chunk is not None:
                monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK",
                                   str(chunk))
            else:
                monkeypatch.delenv("PIPELINEDP_TPU_STREAM_CHUNK",
                                   raising=False)
        ds.invalidate_cache()
        je.fused_aggregate_kernel.clear_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=3.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, backend or JaxBackend(rng_seed=9))
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=list(range(parts)))
        acc.compute_budgets()
        return dict(res), res.timings

    def test_single_batch_blocks_bit_identical(self, monkeypatch):
        """The single-batch walk no longer degrades to per-level row
        scatters past the cap: it partition-block-chunks, and the
        blocked walk is bit-identical to the one-block walk."""
        rng = np.random.default_rng(5)
        n = 8_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 2_000, n),
                              partition_keys=rng.integers(0, 6, n),
                              values=rng.uniform(0, 20, n))
        params = _walk_params(percentiles=(25, 50, 95), hi=20.0,
                              max_partitions_contributed=6,
                              max_contributions_per_partition=50)
        full, _ = self._run_public(ds, params, 6,
                                   monkeypatch=monkeypatch)
        # P_pad = 8, Q = 3: cap sized for 2-partition blocks -> the
        # bottom walk runs as 4 blocks, each built with the compacted
        # sub-histogram machinery. Spy on the builder to prove the
        # chunked path actually traced.
        _, _, _, span = streaming._tree_consts()
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 2 * 3 * span * 4)
        block_sizes = []
        orig = je._build_sub_hist

        def spy(qpk, leaf, kept, sub_start, P, *a, **kw):
            block_sizes.append(P)
            return orig(qpk, leaf, kept, sub_start, P, *a, **kw)

        monkeypatch.setattr(je, "_build_sub_hist", spy)
        chunked, _ = self._run_public(ds, params, 6,
                                      monkeypatch=monkeypatch)
        assert block_sizes == [2, 2, 2, 2]
        for p in range(6):
            for f in _percentile_fields(full):
                assert getattr(chunked[p], f) == getattr(full[p], f), (
                    p, f)

    def test_streamed_single_quantile_over_cap_completes(self,
                                                         monkeypatch):
        """The acceptance case: a streamed percentile run whose SINGLE-
        quantile [P_pad, 1, span] block exceeds a test-shrunken cap
        completes via partition-block chunking (no NotImplementedError)
        and matches the uncapped run bit-for-bit."""
        rng = np.random.default_rng(88)
        n = 6_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 1_500, n),
                              partition_keys=rng.integers(0, 5, n),
                              values=rng.uniform(0.0, 20.0, n))
        params = _walk_params(percentiles=(50, 95), hi=20.0,
                              max_partitions_contributed=5,
                              max_contributions_per_partition=50)
        full, t_full = self._run_public(ds, params, 5, chunk=997,
                                        monkeypatch=monkeypatch)
        assert t_full["stream_batches"] > 1
        assert t_full["stream_pass_b_rounds"] == 1
        # P_pad = 8: a cap of two partitions' single-quantile blocks is
        # BELOW one quantile's [8, 1, span] block -> partition-block
        # mode: 2 q-groups x 4 p-blocks = 8 rounds.
        _, _, _, span = streaming._tree_consts()
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 2 * span * 4)
        chunked, t_chunk = self._run_public(ds, params, 5, chunk=997,
                                            monkeypatch=monkeypatch)
        assert t_chunk["stream_pass_b_rounds"] == 8
        for p in range(5):
            for f in _percentile_fields(full):
                assert getattr(chunked[p], f) == getattr(full[p], f), (
                    p, f)

    def test_streamed_blocks_on_mesh_bit_identical(self, monkeypatch):
        """Partition-block chunking composes with the 8-device mesh
        (block rounds combine shards with a replicating psum instead of
        the owner-block scatter) — still bit-identical."""
        from pipelinedp_tpu.parallel import make_mesh

        rng = np.random.default_rng(17)
        n = 6_000
        ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, 1_500, n),
                              partition_keys=rng.integers(0, 5, n),
                              values=rng.uniform(0.0, 10.0, n))
        params = _walk_params(percentiles=(50, 90),
                              max_partitions_contributed=5,
                              max_contributions_per_partition=50)
        mesh = make_mesh(8)
        # The per-batch target scales with the mesh size: 8 x 499 rows
        # per batch still splits 6,000 rows into > 1 batch.
        full, t_full = self._run_public(
            ds, params, 5, backend=JaxBackend(mesh=mesh, rng_seed=3),
            chunk=499, monkeypatch=monkeypatch)
        assert t_full["stream_batches"] > 1
        _, _, _, span = streaming._tree_consts()
        monkeypatch.setattr(je, "_SUBHIST_BYTE_CAP", 4 * span * 4)
        chunked, t_chunk = self._run_public(
            ds, params, 5, backend=JaxBackend(mesh=mesh, rng_seed=3),
            chunk=499, monkeypatch=monkeypatch)
        assert t_chunk["stream_pass_b_rounds"] > 1
        for p in range(5):
            for f in _percentile_fields(full):
                assert getattr(chunked[p], f) == getattr(full[p], f), (
                    p, f)


class TestGuardBoundaries:
    """The extreme-scale guard cliffs,
    pinned at their EXACT boundaries via the injectable cap seams —
    the way ``test_jax_engine`` pins the lane-plan boundary at
    524,417 rows exactly."""

    def test_lane_plan_boundary_at_true_cap(self):
        """The 2^27-row per-batch unit-skew cliff, at its real
        constant: the narrowest (4-bit) lane plan accumulates exactly
        up to floor((2^31 - 1) / 15) = 143,165,576 rows."""
        boundary = (je._LANE_SUM_CAP - 1) // 15
        assert boundary == 143_165_576 == je._fx_max_rows()
        assert je._fx_plan(boundary) == (4, 6)
        with pytest.raises(NotImplementedError, match="privacy unit"):
            je._fx_plan(boundary + 1)

    def test_unit_skew_guard_exact_boundary(self, monkeypatch):
        """The streamed guard for one privacy unit owning more rows
        than a batch can hold, at the exact injected boundary: with
        ``_LANE_SUM_CAP = 1501`` the cliff is at 100 rows — a unit
        owning exactly 100 streams fine, 101 raises the skew message."""
        monkeypatch.setattr(je, "_LANE_SUM_CAP", 1501)
        assert je._fx_max_rows() == 100
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "50")
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=1,
            max_contributions_per_partition=200,
            min_value=0.0, max_value=1.0)

        def run(n_rows_of_one_unit):
            ds = pdp.ArrayDataset(
                privacy_ids=np.zeros(n_rows_of_one_unit, np.int64),
                partition_keys=np.zeros(n_rows_of_one_unit, np.int64),
                values=np.ones(n_rows_of_one_unit, np.float32))
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
            res = engine.aggregate(ds, params, pdp.DataExtractors(),
                                   public_partitions=[0])
            acc.compute_budgets()
            return dict(res)

        got = run(100)  # exactly at capacity: completes
        assert got[0].sum == pytest.approx(100.0, abs=0.5)
        with pytest.raises(NotImplementedError,
                           match="privacy unit owns"):
            run(101)

    def test_select_units_guard_exact_boundary(self, monkeypatch):
        """The >2^31-privacy-units-per-partition selection guard at an
        injected cap of 64: 63 units in one partition selects fine, 64
        raises."""
        monkeypatch.setattr(streaming, "_SELECT_UNITS_CAP", 64)
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "29")
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=1,
            max_contributions_per_partition=1)

        def run(n_units):
            ds = pdp.ArrayDataset(
                privacy_ids=np.arange(n_units, dtype=np.int64),
                partition_keys=np.zeros(n_units, np.int64),
                values=np.zeros(n_units, np.float32))
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
            res = engine.aggregate(ds, params, pdp.DataExtractors())
            acc.compute_budgets()
            return dict(res)

        got = run(63)  # one below the cap: completes and keeps pk 0
        assert 0 in got
        with pytest.raises(NotImplementedError, match="privacy units"):
            run(64)

    def test_tree_rows_guard_exact_boundary(self, monkeypatch):
        """The >2^31-kept-rows-per-partition streamed-percentile guard
        at an injected cap of 256: a partition holding 255 kept rows
        walks fine, 256 raises."""
        monkeypatch.setattr(streaming, "_TREE_ROWS_CAP", 256)
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "61")
        params = _walk_params(percentiles=(50,),
                              max_partitions_contributed=1,
                              max_contributions_per_partition=300)

        def run(n_rows):
            rng = np.random.default_rng(1)
            ds = pdp.ArrayDataset(
                privacy_ids=np.arange(n_rows, dtype=np.int64),
                partition_keys=np.zeros(n_rows, np.int64),
                values=rng.uniform(0, 10, n_rows))
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
            res = engine.aggregate(ds, params, pdp.DataExtractors(),
                                   public_partitions=[0])
            acc.compute_budgets()
            return dict(res)

        got = run(255)
        assert got[0].percentile_50 == pytest.approx(5.0, abs=1.0)
        with pytest.raises(NotImplementedError, match="2\\^31 kept"):
            run(256)


class TestFoldInKeyLint:
    """Per-element ``vmap(fold_in)`` key constructions rebuild a full
    threefry key schedule per element — the cost the counter-based
    generator removed. New ones are banned outside the one blessed
    helper module (``ops/counter_rng.py``); ``make nofoldin`` enforces
    the same rule at the Makefile level."""

    def test_no_vmap_fold_in_outside_blessed_helper(self):
        # Delegates to the shared AST engine; `make nofoldin` is the
        # same rule.
        from pipelinedp_tpu import lint
        assert lint.check_tree("nofoldin") == []
