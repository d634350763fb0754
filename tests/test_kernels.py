"""Pallas hot-path kernel tests (PR 10).

The ``kernel_backend`` knob dispatches the pass-B multi-tile histogram
binner and the fused lane-packed segment sum to hand-tiled Pallas
kernels (``pipelinedp_tpu/ops/kernels/``) — interpret mode off-TPU, so
every assertion here runs on the CPU proxy. Covered:

* kernel-level bit-parity against the XLA scatter paths, including
  max-value lanes at every lane-plan width (12/11/4 bits) with
  per-partition totals past 2^24 (the f32-block-exactness cliff);
* the wide-D vector twin (``segment_sum_wide``, ISSUE 17): D-tiled
  [P, Dt] slabs bit-identical at every tile width, the
  ``segsum_wide_d_block`` pin, and the ``vector_f32_accumulator``
  refusal (the f32 accumulator never rides the MXU kernel);
* the end-to-end lane-cap boundary shape from ``test_jax_engine.py``
  (525,000 rows — the 12->11-bit plan switch) bit-identical across
  backends;
* the out-of-envelope and pallas-unavailable fallbacks: XLA results
  plus a ``kernel.fallback`` obs event — never a silent path change;
* ``kernel_backend`` knob precedence (env > seam > default)
  and unknown-value hardening;
* the interpret-mode CPU row in the cost observatory's peak table
  (Pallas-path programs on an interpreter backend get a roofline
  verdict instead of ``unknown``);
* the in-tree ``nopallas`` lint twin: pallas imports confined to
  ``pipelinedp_tpu/ops/kernels/``.
"""

import ast
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu import obs
from pipelinedp_tpu import plan as plan_mod
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ops import kernels
from pipelinedp_tpu.ops.kernels import dispatch
from pipelinedp_tpu.plan import knobs as knobs_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = knobs_mod.BY_NAME["kernel_backend"]


def _fallback_events(reason=None):
    events = [e for e in obs.ledger().snapshot()["events"]
              if e["name"] == "kernel.fallback"]
    if reason is not None:
        events = [e for e in events if e.get("reason") == reason]
    return events


class TestSegsumKernelParity:
    """``segment_sum_lanes`` must equal ``jax.ops.segment_sum`` bit
    for bit — the whole dispatch rests on it."""

    @pytest.mark.parametrize("P,C,n", [
        (8, 2, 1000), (64, 11, 5000), (1024, 14, 20_000),
        (8192, 4, 3000),
    ])
    def test_random_parity(self, P, C, n):
        rng = np.random.default_rng(P * C)
        pk = jnp.asarray(rng.integers(0, P, n).astype(np.int32))
        cols = jnp.asarray(
            rng.integers(0, 4096, (n, C)).astype(np.int32))
        env = kernels.segsum_envelope(P, C)
        assert env is not None
        got = kernels.segment_sum_lanes(cols, pk, P, *env,
                                        kernels.use_interpret())
        ref = jax.ops.segment_sum(cols, pk, num_segments=P)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("P,block", [(200, 64), (130, 128)])
    def test_partition_blocks_parity(self, monkeypatch, P, block):
        """Past one partition block the grid walks several (the last
        one ragged): still bit-identical."""
        monkeypatch.setattr(dispatch, "_SEGSUM_P_BLOCK", block)
        rng = np.random.default_rng(P)
        n = 3000
        pk = jnp.asarray(rng.integers(0, P, n).astype(np.int32))
        cols = jnp.asarray(rng.integers(0, 4096, (n, 5)).astype(np.int32))
        rb, pb = kernels.segsum_envelope(P, 5)
        assert pb == block < P
        got = kernels.segment_sum_lanes(cols, pk, P, rb, pb,
                                        kernels.use_interpret())
        ref = jax.ops.segment_sum(cols, pk, num_segments=P)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("bits", [12, 11, 4])
    def test_max_lane_values_past_f32_exactness(self, bits):
        """Every row carries the lane plan's maximum value into ONE
        partition: the total (8192 * (2^bits - 1), up to 33.5M at 12
        bits) exceeds 2^24, so any f32 TOTAL accumulation would go
        inexact — the per-block-partials-then-int32 design must not."""
        n, P = 8192, 16
        lane_max = (1 << bits) - 1
        pk = jnp.zeros(n, jnp.int32)
        cols = jnp.full((n, 3), lane_max, jnp.int32)
        got = np.asarray(kernels.segment_sum_lanes(
            cols, pk, P, *kernels.segsum_envelope(P, 3),
            kernels.use_interpret()))
        assert int(got[0, 0]) == n * lane_max
        ref = np.asarray(jax.ops.segment_sum(cols, pk, num_segments=P))
        np.testing.assert_array_equal(got, ref)


class TestWideSegsumKernelParity:
    """``segment_sum_wide`` must equal ``jax.ops.segment_sum`` bit for
    bit over [N, D] fixed-point vector coordinate lanes — the kernel
    leg of PARITY row 39."""

    @pytest.mark.parametrize("P,D,n", [
        (8, 64, 1000), (37, 200, 511), (512, 1024, 1300),
        (8192, 64, 700),
    ])
    def test_random_parity(self, P, D, n):
        rng = np.random.default_rng(P + D + n)
        pk = jnp.asarray(rng.integers(0, P, n).astype(np.int32))
        cols = jnp.asarray(
            rng.integers(0, 4096, (n, D)).astype(np.int32))
        env = kernels.segsum_wide_envelope(P, D)
        assert env is not None
        got = kernels.segment_sum_wide(cols, pk, P, *env,
                                       kernels.use_interpret())
        ref = jax.ops.segment_sum(cols, pk, num_segments=P)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_max_lane_values_past_f32_exactness(self):
        """Every row carries the 12-bit lane max into ONE partition at
        a D that is NOT a d_block multiple: per-coordinate totals
        (8192 * 4095 > 2^24) exceed f32 exactness, and the ragged last
        D tile must mask its padding columns out of the result."""
        n, P, D = 8192, 16, 130
        lane_max = (1 << 12) - 1
        pk = jnp.zeros(n, jnp.int32)
        cols = jnp.full((n, D), lane_max, jnp.int32)
        got = np.asarray(kernels.segment_sum_wide(
            cols, pk, P, *kernels.segsum_wide_envelope(P, D),
            kernels.use_interpret()))
        assert int(got[0, 0]) == n * lane_max
        ref = np.asarray(jax.ops.segment_sum(cols, pk, num_segments=P))
        np.testing.assert_array_equal(got, ref)

    def test_every_d_block_is_bit_identical(self):
        """The D tile width (the ``segsum_wide_d_block`` knob)
        is a performance hint only: every candidate reduces to the
        same bits, so the sweep can never change released values."""
        rng = np.random.default_rng(40)
        n, P, D = 3000, 64, 640
        pk = jnp.asarray(rng.integers(0, P, n).astype(np.int32))
        cols = jnp.asarray(
            rng.integers(0, 4096, (n, D)).astype(np.int32))
        ref = np.asarray(jax.ops.segment_sum(cols, pk, num_segments=P))
        rb, _, pb = kernels.segsum_wide_envelope(P, D)
        for db in dispatch._D_BLOCKS:
            got = kernels.segment_sum_wide(cols, pk, P, rb, db, pb,
                                           kernels.use_interpret())
            np.testing.assert_array_equal(np.asarray(got), ref,
                                          err_msg=f"d_block={db}")


class TestWideSegsumDispatch:
    """The wide-D dispatch seam: envelope geometry, the d_block pin,
    and the visible fallbacks (``kernel.fallback`` events, never a
    silent path change)."""

    def test_envelope_geometry(self):
        # A full partition block narrows BOTH axes: the [Pk, R] one-hot
        # and the [Pk, Dt] slab each hit their 4 MB budget at 128.
        assert kernels.segsum_wide_envelope(8192, 1024) == (128, 128,
                                                             8192)
        # Small P affords the widest tile.
        assert kernels.segsum_wide_envelope(64, 1024) == (512, 512, 64)
        # No column cap — D is tiled, unlike the scalar lane kernel.
        assert kernels.segsum_wide_envelope(
            64, dispatch._SEGSUM_MAX_COLS * 128) is not None
        # P past one block tiles the partition axis.
        assert kernels.segsum_wide_envelope(
            dispatch._SEGSUM_P_BLOCK * 2, 64) == (128, 128, 8192)

    def test_partition_blocks_dispatch_pallas(self, monkeypatch):
        """P past one partition block stays a pallas dispatch (no
        fallback event) and matches XLA bit for bit."""
        monkeypatch.setattr(dispatch, "_SEGSUM_P_BLOCK", 64)
        rng = np.random.default_rng(1)
        n, P = 500, 150
        pk = jnp.asarray(rng.integers(0, P, n).astype(np.int32))
        cols = jnp.asarray(
            rng.integers(0, 4096, (n, 200)).astype(np.int32))
        obs.reset()
        got = dispatch.try_segment_sum_wide(cols, pk, P, "pallas")
        assert got is not None and not _fallback_events()
        ref = jax.ops.segment_sum(cols, pk, num_segments=P)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_xla_request_short_circuits(self):
        pk = jnp.zeros(4, jnp.int32)
        cols = jnp.ones((4, 8), jnp.int32)
        obs.reset()
        assert dispatch.try_segment_sum_wide(cols, pk, 8, "xla") is None
        assert not _fallback_events()

    def test_pin_honored_and_bad_pin_ignored(self):
        """An in-envelope ``segsum_wide_d_block`` pin is used; a pin
        whose [P, Dt] slab would blow VMEM falls back to the
        envelope's own tile — never to XLA (the knob is a dp-safe
        performance hint, not a correctness gate)."""
        rng = np.random.default_rng(2)
        n, D = 1000, 300
        pk_small = jnp.asarray(rng.integers(0, 64, n).astype(np.int32))
        cols = jnp.asarray(
            rng.integers(0, 4096, (n, D)).astype(np.int32))
        ref64 = np.asarray(
            jax.ops.segment_sum(cols, pk_small, num_segments=64))
        obs.reset()
        got = dispatch.try_segment_sum_wide(cols, pk_small, 64,
                                            "pallas", d_block=128)
        assert got is not None
        np.testing.assert_array_equal(np.asarray(got), ref64)
        # At P=8192 a 512-wide slab is 16 MB — pin ignored, still
        # a pallas dispatch, still exact.
        pk_big = jnp.asarray(
            rng.integers(0, 8192, n).astype(np.int32))
        ref8k = np.asarray(
            jax.ops.segment_sum(cols, pk_big, num_segments=8192))
        got = dispatch.try_segment_sum_wide(cols, pk_big, 8192,
                                            "pallas", d_block=512)
        assert got is not None
        np.testing.assert_array_equal(np.asarray(got), ref8k)
        assert not _fallback_events()

    def test_f32_accumulator_refuses_pallas_visibly(self):
        """A pallas request over the default f32 vector accumulator
        cannot be bit-identical (MXU partial-sum order differs from
        the XLA scatter), so ``_reduce_per_pk`` refuses the kernel
        VISIBLY: XLA results, a ``vector_f32_accumulator`` fallback
        event — the ISSUE-17 'visibly falling back' clause."""
        rng = np.random.default_rng(17)
        data = [(u, f"p{u % 4}", rng.uniform(-1, 1, 64))
                for u in range(300)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            max_partitions_contributed=2,
            max_contributions_per_partition=1,
            vector_size=64, vector_max_norm=4.0,
            vector_norm_kind=pdp.NormKind.L2)

        def run(seed):
            from pipelinedp_tpu.ops import noise as noise_ops
            noise_ops.seed_host_rng(0)
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e5,
                                            total_delta=1e-6)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed))
            import operator
            ext = pdp.DataExtractors(
                privacy_id_extractor=operator.itemgetter(0),
                partition_extractor=operator.itemgetter(1),
                value_extractor=operator.itemgetter(2))
            res = engine.aggregate(data, params, ext,
                                   public_partitions=[f"p{i}"
                                                      for i in range(4)])
            acc.compute_budgets()
            return {k: np.asarray(v.vector_sum)
                    for k, v in dict(res).items()}

        base = run(9)
        obs.reset()
        with plan_mod.seam_override("kernel_backend", "pallas"):
            pal = run(9)
        events = _fallback_events("vector_f32_accumulator")
        assert events and events[0]["site"] == "segment_sum_wide"
        assert set(base) == set(pal)
        for k in base:
            np.testing.assert_array_equal(base[k], pal[k])


class TestHistKernelParity:
    """``hist_bin_multi`` vs ``_subtree_counts_multi``'s XLA scatter
    loop, on dense multi-tile shapes (every row in range)."""

    @pytest.mark.parametrize("T,Pb,Qc,seed", [
        (1, 8, 1, 0), (3, 8, 2, 1), (5, 16, 4, 2),
    ])
    def test_random_parity(self, T, Pb, Qc, seed):
        span = 16
        rng = np.random.default_rng(seed)
        n = 9000
        qpk = jnp.asarray(rng.integers(0, T * Pb, n).astype(np.int32))
        leaf = jnp.asarray(rng.integers(0, 64, n).astype(np.int32))
        kept = jnp.asarray(rng.random(n) < 0.8)
        sub_starts = jnp.asarray(
            rng.integers(0, 48, (T, Pb, Qc)).astype(np.int32))
        p_offsets = jnp.asarray(
            (np.arange(T) * Pb).astype(np.int32))
        env = kernels.hist_envelope(T, Pb, Qc, span)
        assert env is not None
        got = kernels.hist_bin_multi(qpk, leaf, kept, sub_starts,
                                     p_offsets, Pb, span, *env,
                                     kernels.use_interpret())
        ref = je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                       p_offsets, Pb, span)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        # Dense shape: the parity must not be vacuous.
        assert int(np.asarray(ref).sum()) > 100

    @pytest.mark.parametrize("T,Pb,Qc", [(1, 300, 3), (2, 256, 2)])
    def test_partition_blocks_parity(self, monkeypatch, T, Pb, Qc):
        """A tile wider than one VMEM-resident partition block walks
        blocks of 128 (the last one ragged when Pb is not a multiple),
        with walk starts up to the 2^16 leaf range: bit-identical."""
        span = 16
        monkeypatch.setattr(dispatch, "_OUT_BYTES_CAP",
                            128 * Qc * span * 4)
        rng = np.random.default_rng(T * Pb)
        n = 4000
        qpk = jnp.asarray(rng.integers(0, T * Pb, n).astype(np.int32))
        leaf = jnp.asarray(
            rng.integers(0, 1 << 16, n).astype(np.int32))
        kept = jnp.asarray(rng.random(n) < 0.9)
        # Walk starts just below each row's leaf, so most rows land in
        # their partition's subtree.
        starts = np.zeros((T, Pb, Qc), np.int64)
        lf, pk = np.asarray(leaf), np.asarray(qpk)
        for q in range(Qc):
            starts[pk // Pb, pk % Pb, q] = np.maximum(lf - 3 * q, 0)
        sub_starts = jnp.asarray(starts.astype(np.int32))
        p_offsets = jnp.asarray((np.arange(T) * Pb).astype(np.int32))
        rb, pb = kernels.hist_envelope(T, Pb, Qc, span)
        assert pb == 128 < Pb
        got = kernels.hist_bin_multi(qpk, leaf, kept, sub_starts,
                                     p_offsets, Pb, span, rb, pb,
                                     kernels.use_interpret())
        ref = je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                       p_offsets, Pb, span)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert int(np.asarray(ref).sum()) > 100


class TestLaneCapBoundaryEndToEnd:
    """The e2e lane-cap boundary shape from ``test_jax_engine.py``
    (525,000 rows — the first 11-bit/3-lane plan), released
    bit-identically under both backends in interpret mode."""

    def test_sum_at_plan_boundary_bit_identical(self):
        n = 525_000
        assert je._fx_plan(n) == (11, 3)
        rng = np.random.default_rng(n)
        ds = pdp.ArrayDataset(
            privacy_ids=np.arange(n) % (1 << 18),
            partition_keys=np.zeros(n, np.int64),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM], max_partitions_contributed=4,
            max_contributions_per_partition=4, min_value=0.0,
            max_value=10.0)

        def run():
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e12,
                                            total_delta=1e-2)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
            res = engine.aggregate(ds, params, pdp.DataExtractors())
            acc.compute_budgets()
            return dict(res)

        base = run()
        obs.reset()
        with plan_mod.seam_override("kernel_backend", "pallas"):
            pal = run()
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("kernel.pallas_dispatches", 0) >= 1
        assert set(base) == set(pal)
        for k in base:
            for f in base[k]._fields:
                assert getattr(base[k], f) == getattr(pal[k], f), (k, f)


class TestEnvelopeFallback:
    """A requested-but-infeasible pallas dispatch degrades to XLA with
    a ``kernel.fallback`` event — visible in the run report, never a
    silent path change."""

    def test_segsum_out_of_envelope(self):
        # Past one partition block the grid tiles P: still in envelope.
        assert kernels.segsum_envelope(dispatch._SEGSUM_P_BLOCK * 2,
                                       4) == (128, 8192)
        assert kernels.segsum_envelope(
            64, dispatch._SEGSUM_MAX_COLS + 1) is None
        obs.reset()
        assert dispatch.select_backend("pallas", "segment_sum_lanes",
                                       None, P=16384, C=4) == "xla"
        events = _fallback_events("out_of_envelope")
        assert events and events[0]["site"] == "segment_sum_lanes"

    def test_hist_out_of_envelope_falls_back_bit_identical(self):
        """More quantiles than the kernel unrolls, through the REAL
        dispatch seam: XLA result, fallback event."""
        span, Pb, Qc = 16, 8, dispatch._HIST_UNROLL_CAP + 1
        assert kernels.hist_envelope(1, Pb, Qc, span) is None
        rng = np.random.default_rng(3)
        n = 1000
        qpk = jnp.asarray(rng.integers(0, Pb, n).astype(np.int32))
        leaf = jnp.asarray(rng.integers(0, 32, n).astype(np.int32))
        kept = jnp.ones(n, bool)
        sub_starts = jnp.zeros((1, Pb, Qc), jnp.int32)
        p_offsets = jnp.zeros(1, jnp.int32)
        obs.reset()
        got = je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                       p_offsets, Pb, span,
                                       kernel_backend="pallas")
        ref = je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                       p_offsets, Pb, span)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        events = _fallback_events("out_of_envelope")
        assert events and events[0]["site"] == "hist_bin_multi"
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("kernel.fallbacks", 0) >= 1

    def test_single_batch_walk_degrades_visibly(self):
        """The single-batch quantile walk has no Pallas twin (only
        streamed pass B's binner): a pallas request on a non-streamed
        percentile run must say so with a kernel.fallback event —
        while the same program's per-pk reduction still dispatches —
        and stay bit-identical to xla."""
        rng = np.random.default_rng(13)
        n = 6000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 600, n),
            partition_keys=rng.integers(0, 12, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.COUNT],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=3,
            min_value=0.0, max_value=10.0)

        def run():
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=2.0,
                                            total_delta=1e-3)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=5))
            res = engine.aggregate(ds, params, pdp.DataExtractors())
            acc.compute_budgets()
            return dict(res)

        base = run()
        obs.reset()
        with plan_mod.seam_override("kernel_backend", "pallas"):
            pal = run()
        events = _fallback_events("single_batch_walk")
        assert events and events[0]["site"] == "walk_subtree_counts"
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("kernel.pallas_dispatches", 0) >= 1
        assert set(base) == set(pal)
        for k in base:
            for f in base[k]._fields:
                assert getattr(base[k], f) == getattr(pal[k], f)

    def test_pallas_unavailable_falls_back(self, monkeypatch):
        """A host without Pallas (forced via the dispatch seam) runs
        the whole aggregation on XLA — same outputs, fallback event."""
        monkeypatch.setattr(dispatch, "_FORCE_UNAVAILABLE", True)
        assert not kernels.pallas_available()
        rng = np.random.default_rng(7)
        n = 5000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 500, n),
            partition_keys=rng.integers(0, 20, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=10.0)

        def run():
            ds.invalidate_cache()
            acc = pdp.NaiveBudgetAccountant(total_epsilon=2.0,
                                            total_delta=1e-3)
            engine = pdp.DPEngine(acc, JaxBackend(rng_seed=1))
            res = engine.aggregate(ds, params, pdp.DataExtractors())
            acc.compute_budgets()
            return dict(res)

        base = run()
        obs.reset()
        with plan_mod.seam_override("kernel_backend", "pallas"):
            degraded = run()
        events = _fallback_events("pallas_unavailable")
        assert events
        counters = obs.ledger().snapshot()["counters"]
        assert not counters.get("kernel.pallas_dispatches")
        assert set(base) == set(degraded)
        for k in base:
            for f in base[k]._fields:
                assert getattr(base[k], f) == getattr(degraded[k], f)


class TestKernelBackendKnob:
    """``kernel_backend`` resolves through the registry precedence
    (env > seam > default) like every other knob."""

    def test_registered_dp_safe_str(self):
        assert SPEC.dp_safe
        assert SPEC.kind is str
        assert SPEC.default == "xla"
        assert SPEC.choices == ("xla", "pallas")
        assert SPEC.env_var == "PIPELINEDP_TPU_KERNEL_BACKEND"

    def test_default(self, monkeypatch):
        monkeypatch.delenv(SPEC.env_var, raising=False)
        assert knobs_mod.resolve_value(SPEC) == ("xla", "default")

    def test_seam_beats_plan(self, monkeypatch):
        # The seam outranks the default (no plan layer remains).
        monkeypatch.delenv(SPEC.env_var, raising=False)
        with plan_mod.seam_override("kernel_backend", "pallas"):
            got = knobs_mod.resolve_value(SPEC)
        assert got == ("pallas", "seam")
        assert knobs_mod.resolve_value(SPEC) == ("xla", "default")

    def test_env_beats_seam(self, monkeypatch):
        monkeypatch.setenv(SPEC.env_var, "xla")
        with plan_mod.seam_override("kernel_backend", "pallas"):
            got = knobs_mod.resolve_value(SPEC)
        assert got == ("xla", "env")

    def test_unknown_value_hardens_to_default(self, monkeypatch):
        monkeypatch.setenv(SPEC.env_var, "cuda")
        value, source = knobs_mod.resolve_value(SPEC)
        assert (value, source) == ("xla", "env")

    def test_env_dispatches_pallas_end_to_end(self, monkeypatch):
        monkeypatch.setenv(SPEC.env_var, "pallas")
        rng = np.random.default_rng(11)
        n = 4000
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, 400, n),
            partition_keys=rng.integers(0, 10, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.MEAN],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=10.0)
        obs.reset()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=2.0,
                                        total_delta=1e-3)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=2))
        res = engine.aggregate(ds, params, pdp.DataExtractors())
        acc.compute_budgets()
        assert len(dict(res)) > 0
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("kernel.pallas_dispatches", 0) >= 1


class TestInterpretPeakRow:
    """The cost observatory's static peak table covers interpreter
    backends, so Pallas-path programs on the CPU proxy classify
    against a (proxy) roofline instead of ``unknown``."""

    def test_interpreter_row_matches(self):
        from pipelinedp_tpu.obs import costs
        row = costs.device_peaks("Interpreter")
        assert row is not None and row["kind"] == "cpu_interpret"
        assert row["proxy"] is True
        verdict = costs.roofline_verdict(1e9, 1e6, row)
        assert verdict["verdict"] != "unknown"

    def test_cpu_still_matches_the_proxy_row(self):
        from pipelinedp_tpu.obs import costs
        assert costs.device_peaks("cpu")["kind"] == "cpu_proxy"


class TestNoPallasLint:
    """In-tree twin of ``make nopallas``: pallas imports are confined
    to ``pipelinedp_tpu/ops/kernels/`` — every other module dispatches
    through the kernels package (you cannot call ``pallas_call`` or
    ``pl.*`` without importing pallas, so banning the import is the
    AST-precise version of the grep)."""

    def test_pallas_imports_confined_to_kernels_package(self):
        # Delegates to the shared AST engine; `make nopallas` is the
        # same rule.
        from pipelinedp_tpu import lint
        assert lint.check_tree("nopallas") == []

    def test_kernels_package_does_import_pallas(self):
        """The lint must be testing something: the kernels package
        itself carries the (lazy) pallas imports."""
        path = os.path.join(REPO, "pipelinedp_tpu", "ops", "kernels",
                            "hist.py")
        with open(path, encoding="utf-8") as fh:
            assert "pallas" in fh.read()
