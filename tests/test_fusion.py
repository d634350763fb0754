"""Shape-bucketed request fusion acceptance suite (serve/fusion.py).

The ISSUE-14 criteria, end to end:

* fused-vs-solo DP outputs bit-identical — released values AND kept
  sets — as PARITY row 35, asserted across a bucket boundary (request
  sizes straddling a pow2 edge, so both pad masks are exercised inside
  one batched program), with per-request budget debits and audit
  records unchanged in count and content;
* the pad-mask contract the buckets stand on: the solo kernel is
  padding-invariant (same request, larger row padding, identical
  bits) now that row tie-breaks are content-keyed
  (``ops.counter_rng.row_bits``);
* kill-mid-batch: every fused request's lease resolves exactly once
  (the killed member's reserve stays spent, its companions commit);
* zero new ``compile.program`` captures on the second same-bucket
  batch (one warm program per bucket, the whole point);
* per-tenant row/rate quotas refuse as structured ``quota`` refusals
  BEFORE any reserve or compute;
* live bucket occupancy lands in the heartbeat's serve section.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu import obs, serve
from pipelinedp_tpu.dp_engine import DataExtractors
from pipelinedp_tpu.obs import monitor as obs_monitor
from pipelinedp_tpu.resilience import faults
from pipelinedp_tpu.resilience.clock import FakeClock
from pipelinedp_tpu.serve import fusion
from pipelinedp_tpu.serve.budget_ledger import TenantBudgetLedger

BIG_EPS = 1e6


@pytest.fixture(autouse=True)
def fresh_state(monkeypatch, tmp_path):
    monkeypatch.setenv("PIPELINEDP_TPU_LEDGER_DIR",
                       str(tmp_path / "obs_ledger"))
    monkeypatch.delenv(obs_monitor.ENV_VAR, raising=False)
    monkeypatch.delenv("PIPELINEDP_TPU_SERVE_FUSION", raising=False)
    obs.reset()
    yield
    obs_monitor.stop()
    obs.reset()
    orphans = [t.name for t in threading.enumerate()
               if (t.name.startswith("pdp-serve")
                   and t.is_alive())]
    assert not orphans, f"orphan serve threads: {orphans}"


def make_ds(seed, n, users=None, parts=30):
    """Data that EXERCISES contribution bounding: ~20 rows per user
    against (l0=3, linf=2) caps, so the bounding subsamples truncate
    hard (the regime where the padding-invariant tie-breaks are
    load-bearing, not vacuously equal) while partitions still carry
    enough users that private selection KEEPS a real subset — the
    parity assertions below must compare non-empty kept sets."""
    rng = np.random.default_rng(seed)
    users = users or max(n // 20, 10)
    return pdp.ArrayDataset(
        privacy_ids=rng.integers(0, users, n),
        partition_keys=rng.integers(0, parts, n),
        values=rng.uniform(0.0, 10.0, n))


def fusable_params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                 pdp.Metrics.VARIANCE, pdp.Metrics.PERCENTILE(50)],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=3,
        max_contributions_per_partition=2,
        min_value=0.0, max_value=10.0)


def req(tenant, ds, seed, rid, params=None, eps=4.0):
    return serve.ServeRequest(tenant=tenant,
                              params=params or fusable_params(),
                              dataset=ds, epsilon=eps, delta=1e-8,
                              rng_seed=seed, request_id=rid)


def submit_concurrently(svc, requests):
    """Submit all requests from parallel threads (the concurrent-
    tenant model); returns outcomes in request order — a response,
    a refusal, or the raised exception."""
    outs = [None] * len(requests)

    def one(i):
        try:
            outs[i] = svc.submit(requests[i])
        except BaseException as e:  # noqa: BLE001 — surfaced to the test
            outs[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def assert_results_bit_identical(a, b, ctx=""):
    ka, kb = dict(a), dict(b)
    assert set(ka) == set(kb), f"{ctx}: kept sets differ"
    for k in ka:
        assert ka[k]._fields == kb[k]._fields, (ctx, k)
        for f in ka[k]._fields:
            va, vb = getattr(ka[k], f), getattr(kb[k], f)
            assert va == vb, (f"{ctx}: partition {k} metric {f}: "
                              f"{va!r} != {vb!r}")


# ---------------------------------------------------------------------
# PARITY row 35: fused vs solo, across a bucket boundary
# ---------------------------------------------------------------------


class TestFusedSoloParity:

    # 7000 and 8000 rows both bucket at the 8192 pow2 edge (two
    # different pad masks inside ONE batched program); 9000 rows
    # crosses the boundary into the 16384 bucket.
    SIZES = (7_000, 8_000, 9_000)

    def _run(self, state_dir, fusion_on):
        tenants = {f"t{i}": (BIG_EPS, 1e-3) for i in range(3)}
        datasets = [make_ds(40 + i, n) for i, n in enumerate(self.SIZES)]
        requests = [req(f"t{i}", datasets[i], seed=70 + i, rid=f"r{i}")
                    for i in range(3)]
        with serve.Service(str(state_dir), tenants=tenants, workers=2,
                           fusion=fusion_on, fuse_window_ms=250,
                           fuse_max_batch=2) as svc:
            outs = submit_concurrently(svc, requests)
            debits = {t: svc.budgets.debits(t) for t in tenants}
        return outs, debits

    def test_fused_vs_solo_bit_identical_across_bucket_boundary(
            self, tmp_path):
        solo, solo_debits = self._run(tmp_path / "solo", False)
        obs.reset()
        fused, fused_debits = self._run(tmp_path / "fused", True)
        counters = obs.ledger().snapshot()["counters"]
        # The two same-bucket requests really fused; the third crossed
        # the boundary and ran alone.
        assert counters.get("serve.fusion_offered") == 3
        assert counters.get("serve.fused_batches") == 1
        assert counters.get("serve.fused_requests") == 2
        for i in range(3):
            assert solo[i].ok, solo[i]
            assert fused[i].ok, fused[i]
            # The comparison must not be vacuous: selection kept a
            # real, PARTIAL subset (empty kept sets would "agree"
            # about nothing; a full keep would never witness a
            # selection divergence).
            n_kept = len(dict(solo[i].results))
            assert 0 < n_kept < 30, (i, n_kept)
            # Released values AND kept sets, bit for bit.
            assert_results_bit_identical(solo[i].results,
                                         fused[i].results,
                                         ctx=f"request {i}")
            # Audit records unchanged in count and content.
            assert solo[i].audit == fused[i].audit, i
            assert solo[i].remaining == fused[i].remaining, i
        # Budget debits unchanged in count and content.
        for t in solo_debits:
            strip = lambda d: {k: (v["epsilon"], v["delta"], v["state"])
                               for k, v in d.items()}
            assert strip(solo_debits[t]) == strip(fused_debits[t]), t

    def test_books_audit_records_match_solo(self, tmp_path):
        """The per-tenant books carry one serve.request entry per
        request in BOTH modes, with identical embedded audit records
        (the fused entry is additionally stamped fused: true)."""
        import json
        import os

        from pipelinedp_tpu.serve.budget_ledger import tenant_slug

        def books_entries(state_dir, tenant):
            path = os.path.join(str(state_dir), "books",
                                tenant_slug(tenant),
                                "run_ledger.jsonl")
            out = []
            with open(path, encoding="utf-8") as fh:
                for line in fh:
                    entry = json.loads(line)
                    if entry.get("name") == "serve.request":
                        out.append(entry["payload"]["serve"])
            return out

        self._run(tmp_path / "solo", False)
        self._run(tmp_path / "fused", True)
        for i in range(2):  # the two requests that fused
            solo_b = books_entries(tmp_path / "solo", f"t{i}")
            fused_b = books_entries(tmp_path / "fused", f"t{i}")
            assert len(solo_b) == len(fused_b) == 1
            assert solo_b[0]["audit"] == fused_b[0]["audit"]
            assert fused_b[0].get("fused") is True
            assert "fused" not in solo_b[0]


# ---------------------------------------------------------------------
# the pad-mask contract: padding invariance of the kernel
# ---------------------------------------------------------------------


class TestPaddingInvariance:

    def test_solo_kernel_bit_identical_under_larger_row_padding(self):
        """The property every pow2 bucket stands on: padding the SAME
        request further changes nothing — masks keep padding out of
        the data plane, and the content-keyed row tie-breaks
        (counter_rng.row_bits) keep it out of the sampling plane. A
        regression here (e.g. a new shape-dependent draw) would break
        PARITY row 35 for every bucket whose edge exceeds the solo
        shape."""
        ds = make_ds(7, 7_000)
        params = fusable_params()
        config = je.FusedConfig.from_params(params, public=False)
        encoded = je.encode(ds, DataExtractors(), None, None)
        P_pad = je._pad_pow2(len(encoded.pk_vocab))
        keep_table, thr, s_scale, min_count = je.selection_inputs(
            config, 1.0, 1e-8, None)
        scales = np.asarray([0.9], np.float32)

        def run(rows_pad):
            pid, pk, values, valid = fusion.pad_request_to_bucket(
                encoded, rows_pad, config.needs_values)
            keep, raw = je.fused_aggregate_kernel(
                config, P_pad, jnp.asarray(pid), jnp.asarray(pk),
                jnp.asarray(values), jnp.asarray(valid),
                jnp.asarray(scales), jnp.asarray(keep_table),
                jnp.float32(thr), jnp.float32(s_scale),
                jnp.float32(min_count), jnp.float32(1.0),
                jax.random.PRNGKey(11), fx_bits=12)
            return (np.asarray(keep),
                    {k: np.asarray(v) for k, v in raw.items()})

        base_keep, base_raw = run(je._pad_rows(encoded.n_rows))
        for rows_pad in (16_384, 32_768):
            keep, raw = run(rows_pad)
            np.testing.assert_array_equal(base_keep, keep)
            assert set(base_raw) == set(raw)
            for k in base_raw:
                np.testing.assert_array_equal(base_raw[k], raw[k],
                                              err_msg=f"{rows_pad}:{k}")

    def test_row_bits_are_length_invariant(self):
        from pipelinedp_tpu.ops import counter_rng
        key = jax.random.PRNGKey(3)
        short = np.asarray(counter_rng.row_bits(key, 1_000))
        long = np.asarray(counter_rng.row_bits(key, 4_096))
        np.testing.assert_array_equal(short, long[:1_000])

    @pytest.mark.parametrize("accumulator", ["fx", "f32"])
    def test_vector_kernel_bit_identical_under_larger_row_padding(
            self, accumulator):
        """ISSUE-17 acceptance: VECTOR_SUM (both accumulators) holds
        the same padding invariance the scalar metrics do — any bucket
        edge >= the request's rows yields identical raw accumulator
        bits, so a vector request can ride any compatible bucket."""
        from pipelinedp_tpu import plan as plan_mod
        D = 32
        rng = np.random.default_rng(23)
        n = 7_000
        users = n // 20
        data = [(int(rng.integers(0, users)), int(rng.integers(0, 30)),
                 rng.uniform(-1.0, 1.0, D)) for _ in range(n)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=3,
            max_contributions_per_partition=2,
            vector_size=D, vector_max_norm=4.0,
            vector_norm_kind=pdp.NormKind.L2)
        with plan_mod.seam_override("vector_accumulator", accumulator):
            config = je.FusedConfig.from_params(params, public=False)
        assert config.vector_accumulator == accumulator
        import operator
        ext = DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1),
            value_extractor=operator.itemgetter(2))
        encoded = je.encode(data, ext, D, None)
        P_pad = je._pad_pow2(len(encoded.pk_vocab))
        keep_table, thr, s_scale, min_count = je.selection_inputs(
            config, 1.0, 1e-8, None)
        scales = np.asarray([0.9], np.float32)
        fx_bits = je.fused_fx_bits(config, 32_768)

        def run(rows_pad):
            pid, pk, values, valid = fusion.pad_request_to_bucket(
                encoded, rows_pad, config.needs_values)
            keep, raw = je.fused_aggregate_kernel(
                config, P_pad, jnp.asarray(pid), jnp.asarray(pk),
                jnp.asarray(values), jnp.asarray(valid),
                jnp.asarray(scales), jnp.asarray(keep_table),
                jnp.float32(thr), jnp.float32(s_scale),
                jnp.float32(min_count), jnp.float32(1.0),
                jax.random.PRNGKey(11), fx_bits=fx_bits)
            return (np.asarray(keep),
                    {k: np.asarray(v) for k, v in raw.items()})

        base_keep, base_raw = run(je._pad_rows(encoded.n_rows))
        assert "vector_sum" in base_raw
        if accumulator == "fx":
            # The accumulator really is the int32 lane plane, not a
            # float path wearing the knob.
            assert base_raw["vector_sum"].dtype == np.int32
        for rows_pad in (16_384, 32_768):
            keep, raw = run(rows_pad)
            np.testing.assert_array_equal(base_keep, keep)
            assert set(base_raw) == set(raw)
            for k in base_raw:
                np.testing.assert_array_equal(base_raw[k], raw[k],
                                              err_msg=f"{rows_pad}:{k}")


class TestBucketVectorCompatibility:
    """ISSUE-17 satellite: the bucket key carries the vector compile
    shape EXPLICITLY — two requests differing in D, norm kind or
    accumulator can never land in one fused batch."""

    @staticmethod
    def _encoded(d):
        import operator
        rng = np.random.default_rng(d)
        data = [(u, u % 7, rng.uniform(-1, 1, d)) for u in range(200)]
        ext = DataExtractors(
            privacy_id_extractor=operator.itemgetter(0),
            partition_extractor=operator.itemgetter(1),
            value_extractor=operator.itemgetter(2))
        return je.encode(data, ext, d, None)

    @staticmethod
    def _config(d, norm_kind=pdp.NormKind.L2, accumulator="f32"):
        from pipelinedp_tpu import plan as plan_mod
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.VECTOR_SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=3,
            max_contributions_per_partition=2,
            vector_size=d, vector_max_norm=4.0,
            vector_norm_kind=norm_kind)
        with plan_mod.seam_override("vector_accumulator", accumulator):
            return je.FusedConfig.from_params(params, public=False)

    def test_different_d_never_share_a_bucket(self):
        k64 = fusion.bucket_for(self._config(64), self._encoded(64),
                                8192)
        k256 = fusion.bucket_for(self._config(256), self._encoded(256),
                                 8192)
        assert k64 is not None and k256 is not None
        assert k64.vector_size == 64 and k256.vector_size == 256
        assert k64 != k256

    def test_norm_kind_and_accumulator_split_buckets(self):
        enc = self._encoded(64)
        l2 = fusion.bucket_for(self._config(64), enc, 8192)
        linf = fusion.bucket_for(
            self._config(64, norm_kind=pdp.NormKind.Linf), enc, 8192)
        fx = fusion.bucket_for(
            self._config(64, accumulator="fx"), enc, 8192)
        assert l2.vector_norm_kind == "l2"
        assert linf.vector_norm_kind == "linf"
        assert fx.vector_accumulator == "fx"
        assert len({l2, linf, fx}) == 3

    def test_scalar_requests_keep_empty_vector_fields(self):
        ds = make_ds(9, 2_000)
        config = je.FusedConfig.from_params(fusable_params(),
                                            public=False)
        encoded = je.encode(ds, DataExtractors(), None, None)
        key = fusion.bucket_for(config, encoded, 8192)
        assert (key.vector_size, key.vector_norm_kind,
                key.vector_accumulator) == (0, "", "")


# ---------------------------------------------------------------------
# kill-mid-batch: every lease resolves exactly once
# ---------------------------------------------------------------------


class TestKillMidBatch:

    def test_killed_member_keeps_reserve_companions_commit(
            self, tmp_path):
        tenants = {f"t{i}": (BIG_EPS, 1e-3) for i in range(3)}
        datasets = [make_ds(50 + i, 7_000) for i in range(3)]
        requests = [req(f"t{i}", datasets[i], seed=80 + i, rid=f"k{i}")
                    for i in range(3)]
        plan = faults.FaultPlan(fail_serve_requests=(1,))
        with faults.injected_faults(plan):
            with serve.Service(str(tmp_path / "svc"), tenants=tenants,
                               workers=2, fusion=True,
                               fuse_window_ms=250,
                               fuse_max_batch=3) as svc:
                outs = submit_concurrently(svc, requests)
        killed = [i for i, o in enumerate(outs)
                  if isinstance(o, faults.ServeKill)]
        served = [i for i, o in enumerate(outs)
                  if not isinstance(o, BaseException) and o.ok]
        assert len(killed) == 1, outs
        assert sorted(killed + served) == [0, 1, 2]
        # Exactly-once lease resolution, read back from the durable
        # ledger: the killed member's reserve STAYS SPENT (noise may
        # have been drawn), each companion committed exactly once.
        led = TenantBudgetLedger(str(tmp_path / "svc" / "budgets"))
        for i in range(3):
            debits = led.debits(f"t{i}")
            assert list(debits) == [f"k{i}"]
            expected = "reserved" if i in killed else "committed"
            assert debits[f"k{i}"]["state"] == expected, (i, debits)


# ---------------------------------------------------------------------
# one warm program per bucket
# ---------------------------------------------------------------------


class TestWarmBucketPrograms:

    def test_second_same_bucket_batch_captures_zero_new_programs(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_COSTS", "1")
        tenants = {f"t{i}": (BIG_EPS, 1e-3) for i in range(2)}
        datasets = [make_ds(60 + i, 7_000) for i in range(2)]
        with serve.Service(str(tmp_path / "svc"), tenants=tenants,
                           workers=2, fusion=True, fuse_window_ms=250,
                           fuse_max_batch=2) as svc:
            outs = submit_concurrently(svc, [
                req(f"t{i}", datasets[i], seed=90 + i, rid=f"a{i}")
                for i in range(2)])
            assert all(o.ok for o in outs), outs
            captured = obs.ledger().snapshot()["counters"].get(
                "cost.programs_captured", 0)
            outs = submit_concurrently(svc, [
                req(f"t{i}", datasets[i], seed=95 + i, rid=f"b{i}")
                for i in range(2)])
            assert all(o.ok for o in outs), outs
            after = obs.ledger().snapshot()["counters"]
            assert after.get("cost.programs_captured", 0) == captured, (
                "the second same-bucket batch captured new "
                "compile.program spans — the warm program was not "
                "reused")
            assert after.get("serve.fused_batches") == 2

    def test_single_member_window_runs_solo_program(self, tmp_path):
        """A window that expires with one request takes the solo path
        (bit-identical, already compiled) instead of compiling a B=1
        batched program."""
        with serve.Service(str(tmp_path / "svc"),
                           tenants={"t0": (BIG_EPS, 1e-3)}, workers=2,
                           fusion=True, fuse_window_ms=40,
                           fuse_max_batch=4) as svc:
            out = svc.submit(req("t0", make_ds(3, 6_000), seed=5,
                                 rid="solo1"))
            assert out.ok, out
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("serve.fusion_offered") == 1
        assert counters.get("serve.fused_batches", 0) == 0

    def test_non_fusable_params_fall_through_to_solo_queue(
            self, tmp_path):
        """Params the fused plane rejects (here: a percentile range
        whose f32 leaf constant overflows) skip the fuser entirely and
        serve through the classic path."""
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50)],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=3,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=1e-36)
        assert not je.params_are_fusable(params)
        ds = make_ds(9, 600, users=50, parts=5)
        with serve.Service(str(tmp_path / "svc"),
                           tenants={"t0": (BIG_EPS, 1e-3)}, workers=2,
                           fusion=True, fuse_window_ms=40,
                           fuse_max_batch=4) as svc:
            out = svc.submit(req("t0", ds, seed=5, rid="np1",
                                 params=params))
            assert out.ok, out
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("serve.fusion_offered", 0) == 0
        assert counters.get("serve.requests_served") == 1


# ---------------------------------------------------------------------
# quotas (ROADMAP serve item (b))
# ---------------------------------------------------------------------


class TestQuotas:

    def test_row_quota_refuses_before_any_reserve(self, tmp_path):
        ds = make_ds(1, 6_000)
        with serve.Service(str(tmp_path / "svc"),
                           tenants={"t0": (2.0, 1e-6)},
                           max_rows_per_request=1_000) as svc:
            out = svc.submit(req("t0", ds, seed=1, rid="q1"))
            assert not out.ok
            assert out.reason == "quota"
            assert "row quota" in out.detail and "1000" in out.detail
            # Nothing was reserved, nothing ran.
            assert svc.budgets.remaining("t0").epsilon == (
                pytest.approx(2.0))
            assert svc.budgets.debits("t0") == {}
        assert "quota" in serve.REFUSAL_REASONS

    def test_per_tenant_row_quota_overrides_service_default(
            self, tmp_path):
        ds = make_ds(2, 3_000)
        with serve.Service(str(tmp_path / "svc")) as svc:
            svc.register_tenant("tight", BIG_EPS, 1e-3,
                                max_rows_per_request=100)
            svc.register_tenant("loose", BIG_EPS, 1e-3)
            refused = svc.submit(req("tight", ds, seed=1, rid="r1"))
            assert not refused.ok and refused.reason == "quota"
            served = svc.submit(req("loose", ds, seed=1, rid="r2"))
            assert served.ok, served

    def test_rate_quota_windows_on_the_injectable_clock(self, tmp_path):
        clock = FakeClock()
        ds = make_ds(3, 2_000, users=200, parts=5)
        with serve.Service(str(tmp_path / "svc"),
                           tenants={"t0": (BIG_EPS, 1e-3)},
                           max_reqs_per_s=2, clock=clock) as svc:
            assert svc.submit(req("t0", ds, seed=1, rid="h1")).ok
            assert svc.submit(req("t0", ds, seed=2, rid="h2")).ok
            third = svc.submit(req("t0", ds, seed=3, rid="h3"))
            assert not third.ok and third.reason == "quota"
            assert "rate quota" in third.detail
            # The refusal itself must not consume window slots, and
            # the window slides: one second later the tenant is
            # admitted again.
            clock.sleep(1.01)
            assert svc.submit(req("t0", ds, seed=4, rid="h4")).ok


# ---------------------------------------------------------------------
# heartbeat: live bucket occupancy
# ---------------------------------------------------------------------


class TestHeartbeatOccupancy:

    def test_monitor_embeds_fusion_snapshot_in_serve_section(
            self, tmp_path):
        clock = FakeClock()
        mon = obs_monitor.Monitor(
            clock=clock, interval_s=1.0, stall_s=60.0,
            heartbeat_path=str(tmp_path / "hb.json")).start_inline()
        try:
            obs_monitor.update_fusion(
                {"window_ms": 8, "max_batch": 8, "queued": 3,
                 "buckets": {"abc@r8192p64": {
                     "queued": 3, "rows": 8192, "partitions": 64,
                     "window_remaining_s": 0.004}}})
            hb = mon.poll_once()
            assert hb["serve"]["fusion"]["queued"] == 3
            bucket = hb["serve"]["fusion"]["buckets"]["abc@r8192p64"]
            assert bucket["window_remaining_s"] == 0.004
            obs_monitor.update_fusion(None)
            assert "serve" not in mon.poll_once()
        finally:
            # Disarm activity tracking: an armed monitor would hand
            # every later test in this process the measuring tracer.
            obs_monitor.update_fusion(None)
            mon.stop()

    def test_live_fuser_pushes_bucket_occupancy(self, tmp_path):
        with serve.Service(str(tmp_path / "svc"),
                           tenants={"t0": (BIG_EPS, 1e-3)}, workers=2,
                           fusion=True, fuse_window_ms=700,
                           fuse_max_batch=4) as svc:
            seen = []

            def submit_one():
                seen.append(svc.submit(
                    req("t0", make_ds(4, 6_000), seed=6, rid="hb1")))

            t = threading.Thread(target=submit_one)
            t.start()
            # The request sits in its bucket for up to the 700ms
            # window; the pushed snapshot must show it queued.
            deadline = 200
            snap = None
            while deadline:
                snap = obs_monitor.fusion_snapshot()
                if snap and snap.get("queued") == 1:
                    break
                deadline -= 1
                t.join(timeout=0.005)
            assert snap and snap.get("queued") == 1, snap
            (label, bucket), = snap["buckets"].items()
            assert bucket["rows"] == 8192 and bucket["queued"] == 1
            assert bucket["window_remaining_s"] > 0
            t.join()
            assert seen[0].ok, seen[0]
        # The closed fuser clears its heartbeat registration.
        assert obs_monitor.fusion_snapshot() is None


# ---------------------------------------------------------------------
# bench/compare integration
# ---------------------------------------------------------------------


class TestCompareRefusal:

    def test_compare_refuses_cross_fusion_gating(self, monkeypatch):
        import bench

        class _StubLedger:
            fingerprint = "f" * 16

            def baseline(self, metric):
                if metric == "serve_fused_throughput":
                    return ({"ts": 1.0, "payload": {"record": {
                        "value": 100.0, "fusion": False,
                        "kernel_backend": "xla"}}}, False)
                return (None, False)

        monkeypatch.setattr(bench, "_bench_ledger",
                            lambda: _StubLedger())
        rec = {"metric": "serve_fused_throughput", "value": 10.0,
               "unit": "req/s", "fusion": True,
               "kernel_backend": "xla"}
        regressions = bench.compare_to_baseline(records=[rec])
        assert regressions["fusion_mismatches"] == 1
        assert regressions["regressed"] == []  # refused, not gated
        (entry,) = regressions["rates"]
        assert entry["fusion_mismatch"] is True
        assert entry["baseline_fusion"] is False
        line = bench.compare_verdict_line(regressions)
        assert line.startswith("COMPARE: fusion-mode mismatch")
