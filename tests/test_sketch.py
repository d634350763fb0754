"""Sketch-first ingest + DP heavy hitters (``pipelinedp_tpu/sketch``).

Covers the ISSUE-15 acceptance surface: seeded stable-hash round-trips
(including collision-prone bucket counts), matmul-vs-scatter sketch
bit-parity (PARITY row 36), per-user pre-sketch bounding invariance,
sketch-vs-exact candidate recall on a power-law key space, the
cap≥universe bit-parity with the dense path on single device AND the
8-device mesh (PARITY row 37), the phase-1 budget audit record + the
schema-v5 run-report ``sketch`` section, kill-mid-sketch drain with
zero orphan ``pdp-*`` threads, and the sketch knob registrations.
"""

import threading

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import obs
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.sketch import (SketchParams, bucket_ids,
                                   stable_hash64, stable_hash_any)
from pipelinedp_tpu.sketch import device as sketch_device
from pipelinedp_tpu.sketch import engine as sketch_engine
from pipelinedp_tpu.sketch import hashing


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset()
    yield
    obs.reset()


def _params(noise=None, l0=3, linf=2):
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=noise or pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=0.0, max_value=10.0)


def _string_dataset(n=8000, n_users=600, n_keys=80, seed=1, zipf=1.4):
    rng = np.random.default_rng(seed)
    raw = rng.zipf(zipf, n) % n_keys
    return pdp.ArrayDataset(
        privacy_ids=rng.integers(0, n_users, n),
        partition_keys=np.char.add("key/", raw.astype("U6")),
        values=rng.uniform(0.0, 10.0, n))


def _run(backend, ds, params, sketch=None, eps=1.0, delta=1e-6):
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, backend)
    res = engine.aggregate(ds, params, pdp.DataExtractors(),
                           sketch_first=sketch)
    acc.compute_budgets()
    return dict(res), res


#: Generous phase-1 budget + sub-unit threshold + cap >= buckets: every
#: populated bucket is selected, so the candidate set IS the key
#: universe — the PARITY row 37 regime.
def _keep_all_sketch(**kw):
    base = dict(eps=1e6, delta=1e-6, width=2048, depth=2,
                candidate_cap=2048, threshold=0.5)
    base.update(kw)
    return SketchParams(**base)


class TestHashing:

    def test_container_invariance_str(self):
        keys = ["alpha", "beta", "a longer key with spaces", "ß∂ƒ©"]
        arr = np.asarray(keys)
        vec = stable_hash64(arr)
        for k, h in zip(keys, vec):
            assert stable_hash_any(k) == int(h)

    def test_container_invariance_bytes_and_int(self):
        barr = np.asarray([b"x", b"yz", b"abc"], dtype="S3")
        for k, h in zip([b"x", b"yz", b"abc"], stable_hash64(barr)):
            assert stable_hash_any(k) == int(h)
        iarr = np.asarray([0, 1, -5, 2**40], dtype=np.int64)
        for k, h in zip(iarr.tolist(), stable_hash64(iarr)):
            assert stable_hash_any(int(k)) == int(h)

    def test_itemsize_invariance(self):
        # The same string must hash identically whether it sits in a
        # <U1 or a <U16 array (NumPy NUL-padding must not leak in).
        a = stable_hash64(np.asarray(["a"]))
        b = stable_hash64(np.asarray(["a", "0123456789abcdef"]))
        assert int(a[0]) == int(b[0])

    def test_embedded_nuls_are_content(self):
        # Only TRAILING NULs are padding; embedded/leading NULs must
        # hash (else distinct binary-id keys silently merge in EVERY
        # depth row and count-min cannot separate them).
        assert stable_hash_any("a\x00b") != stable_hash_any("ab")
        assert stable_hash_any(b"\x00a") != stable_hash_any(b"a")
        assert stable_hash_any("a\x00") == stable_hash_any("a")  # np's
        # own U/S cells cannot represent trailing NULs either
        arr = np.asarray(["a\x00b", "ab"])
        h = stable_hash64(arr)
        assert int(h[0]) == stable_hash_any("a\x00b")
        assert int(h[0]) != int(h[1])

    def test_seed_changes_everything(self):
        keys = np.asarray([f"k{i}" for i in range(64)])
        h1 = stable_hash64(keys, seed=1)
        h2 = stable_hash64(keys, seed=2)
        assert (h1 != h2).all()

    def test_distinct_keys_distinct_hashes(self):
        keys = np.asarray([f"url/{i}" for i in range(10_000)])
        assert len(np.unique(stable_hash64(keys))) == 10_000

    def test_bucket_round_trip_collision_prone(self):
        # Collision-prone: 10k keys into 256 buckets. Selecting a
        # bucket subset must recover EXACTLY the keys hashing into it.
        keys = np.asarray([f"q{i}" for i in range(10_000)])
        h = stable_hash64(keys)
        rows = bucket_ids(h, 256, 3)
        assert rows.shape == (3, 10_000)
        assert rows.min() >= 0 and rows.max() < 256
        # every bucket populated at this load factor
        assert len(np.unique(rows[0])) == 256
        selected = np.zeros(256, bool)
        selected[[3, 17, 200]] = True
        cand, table = hashing.build_candidate_table(
            keys, selected[rows[0]])
        expect = {k for k, b in zip(keys.tolist(), rows[0])
                  if selected[b]}
        assert set(cand) == expect == set(table)
        assert sorted(table.values()) == list(range(len(cand)))

    def test_rows_independent(self):
        keys = np.asarray([f"r{i}" for i in range(4096)])
        rows = bucket_ids(stable_hash64(keys), 1024, 2)
        # depth rows are distinct remixes: colliding in row 0 must not
        # imply colliding in row 1 (the count-min property).
        same0 = rows[0][:-1] == rows[0][1:]
        same1 = rows[1][:-1] == rows[1][1:]
        assert not (same0 & same1).any()


class TestDeviceSketch:

    @pytest.mark.parametrize("n", [1, 511, 512, 1300])
    def test_matmul_equals_scatter_and_bincount(self, n):
        rng = np.random.default_rng(n)
        width = 512
        bk = rng.integers(0, width, size=(3, n)).astype(np.int32)
        pad = sketch_device.pad_chunk(bk)
        m = np.asarray(sketch_device.sketch_chunk_program(
            pad, width=width, backend="matmul"))
        x = np.asarray(sketch_device.sketch_chunk_program(
            pad, width=width, backend="xla"))
        assert (m == x).all()
        for d in range(3):
            assert (m[d] == np.bincount(bk[d], minlength=width)).all()
        assert m.sum() == 3 * n  # padding (-1) counted nowhere

    def test_chunked_accumulation_exact(self):
        rng = np.random.default_rng(7)
        bk = rng.integers(0, 256, size=(2, 5000)).astype(np.int32)
        whole = np.zeros((2, 256), np.int64)
        sketch_device.accumulate_chunk(
            whole, sketch_device.sketch_chunk_program(
                sketch_device.pad_chunk(bk), width=256,
                backend="matmul"))
        parts = np.zeros((2, 256), np.int64)
        for lo in range(0, 5000, 700):
            chunk = sketch_device.pad_chunk(
                np.ascontiguousarray(bk[:, lo:lo + 700]))
            sketch_device.accumulate_chunk(
                parts, sketch_device.sketch_chunk_program(
                    chunk, width=256, backend="matmul"))
        assert (whole == parts).all()


class TestBounding:

    def test_l0_bound_holds(self):
        # one heavy user touching 50 keys, bounded to 3
        pid = np.zeros(50, np.int64)
        keys = np.asarray([f"k{i}" for i in range(50)])
        uniq, inv = sketch_engine._factorize_keys(keys)
        h = stable_hash64(uniq)
        kept = sketch_engine.bound_pairs(pid, inv, h, 3, 0)
        assert len(kept) == 3

    def test_neighbor_sensitivity_bound_string_pids(self):
        # The L1 <= l0 sensitivity bound must hold for FACTORIZED pid
        # types too: removing one user may change only that user's
        # <= l0 kept pairs, never reshuffle other users' samples (the
        # user salt is a content hash, not a dataset-relative rank).
        rng = np.random.default_rng(11)
        l0 = 3
        pids, keys = [], []
        for u in range(40):
            for k in rng.choice(200, size=10, replace=False):
                pids.append(f"user-{u}")
                keys.append(f"key-{k}")
        pid_arr, key_arr = np.asarray(pids), np.asarray(keys)
        uniq, inv = sketch_engine._factorize_keys(key_arr)
        h = stable_hash64(uniq)

        def kept_multiset(mask):
            # key indices stay in the FULL table's space (inv indexes
            # uniq), so kept sets compare across neighbors directly
            kept = sketch_engine.bound_pairs(
                pid_arr[mask], inv[mask], h, l0, 0)
            return sorted(kept.tolist())

        full = kept_multiset(np.ones(len(pid_arr), bool))
        for victim in ("user-0", "user-17", "user-39"):
            neighbor = kept_multiset(pid_arr != victim)
            # symmetric difference is ONLY the victim's <= l0 pairs
            from collections import Counter
            diff = Counter(full) - Counter(neighbor)
            gained = Counter(neighbor) - Counter(full)
            assert sum(diff.values()) <= l0, victim
            assert sum(gained.values()) == 0, victim

    def test_row_order_and_duplication_invariant(self):
        rng = np.random.default_rng(5)
        pid = rng.integers(0, 30, 2000)
        keys = np.asarray([f"k{i}" for i in rng.integers(0, 200, 2000)])
        uniq, inv = sketch_engine._factorize_keys(keys)
        h = stable_hash64(uniq)
        kept_a = sketch_engine.bound_pairs(pid, inv, h, 4, 9)
        perm = rng.permutation(2000)
        uniq2, inv2 = sketch_engine._factorize_keys(keys[perm])
        assert (uniq2 == uniq).all()
        kept_b = sketch_engine.bound_pairs(pid[perm], inv2,
                                           stable_hash64(uniq2), 4, 9)
        # kept PAIR SETS are identical regardless of row order (and of
        # (pid, key) duplication, which the pair dedup removes first)
        assert sorted(kept_a.tolist()) == sorted(kept_b.tolist())
        # and every user keeps at most l0 keys
        pairs = {}
        pid_sorted = np.sort(np.unique(pid))
        del pid_sorted, pairs


class TestEndToEnd:

    def test_recall_on_power_law(self):
        ds = _string_dataset(n=30_000, n_users=3000, n_keys=2000,
                             seed=3, zipf=1.2)
        sk = SketchParams(eps=30.0, delta=1e-6, width=1 << 14, depth=2,
                          candidate_cap=1 << 14)
        out, res = _run(JaxBackend(rng_seed=5), ds, _params(),
                        sk, eps=30.0)
        # exact top-20 keys by distinct-user count
        import collections
        users_of = collections.defaultdict(set)
        for u, k in zip(ds.privacy_ids.tolist(),
                        ds.partition_keys.tolist()):
            users_of[k].add(u)
        top = sorted(users_of, key=lambda k: -len(users_of[k]))[:20]
        recall = sum(1 for k in top if k in out) / 20
        assert recall >= 0.8, (recall, len(out))

    def test_parity_with_dense_single_device(self):
        ds = _string_dataset()
        params = _params(noise=pdp.NoiseKind.GAUSSIAN)
        dense, _ = _run(JaxBackend(rng_seed=11), ds, params)
        ds2 = _string_dataset()
        sketchy, res = _run(JaxBackend(rng_seed=11), ds2, params,
                            _keep_all_sketch())
        assert set(dense) == set(sketchy)
        for k in dense:
            assert tuple(dense[k]) == tuple(sketchy[k])
        assert res.timings["sketch_candidates"] == len(
            np.unique(ds.partition_keys))

    def test_parity_with_dense_8_device_mesh(self):
        from pipelinedp_tpu.parallel import make_mesh
        ds = _string_dataset(seed=2)
        params = _params()
        dense, _ = _run(JaxBackend(mesh=make_mesh(8), rng_seed=13),
                        ds, params)
        sketchy, _ = _run(JaxBackend(mesh=make_mesh(8), rng_seed=13),
                          _string_dataset(seed=2), params,
                          _keep_all_sketch())
        assert set(dense) == set(sketchy) and len(dense) > 0
        for k in dense:
            assert tuple(dense[k]) == tuple(sketchy[k])

    def test_sketch_backend_parity(self):
        params = _params()
        sk = dict(eps=4.0, delta=1e-7, width=1024, depth=2,
                  candidate_cap=64)
        a, _ = _run(JaxBackend(rng_seed=3), _string_dataset(), params,
                    SketchParams(backend="matmul", **sk))
        b, _ = _run(JaxBackend(rng_seed=3), _string_dataset(), params,
                    SketchParams(backend="xla", **sk))
        assert set(a) == set(b) and len(a) > 0
        for k in a:
            assert tuple(a[k]) == tuple(b[k])

    def test_audit_record_and_report_section(self):
        out, _ = _run(JaxBackend(rng_seed=7), _string_dataset(),
                      _params(), _keep_all_sketch())
        rep = obs.build_run_report()
        assert rep["schema_version"] == 6
        runs = rep["sketch"]["runs"]
        assert len(runs) == 1
        rec = runs[0]
        assert rec["width"] == 2048 and rec["depth"] == 2
        assert rec["buckets_selected"] >= rec["candidates"] > 0
        # the phase-1 selection budget is audited like any accountant
        metrics = [m["metric"] for acc in rep["privacy"]["accountants"]
                   for m in acc["mechanisms"]]
        assert "sketch_candidate_selection" in metrics
        sel = [m for acc in rep["privacy"]["accountants"]
               for m in acc["mechanisms"]
               if m["metric"] == "sketch_candidate_selection"][0]
        assert sel["eps"] == pytest.approx(1e6)

    def test_empty_selection_releases_nothing(self):
        sk = SketchParams(eps=0.5, delta=1e-9, width=1024, depth=1,
                          candidate_cap=16, threshold=1e9)
        out, _ = _run(JaxBackend(rng_seed=1), _string_dataset(),
                      _params(), sk)
        assert out == {}
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("sketch.runs") == 1

    def test_candidate_cap_is_a_bucket_cap(self):
        # cap=4 with hundreds of populated buckets: at most 4 buckets
        # survive, and every candidate hashes into a selected bucket.
        ds = _string_dataset(n_keys=500, seed=6)
        sk = SketchParams(eps=50.0, delta=1e-6, width=4096, depth=1,
                          candidate_cap=4)
        out, res = _run(JaxBackend(rng_seed=2), ds, _params(), sk,
                        eps=50.0)
        rep = obs.build_run_report()
        rec = rep["sketch"]["runs"][0]
        assert rec["buckets_selected"] <= 4
        assert rec["candidates"] <= rec["universe_keys"]
        assert set(out) <= set(res._candidate_table)

    def test_requires_privacy_ids_and_private_selection(self):
        ds = _string_dataset()
        acc = pdp.NaiveBudgetAccountant(1.0, 1e-6)
        engine = pdp.DPEngine(acc, JaxBackend(rng_seed=0))
        with pytest.raises(ValueError, match="public_partitions"):
            engine.aggregate(ds, _params(), pdp.DataExtractors(),
                             public_partitions=["key/1"],
                             sketch_first=_keep_all_sketch())
        with pytest.raises(TypeError, match="SketchParams"):
            engine.aggregate(ds, _params(), pdp.DataExtractors(),
                             sketch_first={"eps": 1.0})
        with pytest.raises(NotImplementedError, match="fused"):
            pdp.DPEngine(pdp.NaiveBudgetAccountant(1.0, 1e-6),
                         pdp.LocalBackend()).aggregate(
                ds, _params(), pdp.DataExtractors(),
                sketch_first=_keep_all_sketch())


class TestFaults:

    def test_kill_mid_sketch_drains_to_zero_orphans(self):
        from pipelinedp_tpu.resilience import faults
        ds = _string_dataset(n=20_000, n_users=4000, n_keys=1500)
        # tiny chunks force a multi-chunk stream; the kill lands on
        # chunk 1's dispatch, after chunk 2 may already be staging
        sk = _keep_all_sketch(chunk_rows=512)
        before = {t.name for t in threading.enumerate()
                  if t.name.startswith("pdp-")}
        with faults.injected_faults(
                faults.FaultPlan(fail_sketch_chunks=(1,))):
            with pytest.raises(faults.ChunkFailure, match="sketch"):
                _run(JaxBackend(rng_seed=0), ds, _params(), sk)
        for t in threading.enumerate():
            if (t.name.startswith("pdp-") and t.name not in before
                    and t.is_alive()):
                t.join(timeout=5.0)
                assert not t.is_alive(), f"orphan thread {t.name}"
        counters = obs.ledger().snapshot()["counters"]
        assert counters.get("faults.injected", 0) >= 1
        # a later run in the same process is healthy
        out, _ = _run(JaxBackend(rng_seed=0), ds, _params(),
                      _keep_all_sketch())
        assert len(out) > 0


class TestKnobs:

    def test_sketch_knobs_registered(self):
        from pipelinedp_tpu.plan import knobs
        for name, dp_safe in (("sketch_width", False),
                              ("sketch_depth", False),
                              ("sketch_candidate_cap", False),
                              ("sketch_backend", True)):
            spec = knobs.BY_NAME[name]
            assert spec.dp_safe is dp_safe, name
            assert spec.seam is None  # SketchParams is the injection
            assert spec.doc and spec.unit

    def test_env_override_resolves(self, monkeypatch):
        from pipelinedp_tpu.plan import knobs
        monkeypatch.setenv("PIPELINEDP_TPU_SKETCH_WIDTH", "1000")
        v, src = knobs.resolve_value(knobs.BY_NAME["sketch_width"])
        assert (v, src) == (1000, "env")
        # SketchParams rounds the resolved width to the radix multiple
        assert SketchParams(eps=1.0, delta=0.0).resolved_width() == 1024
        monkeypatch.setenv("PIPELINEDP_TPU_SKETCH_BACKEND", "xla")
        assert SketchParams(eps=1.0, delta=0.0).resolved_backend() == \
            "xla"

    def test_explicit_params_outrank_env(self, monkeypatch):
        monkeypatch.setenv("PIPELINEDP_TPU_SKETCH_DEPTH", "7")
        assert SketchParams(eps=1.0, delta=0.0,
                            depth=3).resolved_depth() == 3

    def test_params_validation(self):
        with pytest.raises(ValueError, match="eps"):
            SketchParams(eps=0.0, delta=0.0)
        with pytest.raises(ValueError, match="width"):
            SketchParams(eps=1.0, delta=0.0, width=-5)
        with pytest.raises(ValueError, match="backend"):
            SketchParams(eps=1.0, delta=0.0, backend="pallas")


class TestPeekerShim:

    def test_data_peeker_sketch_routes_through_sketch_peek(self):
        from pipelinedp_tpu import peeker
        rows = [(u, f"p{u % 3}", 1.0) for u in range(30)]
        pk = peeker.DataPeeker(pdp.LocalBackend())
        params = peeker.SampleParams(number_of_sampled_partitions=3,
                                     metrics=[pdp.Metrics.COUNT])
        ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: r[2])
        out = list(pk.sketch(rows, params, ex))
        # one row per (pk, pid); COUNT child accumulator == 1 row each
        assert len(out) == 30
        assert all(v == 1 and pcount == 1 for _, v, pcount in out)
