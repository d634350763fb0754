"""Topology-aware collectives: the two-axis ("dcn", "ici") mesh view.

The hierarchical exchange (`mesh_topology=hier`) must be BIT-IDENTICAL
to the flat one for every released value and kept set — that is the
knob's dp-safety contract (PARITY row 43) — while moving strictly fewer
bytes across the host (DCN) boundary. This file is the in-process half
of that proof, on the 8-device CPU mesh with simulated hosts
(``PIPELINEDP_TPU_MESH_HOSTS``); ``test_multihost.py`` repeats the
parity and byte assertions across a real two-process gloo boundary.
``make topocheck`` runs this file plus the collective-confinement lint.
"""

import contextlib
import os

import numpy as np
import pytest

import jax

import pipelinedp_tpu as pdp
from pipelinedp_tpu import obs
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.ops import noise as noise_ops
from pipelinedp_tpu.parallel import sharded as psh
from pipelinedp_tpu.resilience import (CheckpointStore, FaultPlan,
                                       injected_faults)

BIG_EPS = 1e5

TOPOLOGY_ENV = "PIPELINEDP_TPU_MESH_TOPOLOGY"
HOSTS_ENV = psh._MESH_HOSTS_ENV


@pytest.fixture(autouse=True)
def _isolated_topology_registry():
    """Meshes registered by a test (notably a flat topology with
    simulated hosts, whose device order — and registry key — collides
    with the plain flat mesh) must not leak into other files."""
    saved = dict(psh._TOPOLOGIES)
    yield
    psh._TOPOLOGIES.clear()
    psh._TOPOLOGIES.update(saved)


@contextlib.contextmanager
def topology_env(mode=None, hosts=None):
    """Pin the mesh_topology knob (env outranks seam and default) and the
    simulated host count for the duration — make_mesh reads both; the
    registered topology is what the kernels consult afterwards."""
    pairs = ((TOPOLOGY_ENV, mode),
             (HOSTS_ENV, None if hosts is None else str(hosts)))
    saved = {k: os.environ.get(k) for k, _ in pairs}
    for k, v in pairs:
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def device_ids(mesh):
    return [int(d.id) for d in mesh.devices.reshape(-1)]


def require_8():
    assert len(jax.devices()) >= 8, (
        "conftest must provide 8 virtual devices")


# ---------------------------------------------------------------------------
# The registry: interleave order, fallbacks, reform preservation
# ---------------------------------------------------------------------------

class TestTopologyRegistry:

    def test_default_mesh_is_flat_in_natural_order(self):
        require_8()
        mesh = psh.make_mesh(8)
        topo = psh.topology_of(mesh)
        assert topo.mode == "flat"
        assert not topo.hierarchical
        assert not topo.multi_host
        assert device_ids(mesh) == list(range(8))

    def test_hier_interleaves_simulated_hosts(self):
        require_8()
        with topology_env("hier", 2):
            mesh = psh.make_mesh(8)
        topo = psh.topology_of(mesh)
        assert (topo.mode, topo.n_hosts, topo.per_host) == ("hier", 2, 4)
        assert topo.simulated and topo.hierarchical and topo.multi_host
        # Position p = j*H + h holds host h's j-th device: hosts are
        # the contiguous id halves [0..3] and [4..7], interleaved.
        assert device_ids(mesh) == [0, 4, 1, 5, 2, 6, 3, 7]
        assert psh._ici_groups(topo) == [[0, 2, 4, 6], [1, 3, 5, 7]]
        assert psh._dcn_groups(topo) == [[0, 1], [2, 3], [4, 5], [6, 7]]

    def test_hier_on_single_host_degrades_to_flat(self):
        require_8()
        with topology_env("hier", None):
            mesh = psh.make_mesh(8)
        topo = psh.topology_of(mesh)
        assert topo.mode == "flat" and not topo.hierarchical
        assert device_ids(mesh) == list(range(8))

    def test_auto_resolves_by_host_count(self):
        require_8()
        with topology_env("auto", 2):
            assert psh.topology_of(psh.make_mesh(8)).mode == "hier"
        with topology_env("auto", None):
            assert psh.topology_of(psh.make_mesh(8)).mode == "flat"

    def test_ragged_hosts_fall_back_with_event(self, monkeypatch):
        require_8()
        devices = jax.devices()[:8]
        monkeypatch.setattr(
            psh, "_host_groups",
            lambda d: ([list(d[:3]), list(d[3:])], True))
        obs.reset()
        with topology_env("hier", None):
            mesh = psh.make_mesh(8)
        topo = psh.topology_of(mesh)
        assert topo.mode == "flat"
        assert device_ids(mesh) == [int(d.id) for d in devices]
        events = [e for e in obs.ledger().snapshot()["events"]
                  if e["name"] == "mesh.topology_fallback"]
        assert events and events[0]["reason"] == "ragged_hosts"

    def test_plain_mesh_built_elsewhere_is_flat(self):
        require_8()
        mesh = psh.Mesh(np.asarray(jax.devices()[:8]), ("data",))
        topo = psh.topology_of(mesh)
        assert topo.mode == "flat" and topo.n_devices == 8
        assert psh.topology_of(None).n_devices == 1

    def test_reform_preserves_hier_within_hosts(self):
        """8 -> 4 under hier(2,4): the divisor prefix of the interleave
        is [0,4,1,5] — each host sheds its highest-slot devices and the
        survivors regroup within their host as hier(2,2)."""
        require_8()
        with topology_env("hier", 2):
            mesh = psh.make_mesh(8)
        obs.reset()
        half = psh.reform_mesh(mesh)
        topo = psh.topology_of(half)
        assert device_ids(half) == [0, 4, 1, 5]
        assert (topo.mode, topo.n_hosts, topo.per_host) == ("hier", 2, 2)
        ev = [e for e in obs.ledger().snapshot()["events"]
              if e["name"] == "mesh.reformed"]
        assert ev and ev[0]["topology"] == "hier" and ev[0]["hosts"] == 2
        # 4 -> 2: still a valid hier interleave (one device per host,
        # exchange degenerates but the grouping stays host-aligned).
        quarter = psh.reform_mesh(half)
        assert device_ids(quarter) == [0, 4]
        t2 = psh.topology_of(quarter)
        assert (t2.mode, t2.n_hosts, t2.per_host) == ("hier", 2, 1)
        assert not t2.hierarchical
        # 2 -> 1: the host count no longer divides — degrade to flat.
        last = psh.reform_mesh(quarter)
        assert device_ids(last) == [0]
        assert psh.topology_of(last).mode == "flat"


# ---------------------------------------------------------------------------
# Collective-level parity + the comms byte meter
# ---------------------------------------------------------------------------

def _run_collective(mesh, x_global, body):
    """shard_map `body(local_vec, axis, topo)` over dim 0 of
    ``x_global`` (one row per mesh position), owner-sharded output."""
    axis = mesh.axis_names[0]
    topo = psh.topology_of(mesh)
    fn = psh.shard_map(
        lambda v: body(v[0], axis, topo),
        mesh=mesh, in_specs=psh.PSpec(axis), out_specs=psh.PSpec(axis),
        check_vma=False)
    return np.asarray(jax.jit(fn)(x_global))


def _run_replicated(mesh, x_global, body):
    axis = mesh.axis_names[0]
    topo = psh.topology_of(mesh)
    fn = psh.shard_map(
        lambda v: body(v[0], axis, topo),
        mesh=mesh, in_specs=psh.PSpec(axis), out_specs=psh.PSpec(),
        check_vma=False)
    return np.asarray(jax.jit(fn)(x_global))


def _meshes_flat_and_hier(n=8, hosts=2):
    """(flat mesh, hier mesh) over the same devices; the flat one is
    built WITH simulated hosts so its exchange bytes are attributed to
    DCN — the apples-to-apples byte comparison of the two policies."""
    with topology_env("flat", hosts):
        flat = psh.make_mesh(n)
    with topology_env("hier", hosts):
        hier = psh.make_mesh(n)
    return flat, hier


class TestCollectiveParity:

    def _data(self, cols, seed=3):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 1 << 20, (8, cols)).astype(np.int32)

    def test_owner_scatter_bit_identical_and_fewer_dcn_bytes(self):
        """The acceptance pair in one trace: hier == flat bitwise on
        integer payloads, and the hier two-stage scatter crosses the
        host boundary with strictly fewer (estimated) bytes."""
        require_8()
        x = self._data(8 * 288)  # distinctive width: fresh jit traces
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        want = x.sum(axis=0, dtype=np.int32)

        scatter = lambda v, axis, topo: psh.scatter_to_owner(
            v, axis, dim=0, topo=topo)
        obs.reset()
        got_flat = _run_collective(flat_mesh, x, scatter)
        flat_c = dict(obs.ledger().snapshot()["counters"])
        obs.reset()
        got_hier = _run_collective(hier_mesh, x, scatter)
        hier_c = dict(obs.ledger().snapshot()["counters"])

        np.testing.assert_array_equal(got_flat, want)
        np.testing.assert_array_equal(got_hier, got_flat)
        assert flat_c.get("comms.dcn_bytes", 0) > 0
        assert hier_c.get("comms.dcn_bytes", 0) > 0
        assert hier_c["comms.dcn_bytes"] < flat_c["comms.dcn_bytes"]
        assert hier_c.get("comms.ici_bytes", 0) > 0
        assert hier_c.get("comms.collectives", 0) >= 2

    def test_replicating_psum_bit_identical(self):
        require_8()
        x = self._data(8 * 160, seed=4)
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        body = lambda v, axis, topo: psh.combine_shards(
            v, axis, 0, True, topo=topo)
        got_flat = _run_replicated(flat_mesh, x, body)
        got_hier = _run_replicated(hier_mesh, x, body)
        np.testing.assert_array_equal(got_flat,
                                      x.sum(axis=0, dtype=np.int32))
        np.testing.assert_array_equal(got_hier, got_flat)

    def test_replicate_indivisible_block_falls_back_flat(self):
        """Payload the per-host split cannot tile (size % per_host != 0)
        keeps the flat psum — the pass-B tile-block contract."""
        require_8()
        x = self._data(42, seed=5)  # 42 % 4 != 0
        _, hier_mesh = _meshes_flat_and_hier()
        body = lambda v, axis, topo: psh.combine_shards(
            v, axis, 0, True, topo=topo)
        got = _run_replicated(hier_mesh, x, body)
        np.testing.assert_array_equal(got, x.sum(axis=0, dtype=np.int32))

    def test_gather_blocks_byte_identical(self):
        require_8()
        x = self._data(8 * 64, seed=6)
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        body = lambda v, axis, topo: psh.gather_blocks(
            v, axis, dim=0, topo=topo)
        got_flat = _run_replicated(flat_mesh, x, body)
        got_hier = _run_replicated(hier_mesh, x, body)
        np.testing.assert_array_equal(got_flat, x.reshape(-1))
        np.testing.assert_array_equal(got_hier, got_flat)

    def test_single_host_flat_records_no_dcn(self):
        require_8()
        x = self._data(8 * 96, seed=7)
        mesh = psh.make_mesh(8)  # flat, one (real) host
        obs.reset()
        _run_collective(mesh, x, lambda v, axis, topo:
                        psh.scatter_to_owner(v, axis, dim=0, topo=topo))
        c = obs.ledger().snapshot()["counters"]
        assert c.get("comms.dcn_bytes", 0) == 0
        assert c.get("comms.ici_bytes", 0) > 0


class TestCommsSurfaces:

    def test_metrics_endpoint_renders_comms_counters(self):
        from pipelinedp_tpu.obs import metrics
        text = metrics.render_prometheus(
            {"comms.collectives": 3, "comms.ici_bytes": 128,
             "comms.dcn_bytes": 64})
        assert "pdp_comms_ici_bytes_total 128" in text
        assert "pdp_comms_dcn_bytes_total 64" in text
        assert "pdp_comms_collectives_total 3" in text

    def test_heartbeat_carries_comms_section(self, tmp_path):
        from pipelinedp_tpu.obs import monitor as obs_monitor
        mon = obs_monitor.Monitor(
            heartbeat_path=str(tmp_path / "hb.json"), run_name="t")
        counters = {"comms.collectives": 5, "comms.ici_bytes": 1024,
                    "comms.dcn_bytes": 256}
        hb = mon._build_heartbeat(mon._t_start + 1.0, [], [], counters,
                                  False, 0.0)
        assert hb["comms"] == {"collectives": 5, "ici_bytes": 1024,
                               "dcn_bytes": 256}
        hb2 = mon._build_heartbeat(mon._t_start + 1.0, [], [], {},
                                   False, 0.0)
        assert "comms" not in hb2


# ---------------------------------------------------------------------------
# End-to-end engine parity: hier vs flat release bit-identity
# ---------------------------------------------------------------------------

def extractors():
    import operator
    return pdp.DataExtractors(
        privacy_id_extractor=operator.itemgetter(0),
        partition_extractor=operator.itemgetter(1),
        value_extractor=operator.itemgetter(2))


def run(backend, data, params, eps=5.0, delta=1e-6):
    noise_ops.seed_host_rng(0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, backend)
    result = engine.aggregate(data, params, extractors())
    acc.compute_budgets()
    return dict(result)


def assert_bit_identical(got_a, got_b):
    """EXACT equality of every released metric — noisy floats included —
    and of the kept-partition sets: the bit-parity contract."""
    assert set(got_a) == set(got_b), (
        f"kept sets differ: {sorted(set(got_a) ^ set(got_b))}")
    for k in got_a:
        ta, tb = got_a[k], got_b[k]
        assert ta._fields == tb._fields
        for f in ta._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ta, f)), np.asarray(getattr(tb, f)),
                err_msg=f"partition {k}.{f}")


class TestEngineBitParity:
    """Real noise, real private selection, MODERATE eps: any grouping
    drift in the two-stage exchange shows up as a float mismatch."""

    def _data(self, n=3000, parts=6, seed=5):
        rng = np.random.default_rng(seed)
        return [(u, f"p{u % parts}", float(v))
                for u, v in enumerate(rng.uniform(0, 100, n))]

    def _params(self):
        return pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                     pdp.Metrics.PERCENTILE(50)],
            max_partitions_contributed=2,
            max_contributions_per_partition=4,
            min_value=0.0, max_value=100.0)

    def test_hier_matches_flat_on_8_device_mesh(self):
        require_8()
        data, params = self._data(), self._params()
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        got_flat = run(JaxBackend(mesh=flat_mesh, rng_seed=20), data,
                       params)
        got_hier = run(JaxBackend(mesh=hier_mesh, rng_seed=20), data,
                       params)
        assert_bit_identical(got_flat, got_hier)

    def test_hier_knob_is_noop_on_single_device(self):
        data, params = self._data(n=800), self._params()
        got_plain = run(JaxBackend(rng_seed=20), data, params)
        with topology_env("hier", None):
            mesh = psh.make_mesh(1)
        assert psh.topology_of(mesh).mode == "flat"
        got_hier = run(JaxBackend(mesh=mesh, rng_seed=20), data, params)
        assert_bit_identical(got_plain, got_hier)


# ---------------------------------------------------------------------------
# Streamed elastic shrink under hier
# ---------------------------------------------------------------------------

def run_streamed(ds, params, seed=0, eps=5.0, delta=1e-6,
                 checkpoint=None, mesh=None):
    ds.invalidate_cache()
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=delta)
    engine = pdp.DPEngine(acc, JaxBackend(rng_seed=seed, mesh=mesh,
                                          checkpoint=checkpoint))
    res = engine.aggregate(ds, params, pdp.DataExtractors())
    acc.compute_budgets()
    got = dict(res)
    assert res.timings.get("stream_batches", 0) > 1
    return got, res.timings


class TestElasticShrinkUnderHier:

    def test_8_to_4_loss_preserves_hier_and_bit_parity(self, tmp_path,
                                                       monkeypatch):
        """Device loss mid-stream on a hier(2,4) mesh: the survivors
        regroup within their host to hier(2,2), the resume adopts the
        checkpoint, and the release is bit-identical to a clean FLAT
        run at the surviving shape — elastic shrink and the topology
        knob compose without touching the released values."""
        require_8()
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "500")
        rng = np.random.default_rng(8)
        n, users, parts = 14_000, 2_000, 12
        ds = pdp.ArrayDataset(
            privacy_ids=rng.integers(0, users, n),
            partition_keys=rng.integers(0, parts, n),
            values=rng.uniform(0.0, 10.0, n))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=parts,
            max_contributions_per_partition=50,
            min_value=0.0, max_value=10.0)
        baseline, _ = run_streamed(ds, params, seed=21,
                                   mesh=psh.make_mesh(4))

        obs.reset()
        store = CheckpointStore(str(tmp_path / "topo.ckpt"))
        with topology_env("hier", 2):
            mesh = psh.make_mesh(8)
        assert psh.topology_of(mesh).hierarchical
        with injected_faults(FaultPlan(lose_device_chunks=(2,))):
            survived, timings = run_streamed(ds, params, seed=21,
                                             mesh=mesh,
                                             checkpoint=store)
        assert timings["stream_mesh_reshards"] == 1
        hist = timings["stream_reshard_history"]
        assert hist[0]["old_devices"] == 8
        assert hist[0]["new_devices"] == 4
        snap = obs.ledger().snapshot()
        reformed = [e for e in snap["events"]
                    if e["name"] == "mesh.reformed"]
        assert reformed and reformed[0]["topology"] == "hier"
        assert reformed[0]["hosts"] == 2
        assert reformed[0]["per_host"] == 2
        assert snap["counters"]["checkpoint.elastic_adoptions"] >= 1
        assert_bit_identical(baseline, survived)
        assert not store.exists()


# ---------------------------------------------------------------------------
# Sharded sketch accumulation parity
# ---------------------------------------------------------------------------

class TestShardedSketchParity:

    def _buckets(self, depth=3, n=5000, width=512, seed=9):
        rng = np.random.default_rng(seed)
        return rng.integers(0, width, (depth, n)).astype(np.int32)

    @pytest.mark.parametrize("backend", ["matmul", "scatter"])
    def test_chunk_program_matches_single_device(self, backend):
        require_8()
        from pipelinedp_tpu.sketch import device as sk_dev
        width = 512
        raw = self._buckets(width=width)
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        padded = sk_dev.pad_chunk(raw, n_shards=8)
        single = np.asarray(sk_dev._sketch_chunk(padded, width, backend))
        for mesh in (flat_mesh, hier_mesh):
            got = np.asarray(sk_dev.sharded_sketch_chunk_program(
                width, backend, mesh, padded))
            np.testing.assert_array_equal(got, single)

    def test_accumulate_stream_matches_single_device(self):
        require_8()
        from pipelinedp_tpu.sketch import engine as sk_engine
        width = 512
        raw = self._buckets(n=7000, width=width, seed=10)
        tr = obs.tracer()
        want, chunks = sk_engine._accumulate_stream(
            raw, width, "scatter", 1500, tr, mesh=None)
        assert chunks > 1
        flat_mesh, hier_mesh = _meshes_flat_and_hier()
        for mesh in (flat_mesh, hier_mesh):
            got, got_chunks = sk_engine._accumulate_stream(
                raw, width, "scatter", 1500, tr, mesh=mesh)
            assert got_chunks == chunks
            np.testing.assert_array_equal(got, want)

    def test_pad_chunk_aligns_to_shard_blocks(self):
        from pipelinedp_tpu.sketch import device as sk_dev
        raw = self._buckets(n=1000)
        out = sk_dev.pad_chunk(raw, n_shards=8)
        unit = sk_dev.ROW_BLOCK * 8
        assert out.shape[1] % unit == 0
        np.testing.assert_array_equal(out[:, :1000], raw)
        assert (out[:, 1000:] == -1).all()


# ---------------------------------------------------------------------------
# Sweep chunk sizing
# ---------------------------------------------------------------------------

class TestPlannedSweepChunk:

    def test_lane_align(self):
        from pipelinedp_tpu.analysis import jax_sweep as js
        assert js._lane_align(4096) == 4096
        assert js._lane_align(133) == 128
        assert js._lane_align(100) == 64
        assert js._lane_align(1) == 1
        assert js._lane_align(0) == 1
