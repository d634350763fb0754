"""Worker process for the elastic reshard-resume parity test (ISSUE 16).

Two ``jax.distributed`` gloo processes form an 8-device global mesh and
stream the same aggregation. Process 1 (the NON-coordinator, so the
coordinator service survives) carries an injected ``fail_chunks`` fault
in its env and dies mid-stream. Process 0's mesh supervisor (armed via
``PIPELINEDP_TPU_MESH_DIR``) detects the death at its next collective
dispatch — BEFORE enqueueing the collective that would wedge on the
dead peer — raises ``MeshParticipantLost``, and the elastic wrapper in
``streaming.py`` re-forms the mesh over the survivor's 4 local devices,
resumes from the checkpoint, and finishes. The survivor then proves the
recovery:

* released values BIT-IDENTICAL to a clean run at the surviving shape;
* ``stream_mesh_reshards == 1`` with the 8 -> 4 ``participant_lost``
  record in the timings' reshard history;
* the ``mesh.reshard`` event on the run ledger;
* the resume started from a checkpoint, not from scratch.

Both processes exit via ``os._exit(0)`` after printing their marker —
the distributed atexit barrier would otherwise hang on the dead peer.

Not a pytest file — invoked directly with (process_id, n_processes,
rendezvous_file) argv; see ``tests/test_multihost.py``.
"""

import os
import sys

from multihost_worker import rendezvous_port


def main() -> None:
    proc_id = int(sys.argv[1])
    n_proc = int(sys.argv[2])
    rendezvous = sys.argv[3]

    # Self-deadline: an orphaned worker spinning in a gloo collective
    # must never outlive the suite (same discipline as
    # multihost_worker.py).
    import threading
    watchdog = threading.Timer(480.0, lambda: os._exit(3))
    watchdog.daemon = True
    watchdog.start()

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Synchronous dispatch: see multihost_worker.py — keeps the two
    # processes' gloo collectives paired in program order.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    port = rendezvous_port(proc_id, rendezvous)
    from pipelinedp_tpu.resilience import (CheckpointStore, RetryPolicy,
                                           resilient_distributed_initialize)
    resilient_distributed_initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=n_proc, process_id=proc_id,
        policy=RetryPolicy(max_attempts=2, base_delay_s=1.0,
                           multiplier=2.0, max_delay_s=10.0,
                           jitter=0.25, seed=proc_id),
        # The coordination service's default reaction to a peer that
        # stops heartbeating is to FATALLY terminate every surviving
        # client after ~100s — the exact recovery this test exists to
        # prove. Stretch the tolerance past the harness deadline so
        # OUR supervisor, not jax's, owns death detection here.
        heartbeat_timeout_seconds=1000)
    assert len(jax.devices()) == 4 * n_proc, jax.devices()
    assert len(jax.local_devices()) == 4

    import numpy as np

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import obs
    from pipelinedp_tpu.backends import JaxBackend
    from pipelinedp_tpu.parallel import make_mesh
    from pipelinedp_tpu.parallel import sharded
    from pipelinedp_tpu.resilience import faults

    mesh = make_mesh()  # all 8 global devices
    assert mesh.devices.size == 4 * n_proc
    assert os.environ.get("PIPELINEDP_TPU_MESH_DIR"), (
        "the parent must arm the mesh supervisor")

    rng = np.random.default_rng(0)  # identical data on every process
    n = 20_000
    pid = rng.integers(0, 2_000, n)
    pk = rng.integers(0, 40, n)
    vals = rng.uniform(0.0, 10.0, n)
    ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk,
                          values=vals)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=50,
        max_contributions_per_partition=50,
        min_value=0.0, max_value=10.0)
    public = list(range(40))

    def run(backend):
        ds.invalidate_cache()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e8,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, backend)
        res = engine.aggregate(ds, params, pdp.DataExtractors(),
                               public_partitions=public)
        acc.compute_budgets()
        return dict(res), res.timings

    if proc_id != 0:
        # The victim: its own env carries fail_chunks=2, so its stream
        # dies at chunk 2 — from the survivor's side, indistinguishable
        # from this host dropping out. The quiesce path inside
        # streaming drains the in-flight collective first, so the
        # SURVIVOR's matching dispatch completes instead of wedging.
        assert faults.active() is not None, (
            "victim worker expected an injected fault plan")
        try:
            run(JaxBackend(mesh=mesh, rng_seed=11))
        except faults.FaultInjected:
            print(f"proc {proc_id}: dying (injected fault mid-stream)",
                  flush=True)
            os._exit(0)
        print(f"proc {proc_id}: fault never fired", flush=True)
        os._exit(4)

    # The survivor (and coordinator): checkpointed elastic run. The
    # wrapper re-forms onto the 4 local devices when the supervisor
    # reports the peer dead, resumes from the checkpoint, completes.
    assert faults.active() is None, (
        "survivor must not inherit the victim's fault plan")
    store = CheckpointStore(os.path.join(
        os.environ["PDP_TEST_CKPT_DIR"], "elastic.ckpt"))
    survived, timings = run(JaxBackend(mesh=mesh, rng_seed=11,
                                       checkpoint=store))
    assert timings.get("stream_batches", 0) >= 3, timings
    assert timings.get("stream_mesh_reshards") == 1, timings
    (reshard,) = timings["stream_reshard_history"]
    assert reshard["old_devices"] == 8, reshard
    assert reshard["new_devices"] == 4, reshard
    assert reshard["reason"] == "participant_lost", reshard
    assert timings.get("stream_resumed_from", 0) >= 1, (
        "recovery restarted from scratch instead of the checkpoint")
    events = [e for e in obs.ledger().snapshot()["events"]
              if e["name"] == "mesh.reshard"]
    assert len(events) == 1, events
    assert events[0]["old_devices"] == 8, events
    assert events[0]["new_devices"] == 4, events

    # Bit-parity oracle: a CLEAN run at the surviving shape — the same
    # local mesh the wrapper re-formed onto.
    survivor_mesh = sharded.reform_mesh(mesh)
    assert survivor_mesh is not None
    assert survivor_mesh.devices.size == 4
    baseline, base_timings = run(JaxBackend(mesh=survivor_mesh,
                                            rng_seed=11))
    assert base_timings.get("stream_batches", 0) >= 3, base_timings
    assert set(survived) == set(baseline), (
        sorted(set(survived) ^ set(baseline)))
    for k in survived:
        for f in survived[k]._fields:
            va = np.asarray(getattr(survived[k], f))
            vb = np.asarray(getattr(baseline[k], f))
            assert np.array_equal(va, vb), (k, f, va, vb)

    print(f"proc {proc_id}: OK (reshard "
          f"{reshard['old_devices']} -> {reshard['new_devices']}, "
          f"resumed from batch {timings['stream_resumed_from']}, "
          f"{len(survived)} partitions bit-identical)", flush=True)
    # Skip the distributed atexit barrier — the peer is dead.
    os._exit(0)


if __name__ == "__main__":
    main()
