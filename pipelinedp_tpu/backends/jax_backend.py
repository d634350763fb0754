"""JaxBackend — the TPU-native execution plane.

For standard aggregations (COUNT / PRIVACY_ID_COUNT / SUM / MEAN /
VARIANCE / VECTOR_SUM) the engine bypasses the op-by-op graph entirely and
lowers to the fused XLA program in ``pipelinedp_tpu.jax_engine`` (one
device program for bounding + combine + selection + noise). Everything
else (percentiles, custom combiners, the analysis graphs, arbitrary user
``map``s) falls back to the host generator semantics inherited from
``LocalBackend`` — correctness everywhere, compiled speed on the hot
path.

Multi-chip execution goes through ``pipelinedp_tpu.parallel`` (shard rows
over a ``jax.sharding.Mesh``, per-shard segment reduction, ``psum`` for
the per-partition accumulator exchange); construct the backend with a
mesh to enable it.

Fault tolerance goes through ``pipelinedp_tpu.resilience``: pass
``health_policy`` to probe the accelerator (with bounded retry +
backoff) before the first kernel and degrade to CPU — flagged on
``backend.degraded``, never silently — when the runtime is wedged; pass
``checkpoint`` (a path or ``CheckpointStore``) to persist streamed
per-chunk state so a killed run resumes bit-identically without
re-drawing noise (requires ``rng_seed``).
"""

from __future__ import annotations

from typing import Optional

from pipelinedp_tpu.pipeline_backend import LocalBackend


class JaxBackend(LocalBackend):
    """Marker + host-fallback backend for the fused JAX plane.

    Attributes:
      mesh: optional ``jax.sharding.Mesh`` for multi-chip runs (rows are
        sharded by privacy id over the first mesh axis).
      rng_seed: optional fixed seed for reproducible runs (tests,
        checkpointed runs).
      checkpoint: optional checkpoint path or
        ``resilience.checkpoint.CheckpointStore`` — enables budget-safe
        resume of streamed aggregations.
      degraded: True when the device-health probe exhausted its retries
        and execution fell back to CPU. Results produced in this mode
        must be flagged by callers (bench emits ``"degraded": true``).
      health: the ``resilience.health.HealthReport`` of the probe, or
        None when no ``health_policy`` was requested.
      ingest_executor: overlapped streaming-ingest executor
        (``pipelinedp_tpu/ingest``): True/False force it on/off, None
        (default) follows ``PIPELINEDP_TPU_INGEST_EXECUTOR`` (on unless
        0). Both modes are bit-identical; off = the serial reference
        path.
      stream_cache: per-device HBM budget (bytes) for keeping streamed
        batches device-resident so percentile pass B re-reads them from
        HBM instead of re-shipping over the host link. None (default)
        follows ``PIPELINEDP_TPU_STREAM_CACHE`` (4 GiB); 0 disables.
        The cache is a PREFIX cache: on overflow the cached batch
        prefix stays resident and only the suffix re-ships each pass-B
        sweep (``pass_b_source: "hybrid"``). All three sources —
        device_cache / hybrid / reship — are bit-identical.

    Constructing the backend also wires JAX's persistent compilation
    cache when ``JAX_COMPILATION_CACHE_DIR`` or
    ``PIPELINEDP_TPU_COMPILE_CACHE`` names a directory, so cold
    processes skip XLA recompilation of the fused kernels.
    """

    supports_fused_aggregation = True

    def __init__(self, mesh=None, rng_seed: Optional[int] = None,
                 checkpoint=None, health_policy=None, clock=None,
                 probe_timeout_s: Optional[float] = None,
                 ingest_executor: Optional[bool] = None,
                 stream_cache: Optional[int] = None):
        import os

        from pipelinedp_tpu.ingest import maybe_enable_compile_cache
        from pipelinedp_tpu.resilience.health import DEGRADED_ENV

        maybe_enable_compile_cache()
        self.mesh = mesh
        self.rng_seed = rng_seed
        self.checkpoint = checkpoint
        self.ingest_executor = ingest_executor
        self.stream_cache = stream_cache
        # A prior degradation in this process pinned the platform to
        # CPU for EVERY later backend — the flag must say so even when
        # this construction ran no probe of its own.
        self.degraded = bool(os.environ.get(DEGRADED_ENV))
        self.health = None
        if health_policy is not None:
            from pipelinedp_tpu.resilience import health as _health
            policy = (None if health_policy is True else health_policy)
            self.health = _health.ensure_device_or_degrade(
                policy=policy, clock=clock, timeout_s=probe_timeout_s)
            self.degraded = self.health.degraded
            if self.degraded:
                # A wedged-device mesh is unusable; the CPU fallback
                # runs single-device. NEVER silent: ``degraded`` says so.
                self.mesh = None
        # This process now runs on its backend: later probes must not
        # start a child that needs the chip it holds.
        from pipelinedp_tpu.resilience.health import hold_backend
        hold_backend()
        from pipelinedp_tpu import obs
        # seed_fixed, never the seed itself: run reports are meant to
        # be shared, and noise draws are pure functions of the seed —
        # publishing it would let a report holder subtract the noise.
        obs.event("backend.created", degraded=self.degraded,
                  mesh_devices=(int(self.mesh.devices.size)
                                if self.mesh is not None else 0),
                  seed_fixed=rng_seed is not None,
                  checkpoint=bool(checkpoint))
