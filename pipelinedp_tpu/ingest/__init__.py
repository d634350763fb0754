"""Pipelined streaming-ingest executor for the fused TPU plane.

The streaming loop (``pipelinedp_tpu/streaming.py``) has three serial
host phases per batch — stage (numpy gather + byte-plane narrowing +
``device_put``), compute (the fused kernel), and fold (fetch the
[C+1, P] partials block, left-fold into float64 host accumulators).
Run serially they leave the device idle while the host works; PR 1's
``t_stage``/``t_fold`` counters showed staging + folding dominating the
wall clock on CPU runs. This package overlaps them:

* :class:`~pipelinedp_tpu.ingest.executor.BackgroundStager` runs the
  staging generator one batch ahead on a worker thread behind a bounded
  handoff queue — batch b+1 stages while the device computes batch b;
* :class:`~pipelinedp_tpu.ingest.executor.OrderedFoldWorker` drains a
  bounded FIFO of launched batches on a second thread, fetching and
  folding them **in submission order** so the left-fold float64
  operation sequence — and the ``resilience`` checkpoints written after
  each fold — stay bit-identical to the serial path;
* :class:`~pipelinedp_tpu.ingest.executor.StagingRing` gates the reuse
  of the rotating pair of staging buffers so ``device_put`` never
  aliases host memory a later batch mutates;
* :mod:`~pipelinedp_tpu.ingest.assign` groups rows into (batch, shard)
  cells with an O(n) counting-sort scatter instead of a comparison
  argsort;
* :mod:`~pipelinedp_tpu.ingest.compile_cache` wires JAX's persistent
  compilation cache (``JAX_COMPILATION_CACHE_DIR``, else the opt-in
  ``PIPELINEDP_TPU_COMPILE_CACHE``, else an entry point's fixed path)
  so a cold process skips XLA recompilation.

Every worker thread in the library lives here (or in ``resilience``)
and goes through the executor's cancellable lifecycle — a lint test
bans bare ``threading.Thread`` elsewhere — so fault-injected kills
(``resilience/faults.py``) can always drain to zero orphan threads.

The executor is ON by default and disabled with
``PIPELINEDP_TPU_INGEST_EXECUTOR=0``; both modes are bit-identical
(released values, kept-partition set, checkpoint bytes), proven by
``tests/test_ingest.py``.
"""

from pipelinedp_tpu.ingest.assign import group_rows_by_cell
from pipelinedp_tpu.ingest.compile_cache import (
    compile_stats, maybe_enable_compile_cache)
from pipelinedp_tpu.ingest.executor import (THREAD_PREFIX, BackgroundStager,
                                            IngestCancelled,
                                            OrderedFoldWorker, StagingRing,
                                            executor_enabled)

__all__ = [
    "BackgroundStager",
    "IngestCancelled",
    "OrderedFoldWorker",
    "StagingRing",
    "THREAD_PREFIX",
    "executor_enabled",
    "group_rows_by_cell",
    "compile_stats",
    "maybe_enable_compile_cache",
]
