"""Hand-tiled Pallas TPU kernels for the two proven hot spots.

PAPER.md §5.8 frames the TPU mapping as "run the per-key reductions as
fast as the hardware allows"; the device cost observatory (PR 8)
classifies exactly those phases — ``pass_a``'s multi-feature
``segment_sum`` and ``pass_b``'s histogram scatters — as
bandwidth-bound on every measured record. Both currently lower through
XLA's generic sort/scatter machinery. This package holds the
hand-tiled alternatives:

* :func:`hist_bin_multi` — the multi-tile pass-B histogram binner: one
  VMEM-resident pass over a batch's rows bins them into EVERY packed
  ``[T, Pb, Qc, span]`` tile histogram (the Pallas twin of
  ``jax_engine._subtree_counts_multi``). Scatter-free: bin membership
  becomes one-hot operands and the per-tile histogram is an MXU
  matmul ``onehot_p^T @ onehot_s`` — 0/1 products whose per-block
  partial sums stay below 2^24, so the f32 MXU accumulation is EXACT
  and the int32 result is bit-identical to the XLA scatter path.
* :func:`segment_sum_lanes` — the fused lane-packed segment sum: the
  ``[N, C]`` stack of 24-bit fixed-point integer lanes reduces per
  partition as ``onehot_pk^T @ cols`` with one partition block's
  accumulator resident in VMEM across row blocks. Lane values ride as
  bytes and row blocks hold at most 512 rows, so every f32 partial sum
  is below 2^24 — exact — and the int32 totals match
  ``jax.ops.segment_sum`` bit for bit.
* :func:`segment_sum_wide` — the wide-D twin for VECTOR_SUM's
  fixed-point coordinate lanes: the same contraction with D tiled at
  an envelope-governed ``d_block`` (the ``segsum_wide_d_block`` knob
  can pin it), so only a [Pk, Dt] accumulator slab is VMEM-resident.
  Same exactness bound, same bit-identity (PARITY row 39).

Dispatch is the ``kernel_backend`` knob (``plan/knobs.py``: env >
seam > default, default ``xla`` — cold start is
byte-identical to the XLA path). The knob is dp-safe because both
kernels produce bit-identical integers (PARITY row 33); shapes outside
the tiled envelope, or a host without Pallas, fall back to XLA with a
``kernel.fallback`` obs event — never a silent path change. On
non-TPU backends the kernels run in Pallas interpret mode, so tier-1
asserts the parity everywhere the tests run.

``pallas`` imports are confined to this package (``make nopallas`` +
the AST twin in ``tests/test_kernels.py``).
"""

# NOTE: the ``_KERNEL_BACKEND`` knob seam deliberately is NOT
# re-exported — the knob registry reads/writes it as an attribute of
# the ``dispatch`` module, and a by-value copy here would go stale the
# moment ``plan.seam_override`` mutates the real one.
from pipelinedp_tpu.ops.kernels.dispatch import (  # noqa: F401
    KNOWN_BACKENDS, hist_envelope, pallas_available, segsum_envelope,
    segsum_wide_envelope, select_backend, try_hist_bin_multi,
    try_segment_sum_lanes, try_segment_sum_wide, use_interpret)
from pipelinedp_tpu.ops.kernels.hist import (  # noqa: F401
    hist_bin_multi, hist_bin_multi_program)
from pipelinedp_tpu.ops.kernels.segsum import (  # noqa: F401
    segment_sum_lanes, segment_sum_lanes_program, segment_sum_wide,
    segment_sum_wide_program)
