"""Kernel-backend dispatch: availability probe, envelopes, fallback.

The ``kernel_backend`` knob selects between ``xla`` (the generic
sort/scatter lowering — today's behavior, the default) and ``pallas``
(the hand-tiled kernels in this package). Because the Pallas kernels
keep their whole output resident in VMEM, they only run inside a
shape ENVELOPE; a requested-but-infeasible dispatch degrades to XLA
with a ``kernel.fallback`` obs event so the run report shows the
actual path taken. All decisions here happen at jit-TRACE time — the
shapes are static — so a warm program never re-pays them.

This module holds no jax-at-import dependency beyond what the ops
package already has, and no pallas import at all: the pallas modules
import lazily at first dispatch, so a host without Pallas support
still imports the library and falls back cleanly.
"""

from __future__ import annotations

from typing import Optional

KNOWN_BACKENDS = ("xla", "pallas")

#: The ``kernel_backend`` knob's module seam (plan/knobs.py registers
#: it): tests and bench force a backend via ``plan.seam_override``;
#: reads flow through the knob registry, never this name directly.
_KERNEL_BACKEND = "xla"

#: VMEM byte budget for a kernel's resident output block. The kernels
#: ask Mosaic for ``hist.VMEM_LIMIT_BYTES`` (64 MiB of v5e's 128 MiB)
#: of scoped VMEM: the output block is double-buffered, and the one-hot
#: operands, their iota and compare planes and the matmul result each
#: take about as much again, so the working set is ~5x the largest of
#: these caps. Mosaic's default 16 MiB scope refuses the wide-D kernel
#: at an 8192-partition block; ``tests/test_tpu_compile.py`` compiles
#: every envelope corner for v5e.
_OUT_BYTES_CAP = 4 << 20

#: One-hot operand byte budget per row block (the [Pk+span, R] or
#: [Pk, R] compare planes) — bounds the row-block width choice below.
_ONEHOT_BYTES_CAP = 4 << 20

#: Unrolled quantile-group loop bound for the histogram binner: each
#: quantile emits one MXU contraction per grid step.
_HIST_UNROLL_CAP = 32

#: Partition-block width of the segment sums: the grid's outer axis
#: walks blocks of at most this many partitions, each block's [Pk, C]
#: accumulator and [Pk, R] one-hot VMEM-resident while the inner axis
#: streams every row block. A partition axis past one block re-reads
#: the rows once per block (the one-hot work is rows x partitions
#: either way).
_SEGSUM_P_BLOCK = 8192
_SEGSUM_MAX_COLS = 32

#: Row-block candidates, widest first. Exactness bound: every f32
#: partial sum in the kernels is at most R * (2^12 - 1) < 2^24 at
#: R <= 512, so integer accumulation through the f32 MXU stays exact.
_ROW_BLOCKS = (512, 256, 128)

#: Wide-D segment sum: D-tile candidates, widest first. The [Pk, Dt]
#: accumulator slab must fit ``_OUT_BYTES_CAP`` (8192 partitions x
#: 128 lanes x 4 B is exactly 4 MB, so even a full partition block
#: keeps a tile). Lane values stay below 2^12 (the vector fixed-point lane
#: plan), so the scalar kernel's exactness bound carries over.
_D_BLOCKS = (512, 256, 128)

#: The ``segsum_wide_d_block`` knob's module seam (plan/knobs.py
#: registers it): 0 means "envelope picks the widest tile"; a nonzero
#: in-envelope value pins the D tile.
_WIDE_D_BLOCK = 0

#: Test seam: force ``pallas_available()`` to answer False, exercising
#: the unavailability fallback without uninstalling anything.
_FORCE_UNAVAILABLE = False

_available: Optional[bool] = None


def pallas_available() -> bool:
    """Whether this jax build exposes the Pallas API (cached probe).
    A host without it — older jax, stripped builds — dispatches every
    request to XLA with a ``kernel.fallback`` event."""
    global _available
    if _FORCE_UNAVAILABLE:
        return False
    if _available is None:
        try:
            from jax.experimental import pallas  # noqa: F401
            _available = True
        except Exception:
            _available = False
    return _available


def use_interpret() -> bool:
    """Pallas interpret mode everywhere but a TPU: the kernels then
    lower to plain jax ops (bit-identical arithmetic), so tier-1 CI on
    the CPU asserts the same parity the TPU path claims. The compiled
    (Mosaic) path is covered by ``tests/test_tpu_compile.py`` for a
    described v5e and by ``chip_smoke.py``'s Pallas phase on the chip."""
    import jax
    return jax.default_backend() != "tpu"


def _row_block(per_row_bytes: int) -> Optional[int]:
    """Widest row block whose one-hot operands fit the budget, or None
    when even the narrowest block overflows (out of envelope)."""
    for r in _ROW_BLOCKS:
        if r * per_row_bytes <= _ONEHOT_BYTES_CAP:
            return r
    return None


def hist_envelope(T: int, Pb: int, Qc: int, span: int):
    """``(row_block, p_block)`` for an in-envelope ``[T, Pb, Qc, span]``
    histogram request, or None when out of envelope (more quantiles
    than the unroll cap, or one-hots too wide). Tiles run one after
    another on the grid, and a tile's partitions in blocks of
    ``p_block`` (the whole tile when it fits, else a multiple of 128,
    the walk-start block's lane width) whose [Qc, p_block, span]
    output stays VMEM-resident."""
    if Qc > _HIST_UNROLL_CAP:
        return None
    per_p = Qc * span * 4
    if Pb * per_p <= _OUT_BYTES_CAP:
        pb = Pb
    else:
        pb = (_OUT_BYTES_CAP // per_p) // 128 * 128
        if pb < 128:
            return None
    rb = _row_block((pb + span) * 4)
    return None if rb is None else (rb, pb)


def _segsum_p_block(P: int) -> int:
    return min(P, _SEGSUM_P_BLOCK)


def segsum_envelope(P: int, C: int):
    """``(row_block, p_block)`` for an in-envelope ``[P, C]`` lane
    segment-sum request, or None when out of envelope."""
    if C > _SEGSUM_MAX_COLS or C < 1:
        return None
    pb = _segsum_p_block(P)
    rb = _row_block(pb * 4)
    return None if rb is None else (rb, pb)


def segsum_wide_envelope(P: int, D: int):
    """``(row_block, d_block, p_block)`` for an in-envelope wide-D
    ``[P, D]`` vector segment-sum request, or None when out of
    envelope. Unlike :func:`segsum_envelope` there is no column cap —
    D is tiled — but the [Pk, Dt] slab and the [Pk, R] one-hot must
    both fit VMEM."""
    if D < 1:
        return None
    pb = _segsum_p_block(P)
    rb = _row_block(pb * 4)
    if rb is None:
        return None
    for db in _D_BLOCKS:
        if pb * db * 4 <= _OUT_BYTES_CAP:
            return rb, db, pb
    return None


def select_backend(requested: str, site: str,
                   row_block: Optional[int], **shape) -> str:
    """The one fallback decision: ``pallas`` only when requested,
    available AND in-envelope; anything else resolves to ``xla``. A
    degraded pallas request emits ``kernel.fallback`` (+ counter) so a
    changed path is visible in the run report, never silent. Runs at
    trace time — one event per compiled program, not per call."""
    if requested != "pallas":
        return "xla"
    from pipelinedp_tpu import obs
    if not pallas_available():
        obs.inc("kernel.fallbacks")
        obs.event("kernel.fallback", site=site,
                  reason="pallas_unavailable", **shape)
        return "xla"
    if row_block is None:
        obs.inc("kernel.fallbacks")
        obs.event("kernel.fallback", site=site,
                  reason="out_of_envelope", **shape)
        return "xla"
    obs.inc("kernel.pallas_dispatches")
    return "pallas"


def try_segment_sum_lanes(cols, pk, P: int, requested: str):
    """The ONE dispatch seam for the lane-packed segment sum: the
    Pallas result when ``requested`` resolves to an in-envelope pallas
    dispatch, else None (after the ``kernel.fallback`` event) — the
    caller then runs its XLA path. Keeps the envelope/fallback/
    interpret logic out of the call sites."""
    if requested != "pallas":
        return None
    C = int(cols.shape[1])
    env = segsum_envelope(P, C)
    if select_backend(requested, "segment_sum_lanes",
                      env[0] if env else None, P=int(P), C=C,
                      rows=int(pk.shape[0])) != "pallas":
        return None
    from pipelinedp_tpu.ops.kernels.segsum import segment_sum_lanes
    rb, pb = env
    return segment_sum_lanes(cols, pk, P, rb, pb, use_interpret())


def try_segment_sum_wide(cols, pk, P: int, requested: str,
                         d_block: int = 0):
    """Dispatch seam for the wide-D vector segment sum — same contract
    as :func:`try_segment_sum_lanes`. ``d_block`` (the
    ``segsum_wide_d_block`` knob, 0 = auto) pins the D tile when it is
    itself in envelope; an out-of-envelope pin falls back to the
    envelope's own choice rather than to XLA (the knob is a dp-safe
    performance hint, not a correctness gate)."""
    if requested != "pallas":
        return None
    D = int(cols.shape[1])
    env = segsum_wide_envelope(P, D)
    rb = env[0] if env else None
    if select_backend(requested, "segment_sum_wide", rb, P=int(P),
                      D=D, rows=int(pk.shape[0])) != "pallas":
        return None
    _, db, pb = env
    if d_block in _D_BLOCKS and pb * d_block * 4 <= _OUT_BYTES_CAP:
        db = d_block
    from pipelinedp_tpu.ops.kernels.segsum import segment_sum_wide
    return segment_sum_wide(cols, pk, P, rb, db, pb, use_interpret())


def try_hist_bin_multi(qpk, leaf, kept, sub_starts, p_offsets, Pb: int,
                       span: int, requested: str):
    """Dispatch seam for the multi-tile histogram binner — same
    contract as :func:`try_segment_sum_lanes`."""
    if requested != "pallas":
        return None
    T, _, Qc = sub_starts.shape
    env = hist_envelope(int(T), int(Pb), int(Qc), int(span))
    if select_backend(requested, "hist_bin_multi",
                      env[0] if env else None, T=int(T), Pb=int(Pb),
                      Qc=int(Qc), span=int(span)) != "pallas":
        return None
    from pipelinedp_tpu.ops.kernels.hist import hist_bin_multi
    rb, pb = env
    return hist_bin_multi(qpk, leaf, kept, sub_starts, p_offsets, Pb,
                          span, rb, pb, use_interpret())
