"""The fused lane-packed segment sum, as a Pallas TPU kernel.

``jax_engine._reduce_per_pk`` accumulates every scalar metric column
in 24-bit fixed-point integer lanes and reduces them per partition in
ONE multi-feature ``jax.ops.segment_sum`` over the ``[N, C]`` stack
(jax_engine.py's "one wide scatter"). XLA lowers that to a generic
sorted scatter; this kernel replaces it with an MXU contraction that
keeps the lanes in registers/VMEM across the whole reduction::

    out[p, c] = sum_r (pk[r] == p) * cols[r, c]
              = (onehot_pk^T @ cols)[p, c]

with a ``[Pk, C]`` accumulator block VMEM-resident across row blocks:
the grid's outer axis walks partition blocks of ``Pk`` (the whole axis
when it fits), the inner axis every row block.

Bit-identity: lane values are at most ``2^12 - 1`` (the widest lane
plan) and count/marker columns are 0/1. Each value contracts as its
two bytes, each exact in bf16, in two one-pass bf16 MXU contractions
with f32 accumulation (a third of the passes of an f32 contraction at
``Precision.HIGHEST``). With row blocks of at most 512 rows every f32
partial sum is below ``512 * 255 < 2^24`` — exact integer arithmetic
— and the int32 accumulation across blocks is associative integer
addition. Exact for every value in [-2^16, 2^16). The result equals
``jax.ops.segment_sum`` bit for bit (asserted in
``tests/test_kernels.py``, including at the lane-plan boundary
widths).

Invalid rows already arrive masked (pk 0, all columns 0 — the XLA
path's convention), so they add exact zeros; padding rows appended
here do the same.

:func:`segment_sum_wide` is the one kernel, also used for VECTOR_SUM's
fixed-point coordinate lanes: the D axis is tiled at an
envelope-governed ``d_block`` so a [Pk, Dt] accumulator slab (not the
whole [P, D] block) is VMEM-resident; :func:`segment_sum_lanes` runs
it with the C lane columns as one tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pipelinedp_tpu.obs.costs import instrumented_jit
from pipelinedp_tpu.ops.kernels.hist import compiler_params, row_ids


def _segsum_kernel_body(pk_ref, cols_ref, out_ref):
    """One grid step of ``out += onehot(pk)^T @ cols``: the grid is
    (partition blocks, D tiles, row blocks), so each [Pk, Dt]
    accumulator slab is zeroed at its first row block and sees every
    row block before the grid moves on."""
    from jax.experimental import pallas as pl
    Pk = out_ref.shape[0]
    R = pk_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Integer iota against integer ids: exact for every int32.
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (Pk, R), 0)
    local = pk_ref[...] - pl.program_id(0) * Pk       # [1, R]
    oh = jnp.where(local == iota_p, 1.0, 0.0).astype(jnp.bfloat16)
    cols = cols_ref[...]                              # [R, Dt] int32

    def contract(byte):
        # [Pk, R] 0/1 @ [R, Dt] bytes: one bf16 pass, exact.
        return jax.lax.dot_general(
            oh, byte.astype(jnp.float32).astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)

    out_ref[...] += contract(cols >> 8) * 256 + contract(cols & 255)


def segment_sum_wide(cols, pk, P: int, row_block: int, d_block: int,
                     p_block: int, interpret: bool):
    """Tiled segment sum: ``cols`` [N, D] int32 (fixed-point lanes),
    ``pk`` [N] int32 in [0, P) — returns [P, D] int32 bit-identical to
    ``jax.ops.segment_sum(cols, pk, num_segments=P)``.

    The grid is (partition blocks of ``p_block``, D tiles of
    ``d_block``, row blocks of ``row_block``), row blocks innermost, so
    only a [Pk, Dt] accumulator slab is VMEM-resident at a time. The
    three widths come from ``dispatch.segsum_wide_envelope``."""
    from jax.experimental import pallas as pl
    n, D = cols.shape
    d_pad = -(-D // d_block) * d_block
    p_pad = -(-P // p_block) * p_block
    pk3, pk_spec = row_ids(pk, row_block, lambda p, j, i: (i, 0, 0))
    cols2 = jnp.pad(cols, ((0, pk3.size - n), (0, d_pad - D)))
    out = pl.pallas_call(
        _segsum_kernel_body,
        grid=(p_pad // p_block, d_pad // d_block, pk3.shape[0]),
        in_specs=[pk_spec,
                  pl.BlockSpec((row_block, d_block),
                               lambda p, j, i: (i, j))],
        out_specs=pl.BlockSpec((p_block, d_block),
                               lambda p, j, i: (p, j)),
        out_shape=jax.ShapeDtypeStruct((p_pad, d_pad), jnp.int32),
        interpret=interpret,
        compiler_params=compiler_params(3),
    )(pk3, cols2)
    return out[:P, :D]


def segment_sum_lanes(cols, pk, P: int, row_block: int, p_block: int,
                      interpret: bool):
    """Pallas lane-packed segment sum: ``cols`` [N, C] int32, ``pk``
    [N] int32 in [0, P) — returns [P, C] int32 bit-identical to
    ``jax.ops.segment_sum(cols, pk, num_segments=P)``: the tiled
    kernel with all C columns as one tile. ``row_block`` and
    ``p_block`` come from ``dispatch.segsum_envelope``."""
    return segment_sum_wide(cols, pk, P, row_block, cols.shape[1],
                            p_block, interpret)


#: Standalone instrumented entry (phase ``engine``) — see
#: ``hist.hist_bin_multi_program`` for the seam rationale.
segment_sum_lanes_program = instrumented_jit(
    phase="engine", static_argnames=("P", "row_block", "p_block",
                                     "interpret"))(segment_sum_lanes)


#: Standalone instrumented entry for the wide-D kernel.
segment_sum_wide_program = instrumented_jit(
    phase="engine",
    static_argnames=("P", "row_block", "d_block", "p_block",
                     "interpret"))(segment_sum_wide)
