"""The multi-tile pass-B histogram binner, as a Pallas TPU kernel.

One batch's rows bin into EVERY packed ``[T, Pb, Qc, span]`` pass-B
tile histogram in a single ``pallas_call`` — the Pallas twin of
``jax_engine._subtree_counts_multi`` (one masked XLA scatter per
(tile, quantile), T*Qc row passes in the generic lowering).

Scatter-free formulation: for tile ``t`` and quantile-group column
``q``, the count of bin ``(p, s)`` is::

    #rows{ qpk - p_offsets[t] == p  AND
           leaf - sub_starts[t, p, q] == s  AND kept }

which is the matmul ``onehot_p^T @ onehot_s`` over a row block, where
``onehot_p[p, r] = (qpk[r] - p_offsets[t] == p) & kept[r]`` and
``onehot_s[s, r] = (leaf[r] - start_row[r] == s)``. The per-row walk
start gathers through the SAME one-hot (``sub_starts[t, :, q] @
onehot_p``, one nonzero per row), so the kernel needs no gather, no
scatter and no sort. The grid is (tiles, partition blocks, row
blocks): each tile's partitions go in blocks whose [Qc, Pk, span]
output stays VMEM-resident while every row block streams past.

Bit-identity: every one-hot product is 0/1 and every per-block partial
sum is at most the row-block width (<= 512 < 2^24), so one bf16 MXU
pass with f32 accumulation is exact integer arithmetic; the walk
starts (below 2^16) gather as their two bytes, each exact in bf16. The
int32 accumulator equals the XLA scatter path bit for bit — asserted
four ways in ``tests/test_pass_b.py`` and at the kernel level in
``tests/test_kernels.py``. Every membership test compares int32 ids
against an int32 iota, so it is exact for EVERY int32 partition id.

Rows out of a partition block, rows outside [0, span) of a walk start
and padding rows all match no one-hot column: masking is free and
identical to the XLA path's ``ok`` predicate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pipelinedp_tpu.obs.costs import instrumented_jit

#: Scoped-VMEM budget handed to Mosaic for every kernel here. The
#: dispatch envelopes (``dispatch.py``) size the resident output and
#: the one-hot planes against it.
VMEM_LIMIT_BYTES = 64 << 20


def compiler_params(n_grid_axes: int):
    """Mosaic params for a grid whose LAST axis walks the rows into a
    revisited output block ('arbitrary': never split across cores);
    the outer axes (tiles, partition blocks, D tiles) are independent
    ('parallel'). Interpret mode ignores them."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_grid_axes - 1) +
        ("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def row_ids(ids, row_block: int, index_map):
    """A per-row int32 column laid out for the row grid: zero-padded
    to whole blocks and shaped ``[n_blocks, 1, row_block]``, with the
    BlockSpec that hands the kernel one ``[1, row_block]`` row per
    grid step. The block's last two dims equal the array's, which is
    what Mosaic's (8, 128) tiling rule accepts for a one-row block."""
    from jax.experimental import pallas as pl
    n = ids.shape[0]
    n_pad = -(-n // row_block) * row_block
    ids3 = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n)).reshape(
        -1, 1, row_block)
    return ids3, pl.BlockSpec((None, 1, row_block), index_map)


def _hist_kernel_body(Qc: int):
    """The kernel body for a static quantile-group width ``Qc`` (a
    python loop, bounded by the dispatch envelope)."""

    def body(qpk_ref, leaf_ref, kept_ref, starts_ref, poff_ref,
             out_ref):
        from jax.experimental import pallas as pl
        _, _, Pk, span = out_ref.shape
        R = qpk_ref.shape[-1]
        bf16 = jnp.bfloat16

        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # Integer compares throughout: exact for every int32
        # partition id and offset.
        local = (qpk_ref[...] - poff_ref[pl.program_id(0)] -
                 pl.program_id(1) * Pk)               # [1, R]
        iota_p = jax.lax.broadcasted_iota(jnp.int32, (Pk, R), 0)
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (span, R), 0)
        oh_p = jnp.where((local == iota_p) & (kept_ref[...] != 0),
                         1.0, 0.0).astype(bf16)       # [Pk, R]
        leaf = leaf_ref[...]                          # [1, R]

        def gather(byte):
            # [Qc, Pk] bytes of the walk starts @ the one-hot: one
            # nonzero per row, a value below 256 — exact in bf16.
            return jax.lax.dot_general(
                byte.astype(jnp.float32).astype(bf16), oh_p,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32)

        starts = starts_ref[0]                        # [Qc, Pk] int32
        start_rows = gather(starts >> 8) * 256 + gather(starts & 255)
        for q in range(Qc):
            oh_s = jnp.where(leaf - start_rows[q:q + 1, :] == iota_s,
                             1.0, 0.0).astype(bf16)   # [span, R]
            part = jax.lax.dot_general(
                oh_p, oh_s,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [Pk, span]
            out_ref[0, q] += part.astype(jnp.int32)

    return body


def hist_bin_multi(qpk, leaf, kept, sub_starts, p_offsets, Pb: int,
                   span: int, row_block: int, p_block: int,
                   interpret: bool):
    """Pallas multi-tile subtree-leaf counts: same contract as
    ``jax_engine._subtree_counts_multi`` — ``sub_starts`` [T, Pb, Qc],
    ``p_offsets`` [T], output [T, Pb, Qc, span] int32, bit-identical
    to the per-tile XLA scatters. ``row_block`` and ``p_block`` come
    from ``dispatch.hist_envelope`` (callers dispatch through
    ``select_backend``; this function assumes in-envelope shapes).

    Inside the kernel the walk starts and the histogram are laid out
    quantile-major (``[T, Qc, Pb]`` and ``[T, Qc, Pb, span]``) so each
    quantile reads and writes whole (sublane, lane) tiles; the output
    is transposed back outside."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, _, Qc = sub_starts.shape
    p_pad = -(-Pb // p_block) * p_block
    index_map = lambda t, j, i: (i, 0, 0)  # noqa: E731
    # Padding rows carry kept=0 and match no one-hot column.
    qpk3, spec = row_ids(qpk, row_block, index_map)
    leaf3, _ = row_ids(leaf, row_block, index_map)
    kept3, _ = row_ids(kept, row_block, index_map)
    starts = jnp.pad(
        jnp.transpose(sub_starts.astype(jnp.int32), (0, 2, 1)),
        ((0, 0), (0, 0), (0, p_pad - Pb)))
    out = pl.pallas_call(
        _hist_kernel_body(Qc),
        grid=(T, p_pad // p_block, qpk3.shape[0]),
        in_specs=[
            spec, spec, spec,
            pl.BlockSpec((1, Qc, p_block), lambda t, j, i: (t, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, Qc, p_block, span),
                               lambda t, j, i: (t, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((T, Qc, p_pad, span), jnp.int32),
        interpret=interpret,
        compiler_params=compiler_params(3),
    )(qpk3, leaf3, kept3, starts, p_offsets.astype(jnp.int32))
    return jnp.transpose(out[:, :, :Pb], (0, 2, 1, 3))


#: Standalone instrumented entry (phase ``pass_b``): direct host
#: callers — the bench's backend-compare record, kernel microbenches —
#: compile through the device-cost observatory, so the run report's
#: ``device_costs`` section carries the kernel's own roofline verdict.
#: (Inside the streamed pass-B programs the kernel inlines into the
#: already-instrumented ``_pct_multi_sub_kernel`` trace, where the
#: ``kernel_backend`` static argument keys the before/after entries.)
hist_bin_multi_program = instrumented_jit(
    phase="pass_b", static_argnames=("Pb", "span", "row_block",
                                     "p_block", "interpret"))(
                                         hist_bin_multi)
