"""Device-resident counting sketch: one-hot-matmul binning, no scatter.

The sketch is a ``[depth, width]`` int32 count matrix over hashed
bucket ids. The obvious lowering — one masked scatter-add per depth
row — is exactly the gather/scatter traffic the pass-B binner
(``ops/kernels/hist.py``) was built to avoid, so the default backend
here reuses that kernel's idiom: factor each bucket id into radix
digits ``(hi, lo) = (b // 256, b % 256)`` and count bin ``(hi, lo)``
as the MXU contraction ``onehot_hi @ onehot_lo^T`` over a row block —
two one-hot factors, one matmul, the whole ``[W1, 256]`` product
reshaping to the width axis. Per row block every product is 0/1 and
every partial sum is bounded by the block width (512 < 2^24), so the
f32 MXU arithmetic is exact integer arithmetic and the matmul path is
**bit-identical** to the XLA scatter reference (``backend="xla"``) —
the on/off parity the ``sketch_backend`` knob stands on (PARITY row
36, asserted in ``tests/test_sketch.py``).

Padding rows carry bucket id ``-1``: ``-1 // 256 == -1`` matches no
``hi`` one-hot column (and the scatter path masks them explicitly),
so masking is free, exactly like the hist kernel's ``kept``
predicate.

Chunked accumulation is exact (integer sums associate), so the
streamed loop in ``sketch/engine.py`` can feed any batch sizing
through this kernel and land on the same counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu.obs.costs import instrumented_jit

#: Rows per one-hot block: keeps each [W1, R] x [R, 256] contraction's
#: partial sums exact in f32 (R <= 512 < 2^24) and the transient
#: one-hot factors small.
ROW_BLOCK = 512

_LO = 256  # the radix low digit — see sketch.params.WIDTH_MULTIPLE


def _counts_matmul(buckets: jnp.ndarray, width: int) -> jnp.ndarray:
    """[width] int32 bucket counts of one depth row via the radix
    one-hot contraction; ``buckets`` is [n] int32, padded with -1,
    ``n`` a multiple of ROW_BLOCK, ``width`` a multiple of 256."""
    w1 = width // _LO
    blocks = buckets.reshape(-1, ROW_BLOCK)
    iota_hi = jax.lax.broadcasted_iota(jnp.float32, (w1, ROW_BLOCK), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.float32, (_LO, ROW_BLOCK), 0)

    def body(acc, blk):
        # Integer divmod FIRST, one small-value f32 cast after — the
        # same exactness ordering as the hist kernel: hi < w1 < 2^24
        # casts exactly, and -1 (padding) matches no iota column.
        hi = (blk // _LO).astype(jnp.float32)
        lo = (blk % _LO).astype(jnp.float32)
        oh_hi = jnp.where(hi[None, :] == iota_hi, 1.0, 0.0)  # [w1, R]
        oh_lo = jnp.where(lo[None, :] == iota_lo, 1.0, 0.0)  # [256, R]
        part = jax.lax.dot_general(
            oh_hi, oh_lo, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [w1, 256], exact
        return acc + part.astype(jnp.int32).reshape(width), None

    acc0 = jnp.zeros(width, jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, blocks)
    return acc


def _counts_scatter(buckets: jnp.ndarray, width: int) -> jnp.ndarray:
    """The XLA scatter-add reference lowering (bit-parity twin)."""
    ok = buckets >= 0
    idx = jnp.where(ok, buckets, 0)
    ones = jnp.where(ok, 1, 0).astype(jnp.int32)
    return jnp.zeros(width, jnp.int32).at[idx].add(ones)


def _sketch_chunk(buckets, width: int, backend: str) -> jnp.ndarray:
    """[depth, width] int32 counts of one chunk; ``buckets`` is
    [depth, n] int32 with -1 padding, ``n`` a multiple of ROW_BLOCK.
    ``backend`` rides in static so a knob flip re-traces (jit caches by
    signature) and the cost observatory keys the two programs apart."""
    fn = _counts_matmul if backend == "matmul" else _counts_scatter
    return jnp.stack([fn(buckets[d], width)
                      for d in range(buckets.shape[0])])


#: Instrumented entry (phase ``sketch``): every sketch accumulation
#: compiles through the device-cost observatory, so the run report's
#: ``device_costs`` section carries the binner's roofline verdict.
sketch_chunk_program = instrumented_jit(
    phase="sketch", static_argnames=("width", "backend"))(_sketch_chunk)


def pad_chunk(buckets: np.ndarray, n_shards: int = 1) -> np.ndarray:
    """Pad a [depth, n] host chunk to a ROW_BLOCK multiple with -1
    rows (matched by neither backend) so every chunk shares a jit
    signature per (depth, padded-n) pair. With ``n_shards`` > 1 the
    padded length is a multiple of ``n_shards * ROW_BLOCK``, so every
    mesh shard's row slice is itself ROW_BLOCK-aligned."""
    depth, n = buckets.shape
    unit = ROW_BLOCK * max(1, int(n_shards))
    n_pad = max(-(-n // unit) * unit, unit)
    if n_pad == n:
        return buckets
    out = np.full((depth, n_pad), -1, dtype=np.int32)
    out[:, :n] = buckets
    return out


@instrumented_jit(phase="sketch", static_argnames=("width", "backend",
                                                   "mesh"))
def sharded_sketch_chunk_program(width: int, backend: str, mesh,
                                 buckets):
    """Mesh twin of ``sketch_chunk_program``: the chunk's row axis
    shards over the mesh, each device bins its slice through the SAME
    per-backend chunk body, and the local [depth, width] exact-integer
    sketches combine through ``parallel.sharded``'s one exchange
    policy (owner-block width scatter on a single-controller mesh —
    width is a 256 multiple, so any power-of-two mesh tiles it — a
    replicating psum on a multi-process mesh, two-stage under a
    hierarchical topology). Integer sums associate, so the sharded
    accumulation is BIT-IDENTICAL to the single-device scan for any
    mesh size — the phase-1 ceiling removal rides on the same parity
    argument as the pass-A kernels (PARITY row 43)."""
    from pipelinedp_tpu.parallel import sharded as psh

    axis = mesh.axis_names[0]
    topo = psh.topology_of(mesh)
    multiproc = mesh.is_multi_process

    def local_fn(buckets):
        local = _sketch_chunk(buckets, width, backend)
        return psh.combine_shards(local, axis, 1, multiproc, topo=topo)

    row_shard = psh.PSpec(None, axis)
    mapped = psh.shard_map(
        local_fn, mesh=mesh, in_specs=(row_shard,),
        out_specs=psh.PSpec() if multiproc else psh.PSpec(None, axis),
        check_vma=False)
    return mapped(buckets)


def accumulate_chunk(total: np.ndarray, device_counts) -> None:
    """Fold one chunk's device counts into the host int64 accumulator
    (in place). Exact: integer sums associate, so any chunking lands
    on the same totals."""
    total += np.asarray(device_counts).astype(np.int64)
