"""Multi-batch streaming ingest for the fused plane: unbounded rows.

The fused kernel's per-pk accumulator columns are ADDITIVE — counts and
kept-segment markers are int32 sums, value columns are exact fixed-point
integer lane sums, vector coordinates are float sums. A dataset larger
than one device batch therefore streams through the SAME kernel
(``jax_engine._partials``) in privacy-id-partitioned chunks:

* every privacy unit's rows land in exactly ONE chunk (rows are grouped
  by ``fmix32(pid)`` — the same invariant ``parallel/sharded.py`` relies
  on for its row sharding), so per-chunk contribution bounding equals
  global bounding;
* each chunk's per-pk partials are fetched (a small [C, P] int32 block)
  and folded into host accumulators: counts in exact int64, fixed-point
  value lanes reassembled per chunk into EXACT integer step totals
  summed in float64 (the scale division happens ONCE at release, so
  the released bits are invariant to the batch boundaries — and to the
  mesh size, which the elastic reshard parity relies on), vector
  coordinates in float64 — BETTER conditioned than the single-batch
  float32 vector accumulation;
* partition selection then runs ONCE on device over the combined
  privacy-id counts (the same batched draw as the single-batch kernel),
  and the scalar DP release goes through the shared float64 host
  mechanisms (``jax_engine._host_release``).

This is the TPU plane's answer to the reference's unbounded Beam/Spark
dataflow ingestion (reference ``pipeline_dp/pipeline_backend.py:219-359``):
dataset size is bounded by HOST memory only — not by HBM and not by the
int32 lane capacity that caps a single batch at 2^27 rows.

Exactness: per-chunk folded value sums are integers in units of the
quantization step with magnitude <= chunk_rows * 2^23; their float64
accumulation stays exact while the GLOBAL total stays below 2^53 steps
(~2^30 rows at full-scale values); beyond that the only additional error
is float64 rounding at relative 2^-53 — far below the per-row
quantization already accepted by the single-batch kernel.

Percentile metrics stream in TWO passes: the
walk's adaptive descent needs the chosen subtrees' leaf counts, which
only exist after the top levels are walked — so pass A accumulates the
additive mid-level tree histogram alongside the scalar partials, the
top levels walk on it, pass B re-streams the same deterministic batches
for the subtree leaf histograms, and the bottom levels finish. When the
full [P, Q, span] subtree block exceeds ``je._SUBHIST_BYTE_CAP``, the
SWEEP PLANNER (:func:`plan_pass_b_sweeps`) tiles the (quantile x
partition) grid and packs as many tiles as fit under the cap into each
batch-stream traversal — the multi-tile kernels scatter one batch's
rows into every packed tile's histogram from a single bounding
recompute, so pass B pays ``ceil(tiles / tiles_per_sweep)`` sweeps
instead of one per tile. Batches re-read from the device-resident
PREFIX cache where it reaches (overflow keeps the cached prefix and
reships only the suffix — the hybrid source). With the
engine's seed the streamed walk sees the same exact histograms, the
same counter-keyed node noise (a pure function of (partition, node id)
— ``ops/counter_rng.py``) and the same selection/noise key splits as
the single-batch and owner-sharded-mesh walks, and the host release
draws over the kept set in the same order as the single-batch
release — released values and kept sets are bit-identical across the
three paths on the CPU test platform at any kept count. Asserted in
``tests/test_walk.py::TestThreeWayBitParity``; the descent arithmetic
lives in one shared ``_walk_level``, though separate XLA programs on
other backends could in principle still differ in the last f32 bit.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from pipelinedp_tpu import jax_engine as je
from pipelinedp_tpu import obs
from pipelinedp_tpu.obs.costs import instrumented_jit, scoped
from pipelinedp_tpu.ops.segment import fmix32

#: Rows per device batch (and the engine's streaming trigger: pipelines
#: with more rows than one chunk stream). Overridable for tests and for
#: hosts with small HBM. Registered as the ``stream_chunk_rows`` knob
#: (NOT dp-safe: batch membership decides a unit's bounding subsample).
_CHUNK_ENV = "PIPELINEDP_TPU_STREAM_CHUNK"


def stream_chunk_rows() -> int:
    from pipelinedp_tpu import plan as plan_mod
    return int(plan_mod.knob_value("stream_chunk_rows"))


#: HBM budget for keeping shipped batches device-resident so percentile
#: pass B re-reads them from HBM instead of re-shipping every byte over
#: the host link. 0 disables the cache. Registered as the
#: ``stream_cache_bytes`` knob (dp-safe: all three pass-B sources are
#: bit-identical, so the budget trades HBM for link traffic only).
_CACHE_ENV = "PIPELINEDP_TPU_STREAM_CACHE"


def stream_cache_bytes() -> int:
    from pipelinedp_tpu import plan as plan_mod
    return int(plan_mod.knob_value("stream_cache_bytes"))


#: Extreme-scale guard caps (int32 accumulator capacity), module-level
#: seams so boundary tests can inject a small cap and pin each guard's
#: exact cliff the way the lane plan's
#: 524,417-row boundary is pinned — without materializing 2^31-row
#: datasets. ``_SELECT_UNITS_CAP``: privacy units per partition at
#: selection time; ``_TREE_ROWS_CAP``: kept rows per partition in the
#: streamed percentile tree histograms. Registered knobs
#: (``select_units_cap`` / ``tree_rows_cap``); refusal thresholds, not
#: performance choices. Reads flow through ``plan.knobs``
#: (``make noknobs``); the names stay as seams.
_SELECT_UNITS_CAP = int(np.iinfo(np.int32).max)
_TREE_ROWS_CAP = int(np.iinfo(np.int32).max)

#: Pass-B quantiles-per-tile pin (the ``q_chunk`` knob's seam): 0 lets
#: :func:`plan_pass_b_sweeps` search the (q_chunk, p_blk) grid; a
#: positive value pins the quantile-group width (every tiling is
#: bit-identical — PARITY row 3 — so the pin is purely a perf choice).
_Q_CHUNK = 0


def chunk_target_rows(config, n_dev: int) -> int:
    """Per-batch GLOBAL row target: the per-device chunk knob times the
    mesh size, capped by the per-batch fixed-point lane capacity for
    value configs — the mesh psum combines int32 shard lanes, so lane
    capacity is a global per-batch bound that device count cannot
    raise. Without the cap an 8-device mesh at the default knob would
    target 2^29-row batches that ``_fx_plan`` must reject. Every
    config is also capped at int32 capacity: the per-partition count /
    privacy-id-count columns are int32 psums of per-shard segment
    sums, so one batch holding >= 2^31 rows of one partition (possible
    at the default knob on a >= 32-device mesh) would silently wrap
    them."""
    chunk = min(stream_chunk_rows() * n_dev, (1 << 31) - 1)
    if je._fixedpoint_layout(config) or je._vector_fx(config):
        chunk = min(chunk, je._fx_max_rows())
    return chunk


def should_stream(config, n_rows: int, mesh) -> bool:
    """The engine streams when one batch can't hold the pipeline.
    Streaming COMPOSES with a mesh: each chunk's rows are sharded by
    privacy id over the mesh exactly like the single-batch sharded
    kernel, and the per-pk partials combine in ONE collective per chunk
    — a ``psum_scatter`` to owner blocks (state/ICI O(P/n_dev)) on a
    single-controller mesh, a replicating ``psum`` (O(P) per device,
    every process fetches its own copy) on a multi-process mesh — then
    fold into the same host accumulators as the single-device stream.
    On a mesh the per-chunk row budget scales with the device count
    (up to the global lane capacity): every device still sees at most
    ``stream_chunk_rows()`` rows. EVERY fused configuration streams —
    percentiles included, in two passes (see the module docstring) —
    so size is the only criterion."""
    n_dev = mesh.devices.size if mesh is not None else 1
    return n_rows > chunk_target_rows(config, n_dev)


def _rank1_names(config, fx_bits: int):
    """Host mirror of the rank-1 accumulator columns ``_reduce_per_pk``
    produces (all int32): deterministic packing order for the fetch."""
    names = ["count"]
    n_lanes = -(-je._FX_PAYLOAD_BITS // fx_bits)
    for spec in je._fixedpoint_layout(config):
        names += [f"{spec.name}_fx{k}" for k in range(n_lanes)]
    return sorted(names)


def _tree_consts():
    from pipelinedp_tpu.ops import quantile_tree as qt
    return qt.tree_constants()  # (b, height, n_mid, bucket_w == span)


def _combine_shards(x, axis, dim, multiproc, topo=None):
    """Delegates to :func:`parallel.sharded.combine_shards` — the ONE
    cross-shard exchange policy: owner-block ``psum_scatter`` along
    ``dim`` on a single-controller mesh; replicating ``psum`` on a
    multi-process mesh (another process's owner block is not
    host-addressable). ``topo`` (``parallel.sharded.topology_of`` of
    the kernel's mesh) steers the hierarchical two-stage exchange and
    the ici/dcn byte accounting."""
    from pipelinedp_tpu.parallel import sharded as psh
    return psh.combine_shards(x, axis, dim, multiproc, topo=topo)


def _chunk_body(config, num_partitions, planes, values, n_valid, key,
                fx_bits, n_pid_planes, kernel_backend="xla"):
    """The shared per-chunk trace: widen the narrow id planes, derive
    the validity mask from the row count, bound + reduce. ONE body for
    all four kernels (single-device / sharded x pass A / pass B) — the
    mesh-vs-single-device parity contract rests on them tracing
    identical row math. ``kernel_backend`` steers the per-pk reduction
    (the Pallas lane-packed segment sum vs the XLA scatter — bit-
    identical either way); the pass-B kernels leave it at "xla" since
    their reduction output is dead code XLA eliminates anyway."""
    pid = je._widen_ids(planes[:n_pid_planes])
    pk = je._widen_ids(planes[n_pid_planes:])
    valid = jnp.arange(pid.shape[0]) < n_valid
    return je._partials(config, num_partitions, pid, pk, values, valid,
                        key, fx_bits, kernel_backend=kernel_backend)


def _pack_rank1(part, nseg):
    """[C+1, P] int32 stack: rank-1 columns in sorted-name order, the
    privacy-id count last (the fetch's host mirror is ``_rank1_names``).
    Returns (packed, vector_sum | None)."""
    vec = part.pop("vector_sum", None)
    names = sorted(k for k in part)
    return jnp.stack([part[k] for k in names] + [nseg]), vec


@scoped("walk")
def _mid_histogram(config, num_partitions, qrows):
    """The chunk's [P * n_mid] mid-level quantile-tree histogram
    (additive across chunks and shards)."""
    _, _, n_mid, bucket_w = _tree_consts()
    qpk, leaf, kept = qrows
    return jax.ops.segment_sum(
        kept.astype(jnp.int32),
        qpk * n_mid + jnp.minimum(leaf // bucket_w, n_mid - 1),
        num_segments=num_partitions * n_mid)


@instrumented_jit(phase="pass_a", static_argnames=(
    "config", "num_partitions", "fx_bits", "n_pid_planes",
    "kernel_backend"))
def _partials_kernel(config, num_partitions, planes, values, n_valid, key,
                     fx_bits, n_pid_planes, kernel_backend="xla"):
    """One chunk's bounding + per-pk reduction, packed for the fetch:
    the ``_pack_rank1`` stack, the rank-2 vector sums (or None), and —
    for percentile configs — the ``_mid_histogram`` (stays
    device-resident).

    Ids arrive as narrow byte planes (fewer bytes over the host link,
    exactly as in ``jax_engine.pad_and_put``) and the row-validity mask
    is derived on device from the scalar row count."""
    part, nseg, qrows = _chunk_body(config, num_partitions, planes,
                                    values, n_valid, key, fx_bits,
                                    n_pid_planes,
                                    kernel_backend=kernel_backend)
    packed, vec = _pack_rank1(part, nseg)
    mid = (_mid_histogram(config, num_partitions, qrows)
           if config.percentiles else None)
    return packed, vec, mid


@instrumented_jit(phase="pass_b", static_argnames=(
    "config", "num_partitions", "fx_bits", "n_pid_planes", "n_block"))
def _pct_sub_kernel(config, num_partitions, planes, values, n_valid, key,
                    fx_bits, n_pid_planes, sub_start, p_offset, n_block):
    """Pass B: recompute the chunk's bounded rows (same key -> identical
    bounding sample as pass A) and count leaves inside each quantile's
    chosen subtree — [n_block, Qc, span] int32, additive across chunks.
    ``n_block``/``p_offset`` select a partition block (the full axis is
    n_block == num_partitions, p_offset == 0): the per-partition counts
    are identical either way, which is what makes the partition-block-
    chunked walk bit-identical to the unchunked one."""
    _, _, qrows = _chunk_body(config, num_partitions, planes, values,
                              n_valid, key, fx_bits, n_pid_planes)
    qpk, leaf, kept = qrows
    _, _, _, span = _tree_consts()
    return je._subtree_counts(qpk, leaf, kept, sub_start, n_block, span,
                              p_offset=p_offset)


@instrumented_jit(phase="pass_b", static_argnames=(
    "config", "num_partitions", "fx_bits", "n_pid_planes", "n_block",
    "kernel_backend"))
def _pct_multi_sub_kernel(config, num_partitions, planes, values, n_valid,
                          key, fx_bits, n_pid_planes, sub_starts,
                          p_offsets, n_block, kernel_backend="xla"):
    """Multi-tile pass B: ONE bounding recompute of the chunk's rows
    (same key -> identical bounding sample as pass A) scatters into
    EVERY tile the sweep planner packed into this round —
    ``sub_starts`` [T, Pb, Qc], ``p_offsets`` [T], output
    [T, Pb, Qc, span] int32, additive across chunks. Per tile the
    counts are exactly ``_pct_sub_kernel``'s, so the packed sweep is
    bit-identical to the per-tile loop while paying the batch stream
    (and the row recompute) once instead of T times."""
    _, _, qrows = _chunk_body(config, num_partitions, planes, values,
                              n_valid, key, fx_bits, n_pid_planes)
    qpk, leaf, kept = qrows
    _, _, _, span = _tree_consts()
    return je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                    p_offsets, n_block, span,
                                    kernel_backend=kernel_backend)


@dataclasses.dataclass(frozen=True)
class PassBPlan:
    """The sweep planner's output: how pass B covers the (quantile x
    partition) grid. ``tiles`` are [p_blk, q_chunk]-shaped
    (quantile-group, partition-block) units in walk order (quantile
    groups outer, partition blocks inner — the last group/block may be
    smaller); ``sweeps`` packs consecutive same-shape tiles so that one
    batch-stream traversal serves every tile in the pack while the
    combined [T, Pb, Qc, span] sub-histogram stays within the byte
    cap. One tile covering the full grid (the common case) is one
    sweep; the planner's job is to make the chunked regime pay
    ``len(sweeps)`` stream reads instead of ``len(tiles)``."""
    q_chunk: int                 #: quantiles per (full) tile
    p_blk: int                   #: partitions per (full) tile
    tiles_per_sweep: int         #: cap // tile_units for a full tile
    tiles: Tuple[Tuple[int, int, int], ...]        #: (q0, qc, p0)
    sweeps: Tuple[Tuple[Tuple[int, int, int], ...], ...]

    @property
    def n_tiles(self) -> int:
        return len(self.tiles)

    @property
    def n_sweeps(self) -> int:
        return len(self.sweeps)

    @property
    def chunked(self) -> bool:
        return len(self.tiles) > 1


def plan_pass_b_sweeps(P_pad, Q, span, cap, q_chunk=0) -> PassBPlan:
    """Sizes pass B's stream sweeps BEFORE anything streams. The device
    budget is ``cap`` bytes of int32 [.., span] subtree block; the unit
    of account is one [1, 1, span] block. The planner searches the
    (q_chunk, p_blk) tile grids whose tiles fit the cap and picks the
    one minimizing STREAM SWEEPS — the round count the host link pays —
    tie-breaking toward fewer tiles (fewer scatters + walk launches),
    then larger partition blocks (the historical per-tile shapes, so
    the non-packable regimes keep their exact old round structure).
    A positive ``q_chunk`` (the registry's knob) pins the
    quantile-group width instead of searching it — every tiling is
    bit-identical, so the pin is a pure performance choice; an
    infeasible pin falls back to the full search. Past the cap,
    capacity becomes extra sweeps (a time cost), never a refusal; only
    a cap below a single [1, 1, span] block (necessarily
    test-shrunken) raises."""
    unit = span * 4
    if unit > cap:
        raise NotImplementedError(
            f"streamed percentiles need one [1, 1, {span}] "
            f"subtree block ({unit} bytes) within the subhist byte "
            "cap — the cap is below a single partition's block")
    budget = cap // unit  # [1, 1, span] blocks per sweep
    if P_pad * Q <= budget and not (0 < q_chunk < Q):
        tile = ((0, Q, 0),)
        return PassBPlan(Q, P_pad, 1, tile, (tile,))
    # Candidate partition blocks: the full axis (which may be a
    # non-pow2 multiple of the mesh size) plus the powers of two that
    # DIVIDE it — divisibility keeps every partition block full-size,
    # so the sweep estimate below is exactly what the greedy packer
    # produces (a non-dividing pb would alternate block shapes per
    # q-group and fragment the same-shape packing runs).
    pbs = sorted({P_pad} | {1 << k for k in range(P_pad.bit_length())
                            if P_pad % (1 << k) == 0},
                 reverse=True)
    best = None
    qcs = ([min(int(q_chunk), Q)] if q_chunk and q_chunk > 0
           else range(1, Q + 1))
    for qc in qcs:
        for pb in pbs:
            if qc * pb > budget:
                continue
            t_full = budget // (qc * pb)
            n_pb = P_pad // pb
            n_fullq, rq = divmod(Q, qc)
            n_tiles = (n_fullq + (1 if rq else 0)) * n_pb
            sweeps = -(-(n_fullq * n_pb) // t_full)
            if rq:
                sweeps += -(-n_pb // (budget // (rq * pb)))
            key = (sweeps, n_tiles, -pb, -qc)
            if best is None or key < best[0]:
                best = (key, qc, pb, t_full)
    if best is None and q_chunk:
        # The pinned quantile-group width fits no partition block under
        # this cap — fall back to the full search rather than refuse (a
        # plan must never make a previously-feasible shape infeasible).
        obs.event("plan.q_chunk_infeasible", q_chunk=int(q_chunk),
                  Q=int(Q), P_pad=int(P_pad), cap=int(cap))
        return plan_pass_b_sweeps(P_pad, Q, span, cap)
    _, qc, pb, t_full = best
    tiles = tuple((q0, min(qc, Q - q0), p0)
                  for q0 in range(0, Q, qc)
                  for p0 in range(0, P_pad, pb))
    sweeps = []
    i = 0
    while i < len(tiles):
        qn, pn = tiles[i][1], min(pb, P_pad - tiles[i][2])
        t_cap = max(1, budget // (qn * pn))
        j = i
        while (j < len(tiles) and j - i < t_cap and tiles[j][1] == qn
               and min(pb, P_pad - tiles[j][2]) == pn):
            j += 1
        sweeps.append(tiles[i:j])
        i = j
    return PassBPlan(qc, pb, t_full, tiles, tuple(sweeps))


@instrumented_jit(phase="pass_a", static_argnames=(
    "config", "num_partitions", "mesh", "fx_bits", "n_pid_planes",
    "kernel_backend"))
def _sharded_partials_kernel(config, num_partitions, mesh, planes, values,
                             n_valid_shard, key, fx_bits, n_pid_planes,
                             kernel_backend="xla"):
    """Mesh twin of ``_partials_kernel``: each device bounds + reduces
    ITS shard of the chunk's rows (rows arrive pid-sharded over the
    mesh axis, so contribution bounding is shard-local exactly as in
    ``parallel/sharded.py``), then ONE ``psum_scatter`` per output
    hands every owner its partition block. Outputs come back
    partition-sharded; summed across chunks they equal the single-batch
    sharded kernel's accumulators."""
    from pipelinedp_tpu.parallel import sharded as psh
    axis = mesh.axis_names[0]
    has_vec = "VECTOR_SUM" in config.metrics
    multiproc = mesh.is_multi_process
    topo = psh.topology_of(mesh)

    def _combine(x, dim):
        return _combine_shards(x, axis, dim, multiproc, topo=topo)

    def local_fn(planes, values, n_valid, key):
        # lint: disable=rng-purity(per-shard bound key: fold of the shard index)
        k_bound = jax.random.fold_in(key, jax.lax.axis_index(axis))
        part, nseg, qrows = _chunk_body(config, num_partitions, planes,
                                        values, n_valid[0], k_bound,
                                        fx_bits, n_pid_planes,
                                        kernel_backend=kernel_backend)
        packed, vec = _pack_rank1(part, nseg)
        outs = [_combine(packed, 1)]
        if vec is not None:
            outs.append(_combine(vec, 0))
        if config.percentiles:
            mid = _mid_histogram(config, num_partitions, qrows)
            outs.append(_combine(mid, 0))
        return tuple(outs)

    shard, repl = psh.PSpec(axis), psh.PSpec()
    own = repl if multiproc else shard
    out_specs = [repl if multiproc else psh.PSpec(None, axis)]
    if has_vec:
        out_specs.append(own)
    if config.percentiles:
        out_specs.append(own)
    mapped = psh.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tuple(shard for _ in planes), shard, shard, repl),
        out_specs=tuple(out_specs), check_vma=False)
    outs = list(mapped(planes, values, n_valid_shard, key))
    packed = outs.pop(0)
    vec = outs.pop(0) if has_vec else None
    mid = outs.pop(0) if config.percentiles else None
    return packed, vec, mid


@instrumented_jit(phase="pass_b", static_argnames=(
    "config", "num_partitions", "mesh", "fx_bits", "n_pid_planes",
    "n_block"))
def _sharded_pct_sub_kernel(config, num_partitions, mesh, planes, values,
                            n_valid_shard, key, fx_bits, n_pid_planes,
                            sub_start, p_offset, n_block):
    """Mesh twin of ``_pct_sub_kernel``: recompute this shard's bounded
    rows (same per-shard key derivation as pass A -> identical bounding
    sample) and combine the [n_block, Qc, span] subtree-leaf counts
    across shards. The full axis (n_block == num_partitions)
    psum_scatters to owner blocks as before; a partition BLOCK
    (n_block < num_partitions — the block-chunked walk) uses a
    replicating psum instead: the block is at most the byte cap by
    construction, and psum has no divisibility constraint on the block
    size."""
    from pipelinedp_tpu.parallel import sharded as psh
    axis = mesh.axis_names[0]
    _, _, _, span = _tree_consts()
    multiproc = mesh.is_multi_process  # see _sharded_partials_kernel
    blocked = n_block < num_partitions
    topo = psh.topology_of(mesh)

    def local_fn(planes, values, n_valid, key, sub_start, p_offset):
        # lint: disable=rng-purity(per-shard bound key: fold of the shard index)
        k_bound = jax.random.fold_in(key, jax.lax.axis_index(axis))
        _, _, qrows = _chunk_body(config, num_partitions, planes,
                                  values, n_valid[0], k_bound, fx_bits,
                                  n_pid_planes)
        qpk, leaf, kept = qrows
        sub = je._subtree_counts(qpk, leaf, kept, sub_start, n_block,
                                 span, p_offset=p_offset)
        return _combine_shards(sub, axis, 0, multiproc or blocked,
                               topo=topo)

    shard, repl = psh.PSpec(axis), psh.PSpec()
    mapped = psh.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tuple(shard for _ in planes), shard, shard, repl, repl,
                  repl),
        out_specs=repl if (multiproc or blocked) else shard,
        check_vma=False)
    return mapped(planes, values, n_valid_shard, key, sub_start, p_offset)


@instrumented_jit(phase="pass_b", static_argnames=(
    "config", "num_partitions", "mesh", "fx_bits", "n_pid_planes",
    "n_block", "kernel_backend"))
def _sharded_pct_multi_sub_kernel(config, num_partitions, mesh, planes,
                                  values, n_valid_shard, key, fx_bits,
                                  n_pid_planes, sub_starts, p_offsets,
                                  n_block, kernel_backend="xla"):
    """Mesh twin of ``_pct_multi_sub_kernel``: each shard recomputes its
    bounded rows once (same per-shard key derivation as pass A) and
    scatters them into every packed tile's [Pb, Qc, span] block; the
    [T, Pb, Qc, span] stack combines across shards with the replicating
    ``psum`` (the blocked-tile policy of ``_sharded_pct_sub_kernel``:
    the combined stack is at most the byte cap by construction, and
    psum has no divisibility constraint on the block sizes)."""
    from pipelinedp_tpu.parallel import sharded as psh
    axis = mesh.axis_names[0]
    _, _, _, span = _tree_consts()
    topo = psh.topology_of(mesh)

    def local_fn(planes, values, n_valid, key, sub_starts, p_offsets):
        # lint: disable=rng-purity(per-shard bound key: fold of the shard index)
        k_bound = jax.random.fold_in(key, jax.lax.axis_index(axis))
        _, _, qrows = _chunk_body(config, num_partitions, planes,
                                  values, n_valid[0], k_bound, fx_bits,
                                  n_pid_planes)
        qpk, leaf, kept = qrows
        sub = je._subtree_counts_multi(qpk, leaf, kept, sub_starts,
                                       p_offsets, n_block, span,
                                       kernel_backend=kernel_backend)
        return psh.combine_shards(sub, axis, 0, True, topo=topo)

    shard, repl = psh.PSpec(axis), psh.PSpec()
    mapped = psh.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tuple(shard for _ in planes), shard, shard, repl, repl,
                  repl),
        out_specs=repl, check_vma=False)
    return mapped(planes, values, n_valid_shard, key, sub_starts,
                  p_offsets)


@instrumented_jit(phase="walk", static_argnames=("config", "P"))
@scoped("walk")
def _walk_top_kernel(config, P, mid, key, scale):
    """Walk the levels the mid histogram serves (node width >= bucket_w)
    — the streaming twin of ``jax_engine._percentile_values``' top-
    histogram path, with the SAME node-noise keying so a streamed run
    with the engine's seed matches the single-batch walk bit-for-bit."""
    b, height, n_mid, bucket_w = _tree_consts()
    quantiles = np.asarray([p / 100.0 for p in config.percentiles],
                           np.float32)
    Q = quantiles.shape[0]
    mid = mid.reshape(P, n_mid)
    lo = jnp.full((P, Q), float(config.min_value), jnp.float32)
    hi = jnp.full((P, Q), float(config.max_value), jnp.float32)
    target = jnp.broadcast_to(quantiles[None, :], (P, Q))
    leaf_lo = jnp.zeros((P, Q), jnp.int32)
    done = jnp.zeros((P, Q), bool)
    level_offset = 0
    # The mid histogram serves exactly the levels whose node width is
    # >= bucket_w: levels 0 and 1 for ANY tree height (w = b^(h-1-l)
    # >= b^(h-2) iff l <= 1).
    for level in range(min(2, height)):
        w = b**(height - 1 - level)
        base = leaf_lo // w
        raw = je._mid_level_counts(mid, base, w, bucket_w, b)
        lo, hi, target, leaf_lo, done = je._walk_level(
            config.noise_kind, key, scale, raw, base, level_offset, lo,
            hi, target, leaf_lo, done, b, w)
        level_offset += b**(level + 1)
    return lo, hi, target, leaf_lo, done


@instrumented_jit(phase="walk", static_argnames=("config", "P"))
@scoped("walk")
def _walk_bottom_kernel(config, P, sub, sub_start, lo, hi, target,
                        leaf_lo, done, key, scale, p_offset):
    """Finish the walk from the accumulated [P, Qc, span] subtree leaf
    histograms (levels below the mid histogram). ``Qc`` may be a CHUNK
    of the quantile list and ``P`` a BLOCK of the partition axis whose
    first global partition is ``p_offset`` (the over-cap fallbacks walk
    quantile groups / partition blocks independently — valid because
    node noise is a pure function of the GLOBAL (partition, node id),
    threaded here via ``pk_index``, so each walk's descent is identical
    whether its neighbors walk alongside it or not); the caller applies
    the cross-quantile monotone step over the full list."""
    b, height, n_mid, bucket_w = _tree_consts()
    pk_index = (p_offset + jnp.arange(P)).astype(jnp.uint32)
    # All remaining levels (node width < bucket_w) read the [P, Qc,
    # span] subtree histograms — any height: within the subtree a
    # width-w node is a contiguous group of w leaves.
    level_offset = sum(b**(level + 1) for level in range(min(2, height)))
    for level in range(min(2, height), height):
        w = b**(height - 1 - level)
        base = leaf_lo // w
        raw = je._sub_level_counts(sub, sub_start, leaf_lo, w, b)
        lo, hi, target, leaf_lo, done = je._walk_level(
            config.noise_kind, key, scale, raw, base, level_offset, lo,
            hi, target, leaf_lo, done, b, w, pk_index=pk_index)
        level_offset += b**(level + 1)
    return lo + (hi - lo) * target


@instrumented_jit(phase="select", static_argnames=("config",
                                                   "num_partitions"))
def _select_kernel(config, num_partitions, part_nseg, keep_table,
                   sel_threshold, sel_scale, sel_min_count,
                   sel_rows_per_uid, k_sel):
    """Batched partition selection over the combined partials — the same
    draw structure as the single-batch kernel's selection block."""
    keep_pk, _ = je._selection_and_metrics(
        config, num_partitions, {}, part_nseg,
        jnp.zeros(1, jnp.float32), keep_table, sel_threshold, sel_scale,
        sel_min_count, sel_rows_per_uid, k_sel, k_sel)
    return keep_pk


def _batch_assignment(config, encoded, n_batches: int, seed: int,
                      n_dev: int = 1):
    """Row order + per-(batch, shard) counts such that each privacy
    unit's rows are contiguous within ONE shard of one batch (bounding
    must see all of a unit's rows together; the shard hash matches
    ``parallel/sharded.py`` so mesh streaming bounds identically to the
    single-batch mesh kernel). Without privacy ids every row is its own
    unit, so plain contiguous slices suffice (no reorder). Returns
    ``(order | None, counts[n_batches, n_dev])``."""
    from pipelinedp_tpu.ingest import assign as ingest_assign

    n = encoded.n_rows
    cells = n_batches * n_dev
    if config.bounds_already_enforced:
        base, rem = divmod(n, cells)
        counts = np.full(cells, base, np.int64)
        counts[:rem] += 1
        return None, counts.reshape(n_batches, n_dev)
    # Hash before the bucketing (id families sharing low bits would pile
    # into one batch), salt by the run seed so adversarial id sets can't
    # target a batch across runs.
    h = fmix32(encoded.pid.astype(np.uint32) ^ np.uint32(seed & 0xFFFFFFFF))
    batch_of_row = ((h.astype(np.uint64) * np.uint64(n_batches)) >>
                    np.uint64(32)).astype(np.int64)
    if n_dev > 1:
        # UNsalted shard hash — the same ``fmix32(pid) % n_dev`` as
        # ``sharded_fused_aggregate``, independent of the batch hash.
        shard = (fmix32(encoded.pid.astype(np.uint32)) %
                 np.uint32(n_dev)).astype(np.int64)
        cell_of_row = batch_of_row * n_dev + shard
    else:
        cell_of_row = batch_of_row
    # O(n) counting-sort scatter (bit-identical to the former stable
    # argsort, ~4x faster at bench scale — see ingest/assign.py).
    order, counts = ingest_assign.group_rows_by_cell(cell_of_row, cells)
    return order, counts.reshape(n_batches, n_dev)


def stream_partials_and_select(config, encoded, scales, keep_table,
                               sel_threshold, sel_scale, sel_min_count,
                               sel_rows_per_uid, rng_seed: Optional[int],
                               mesh=None, checkpoint=None,
                               executor: Optional[bool] = None,
                               cache_bytes: Optional[int] = None
                               ) -> Tuple[np.ndarray, Dict, Dict]:
    """Elastic wrapper around the streaming aggregation: device or
    process loss mid-stream (an injected ``faults.DeviceLost``, or the
    mesh supervisor's ``MeshParticipantLost`` heartbeat-silence verdict)
    re-forms the mesh from the survivors (``parallel.sharded.
    reform_mesh``), records a structured ``mesh.reshard`` event
    (old shape -> new shape, reason, chunk index) and re-enters the
    stream at the new shape — resuming from the last checkpoint when a
    store is attached, restarting cleanly otherwise. Either way the
    released values are bit-identical to a clean run at the surviving
    shape (noise keys are pure functions of the run seed; the resumed
    fold adopts the ORIGINAL batch assignment, regrouped onto the
    smaller mesh — see ``ingest.assign.regroup_cells`` for the
    non-binding-caps caveat). Requires a fixed ``rng_seed``: without
    one the loss re-raises (replay would re-draw noise).

    A third detection channel: a peer that dies while this process is
    already blocked INSIDE a matching collective surfaces here as a
    runtime error from the transport, not as a supervisor verdict.
    ``health.collective_failure_to_loss`` confirms an actual peer
    death against the beat files before that error is allowed to
    shrink the mesh; unconfirmed errors re-raise untouched.

    See :func:`_stream_impl` for the streaming contract itself."""
    from pipelinedp_tpu.parallel import sharded as sharded_mod
    from pipelinedp_tpu.resilience import faults
    from pipelinedp_tpu.resilience import health as health_mod

    reshards: list = []
    while True:
        try:
            return _stream_impl(
                config, encoded, scales, keep_table, sel_threshold,
                sel_scale, sel_min_count, sel_rows_per_uid, rng_seed,
                mesh=mesh, checkpoint=checkpoint, executor=executor,
                cache_bytes=cache_bytes, _reshards=reshards)
        except (faults.DeviceLost, health_mod.MeshParticipantLost,
                RuntimeError) as loss:
            if not isinstance(loss, (faults.DeviceLost,
                                     health_mod.MeshParticipantLost)):
                # XlaRuntimeError (a RuntimeError subclass) out of a
                # collective the dead peer never joined.
                converted = health_mod.collective_failure_to_loss(
                    loss, mesh)
                if converted is None:
                    raise
                loss = converted
            if rng_seed is None or mesh is None:
                raise
            new_mesh = sharded_mod.reform_mesh(mesh)
            if new_mesh is None:
                raise  # nothing left to shrink to
            record = {
                "old_devices": int(mesh.devices.size),
                "new_devices": int(new_mesh.devices.size),
                "reason": ("participant_lost"
                           if isinstance(loss,
                                         health_mod.MeshParticipantLost)
                           else "device_lost"),
                "chunk": int(getattr(loss, "index", -1)),
                "detail": str(loss),
            }
            reshards.append(record)
            obs.inc("mesh.reshards")
            obs.event("mesh.reshard", **record)
            obs.monitor.update_mesh({
                "state": "reformed", "reshards": len(reshards),
                "old_devices": record["old_devices"],
                "new_devices": record["new_devices"],
                "reason": record["reason"]})
            mesh = new_mesh


def _stream_impl(config, encoded, scales, keep_table,
                 sel_threshold, sel_scale, sel_min_count,
                 sel_rows_per_uid, rng_seed: Optional[int],
                 mesh=None, checkpoint=None,
                 executor: Optional[bool] = None,
                 cache_bytes: Optional[int] = None,
                 _reshards: Optional[list] = None
                 ) -> Tuple[np.ndarray, Dict, Dict]:
    """Runs the streaming aggregation. Returns ``(keep[P_pad] bool,
    part64, stats)`` where ``part64`` holds the combined float64/int64
    accumulator columns ready for ``jax_engine._host_release``; for
    percentile configs ``stats["percentile_values"]`` carries the
    [P_pad, Q] walked quantile values (pass B re-streams the batches —
    see the module docstring).

    ``executor`` selects the overlapped ingest pipeline
    (``pipelinedp_tpu/ingest``): a background stager prepares batch b+1
    while the device computes batch b, and an ordered fold worker
    fetches + folds finished batches behind the dispatch thread. None
    (the default) follows ``PIPELINEDP_TPU_INGEST_EXECUTOR`` (on unless
    set to 0). The overlapped and serial paths are BIT-IDENTICAL —
    the fold worker preserves the exact left-fold float64 operation
    sequence and checkpoint order — proven by ``tests/test_ingest.py``.

    ``cache_bytes`` overrides the pass-B device-cache budget
    (``PIPELINEDP_TPU_STREAM_CACHE`` when None; 0 disables). The cache
    is a PREFIX cache: on overflow the already-cached batch prefix
    stays device-resident and only the suffix re-ships per pass-B sweep
    (``pass_b_source: "hybrid"``) — one batch over budget no longer
    forces 100% reship.

    ``checkpoint`` (a ``resilience.checkpoint.CheckpointStore`` or path)
    enables budget-safe resume: the host accumulators are pure monoid
    state and every noise key is a pure function of the run seed, so
    persisting ``(next_batch, accumulators)`` after each fold lets a
    killed run resume bit-identically — same noise draws, same
    kept-partition set, one budget charge. Requires a fixed
    ``rng_seed`` (resume must replay identical keys). A checkpoint
    written by a different (config, data, seed) run raises
    ``CheckpointMismatch`` instead of silently restarting.

    With a ``mesh``, every chunk is itself pid-sharded over the mesh
    and reduced by the sharded kernels; host accumulation, selection
    and release are IDENTICAL to the single-device stream (the owner
    blocks concatenate to the same [C+1, P] layout). Fetches gather
    the owner-sharded outputs through the single-controller runtime.
    On a multi-PROCESS mesh (``jax.distributed``) the kernels switch
    from owner-block ``psum_scatter`` to a replicating ``psum`` so
    every process fetches its own complete copy and runs the identical
    host fold/selection — proven across a two-process gloo mesh by
    ``tests/test_multihost.py``."""
    from pipelinedp_tpu import ingest
    from pipelinedp_tpu.ingest import assign as ingest_assign
    from pipelinedp_tpu.ops import noise as noise_ops
    from pipelinedp_tpu.resilience import checkpoint as ckpt_mod
    from pipelinedp_tpu.resilience import faults
    from pipelinedp_tpu.resilience import health as health_mod

    # The run's span tracer: phase totals always accumulate (the bench
    # timing fields below are derived views over them), full spans
    # reach the ledger when PIPELINEDP_TPU_TRACE is set.
    tr = obs.run_tracer()
    # Live telemetry: under PIPELINEDP_TPU_HEARTBEAT a monitor thread
    # streams heartbeats (phase, batches/sweeps done vs planned,
    # rows/s, pace-vs-baseline) and watches for stalls; off, this is a
    # no-op and the spans below cost exactly what they did before.
    obs.monitor.maybe_start()

    # One knob vector for the whole run (env > seam > default —
    # plan/knobs.py), taken once so pass A and pass B read the same
    # values even if an env var or seam moves mid-run.
    from pipelinedp_tpu.plan import knobs as knobs_mod
    knob_vec = {name: v for name, (v, _)
                in knobs_mod.resolve_all().items()}

    use_executor = (bool(knob_vec["ingest_executor"])
                    if executor is None else bool(executor))
    # Resolved OUTSIDE jit and passed as a static argument to the
    # chunk kernels: jit caches by signature, so a backend switch
    # between requests re-traces instead of silently reusing the
    # other backend's compiled program.
    kernel_backend = str(knob_vec["kernel_backend"])
    if mesh is not None and mesh.is_multi_process:
        # Multi-PROCESS meshes run the serial path: every process must
        # enqueue the same device work in the same order, and the
        # executor's stager/fold threads interleave transfers with the
        # collective kernels differently per process — measured as a
        # gloo rendezvous wedge on the two-process CPU mesh. The
        # single-controller mesh (one process, many devices) keeps the
        # overlap. A formerly-silent branch: the event makes the forced
        # serialization visible in the run ledger.
        if use_executor:
            obs.event("ingest.forced_serial",
                      reason="multi-process mesh: threaded enqueue "
                             "wedges the collective rendezvous")
            obs.inc("ingest.forced_serial")
        use_executor = False

    # Mesh supervision (elastic multi-process recovery): armed only
    # when the harness set PIPELINEDP_TPU_MESH_DIR and the mesh spans
    # processes. Each collective dispatch first passes the supervisor's
    # gate — publish my liveness beat, wait for every peer's — so a
    # dead peer surfaces as MeshParticipantLost BEFORE this process
    # enqueues the collective that would wedge on it.
    sup = (health_mod.supervisor_from_env(mesh)
           if mesh is not None else None)

    n_dev = mesh.devices.size if mesh is not None else 1
    P = len(encoded.pk_vocab)
    P_pad = je._pad_pow2(P)
    if mesh is not None:
        # Owner blocks must tile the pk axis (same rounding + replay
        # caveat as ``sharded_fused_aggregate``: a pow2 mesh is a no-op).
        P_pad = -(-P_pad // n_dev) * n_dev
    n = encoded.n_rows
    chunk = chunk_target_rows(config, n_dev)
    n_batches = max(1, -(-n // chunk))
    seed = (rng_seed if rng_seed is not None else
            int(noise_ops._host_rng.integers(0, 2**31 - 1)))
    # lint: disable=rng-purity(seed protocol root key, pure in rng_seed)
    key = jax.random.PRNGKey(seed)
    # Same key topology as the single-batch kernel: one bounding stream
    # (folded per batch, then per shard inside the sharded kernel), one
    # selection stream.
    # lint: disable=rng-purity(root split seam, pure in the run seed)
    k_bound, k_sel, k_noise = jax.random.split(key, 3)

    if config.percentiles:
        # Plan pass B's sweeps BEFORE streaming anything: the planner
        # tiles the (quantile x partition) grid so each [Pb, Qc, span]
        # tile fits the device budget, then packs as many tiles as fit
        # under ``je._SUBHIST_BYTE_CAP`` into one stream sweep — past
        # the cap, capacity becomes extra sweeps (a time cost), never a
        # refusal. Node noise is a pure function of the GLOBAL
        # (partition, node id), so any tiling walks bit-identically to
        # the unchunked descent. Only a cap below a single [1, 1, span]
        # block (necessarily test-shrunken) is refused.
        _, _, _, span = _tree_consts()
        subhist_cap = int(knob_vec["subhist_byte_cap"])
        try:
            plan = plan_pass_b_sweeps(P_pad, len(config.percentiles),
                                      span, subhist_cap,
                                      q_chunk=int(
                                          knob_vec["q_chunk"]))
        except NotImplementedError:
            obs.inc("walk.path_streamed_refusal")
            obs.event("walk.fallback", path="streamed_refusal",
                      span_bytes=span * 4, cap=subhist_cap)
            raise
        if plan.chunked:
            # The guard-cliff path fired: extra pass-B sweeps instead
            # of a refusal — record WHICH shape triggered it and how
            # the planner packed it.
            obs.inc("walk.path_partition_block_chunked")
            obs.event("walk.fallback", path="partition_block_chunked",
                      p_blk=int(plan.p_blk), q_chunk=int(plan.q_chunk),
                      P_pad=int(P_pad), tiles=plan.n_tiles,
                      tiles_per_sweep=plan.tiles_per_sweep,
                      sweeps=plan.n_sweeps)

    # --- elastic resume: adopt the saved assignment -------------------
    # A checkpoint written at a LARGER mesh shape would normally refuse
    # to resume (its fingerprint binds n_dev). When the saved shape's
    # own fingerprint verifies AND the new size divides the old one,
    # the resume ADOPTS the saved assignment instead: same n_batches,
    # same row order, same ``fold_in(k_bound, b)`` keys — the original
    # run replayed exactly, with each batch's shard cells regrouped
    # contiguously onto the survivors (``ingest.assign.regroup_cells``).
    # The partition padding must also agree (pow2 meshes: it does), so
    # the per-pk accumulator layout is unchanged.
    ckpt_store = ckpt_mod.as_store(checkpoint)
    if ckpt_store is not None and rng_seed is None:
        raise ValueError(
            "checkpointing requires a fixed rng_seed: resume must "
            "replay the identical noise keys (the privacy budget is "
            "consumed at noise draw, not at job success)")
    adopt = None
    peeked = None
    data_dig = None
    if ckpt_store is not None and ckpt_store.exists():
        data_dig = ckpt_mod.data_digest(encoded)
        peeked = ckpt_store.load()
        a = peeked.assign if peeked is not None else None
        if (a is not None and int(a["n_dev"]) != n_dev and
                int(a["num_partitions"]) == P_pad and
                int(a["n_dev"]) % n_dev == 0):
            fp_saved = ckpt_mod.run_fingerprint(
                config, n, int(a["n_batches"]), seed, P_pad,
                int(a["n_dev"]), int(a["fx_bits"]), data=data_dig)
            if fp_saved == peeked.fingerprint:
                adopt = {k: int(v) for k, v in a.items()}
                n_batches = int(adopt["n_batches"])
                obs.inc("checkpoint.elastic_adoptions")
                obs.event("checkpoint.elastic_adoption",
                          saved_n_dev=int(adopt["n_dev"]),
                          n_dev=int(n_dev),
                          n_batches=int(n_batches))
    # Persisted reshards (prior processes) + this process's records.
    # When a run resumes its OWN checkpoint the two overlap — dedupe by
    # content, which is safe because the chunk ordinal is global and
    # monotone so no two distinct reshards compare equal.
    reshard_history = list(peeked.reshards) if peeked is not None else []
    for _rec in (_reshards or []):
        if _rec not in reshard_history:
            reshard_history.append(_rec)

    if adopt is not None:
        order, counts = _batch_assignment(config, encoded, n_batches,
                                          seed, int(adopt["n_dev"]))
        counts = ingest_assign.regroup_cells(counts, n_dev)
    else:
        order, counts = _batch_assignment(config, encoded, n_batches,
                                          seed, n_dev)
    batch_rows = counts.sum(axis=1)
    # Lane capacity is bounded by the largest chunk's GLOBAL row count
    # (shard lane sums combine by psum); padding is per shard cell.
    max_rows = int(batch_rows.max()) if len(batch_rows) else 1
    pad_rows = je._pad_rows(int(counts.max()) if counts.size else 1)
    layout = je._fixedpoint_layout(config)
    # Lane capacity is a PER-BATCH bound here — that is the whole point:
    # the plan depends on the largest chunk, not the global row count.
    # A batch can exceed the chunk target only through privacy-unit
    # skew: one unit's rows are indivisible (bounding must see them
    # together), so the heaviest unit sets the batch floor.
    try:
        fx_bits = (je._fx_plan(max_rows)[0]
                   if layout or je._vector_fx(config) else 12)
    except NotImplementedError:
        raise NotImplementedError(
            f"the largest streaming batch holds {max_rows} rows — beyond "
            "the 2^27-row per-batch lane capacity. A batch this far over "
            f"the {chunk}-row chunk target means a single privacy unit "
            "owns that many rows; its rows cannot be split across "
            "batches (contribution bounding must see them together)")
    names = _rank1_names(config, fx_bits)

    # Lane columns fold into EXACT float64 step totals per batch (the
    # scale division is deferred to release — see fold_host) and never
    # accumulate raw: only the integer count columns live in acc.
    acc = {"count": np.zeros(P_pad, np.int64),
           "privacy_id_count_raw": np.zeros(P_pad, np.int64)}
    val_acc = {spec.name: np.zeros(P_pad, np.float64) for spec in layout}
    vec_acc = None

    # Budget-safe resume: restore the monoid accumulators and skip the
    # already-folded batch prefix. The fold is left-associative, so
    # restoring the prefix sum and continuing reproduces the EXACT
    # float64 operation sequence of an uninterrupted run.
    start_batch = 0
    ckpt_fp = None
    mid_restore = None
    if adopt is not None and fx_bits != int(adopt["fx_bits"]):
        # Regrouping preserves each batch's GLOBAL row total, so the
        # lane plan recomputes to the saved width by construction; a
        # divergence means the adoption premise is broken.
        raise AssertionError(
            f"elastic adoption recomputed fx_bits={fx_bits} != saved "
            f"{adopt['fx_bits']}")
    if ckpt_store is not None:
        with tr.span("ckpt.restore", cat="checkpoint"):
            if data_dig is None:
                data_dig = ckpt_mod.data_digest(encoded)
            # Under adoption the fingerprint is the ORIGINAL shape's —
            # it stays constant across every elastic reshard, so a
            # twice-shrunken run still resumes its own checkpoints.
            ckpt_fp = ckpt_mod.run_fingerprint(
                config, n, n_batches, seed, P_pad,
                int(adopt["n_dev"]) if adopt is not None else n_dev,
                fx_bits, data=data_dig)
            saved = ckpt_store.load_for(ckpt_fp)
        if saved is not None:
            start_batch = saved.next_batch
            for name in acc:
                acc[name] = saved.arrays[f"acc:{name}"]
            for name in val_acc:
                val_acc[name] = saved.arrays[f"val:{name}"]
            if "vec" in saved.arrays:
                vec_acc = saved.arrays["vec"]
            if "mid" in saved.arrays:
                mid_restore = saved.arrays["mid"]

    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _PSpec

        from pipelinedp_tpu.parallel import sharded as psh
        row_sharding = NamedSharding(mesh, _PSpec(mesh.axis_names[0]))
    else:
        row_sharding = None

    # Phase timing now rides on spans: "ingest.stage" (host staging +
    # enqueue, both passes), "ingest.fetch" (blocked on the device for
    # batch outputs), "ingest.fold" (host fold math) — tr.total(name)
    # is the derived accumulator the bench fields read.
    obs.inc("ingest.streamed_runs")
    # Only the rows THIS process will actually stage: a checkpoint
    # resume skips the already-folded batch prefix, and the counter
    # must not let a resumed partial run masquerade as a full one.
    obs.inc("ingest.rows_ingested",
            int(batch_rows[start_batch:].sum()))
    # The heartbeat's denominator: how many non-empty batches this run
    # WILL stage (a resume skips the folded prefix), so "done vs
    # planned" is computable mid-flight, not only post-hoc.
    obs.inc("progress.batches_planned",
            int((batch_rows[start_batch:] > 0).sum()))
    if config.percentiles:
        obs.inc("progress.sweeps_planned", plan.n_sweeps)
    obs.inc("ingest.executor_overlapped" if use_executor
            else "ingest.executor_serial")

    # Plane-width tiers are decided ONCE from the global id maxima (the
    # jit signature must not vary per batch) and hoisted out of the
    # generator: percentile pass B used to rescan the full id columns
    # on every re-stream round.
    pid_spec = ("u16" if config.bounds_already_enforced else
                je._plane_spec(int(encoded.pid.max(initial=0))))
    pk_spec = je._plane_spec(int(encoded.pk.max(initial=0)))

    # Device-resident batch cache: percentile pass B re-reads shipped
    # batches from HBM instead of paying the host link twice. Bounded
    # by the per-device HBM budget; overflow FREEZES the cache (the
    # prefix stays resident, pass B reships only the suffix — the
    # hybrid source). A RESUMED run never caches: the skipped batch
    # prefix is absent, so a partial cache would silently drop those
    # rows from pass B.
    cache_cap = (int(knob_vec["stream_cache_bytes"])
                 if cache_bytes is None else int(cache_bytes))
    cache: Optional[list] = ([] if config.percentiles and
                             start_batch == 0 and cache_cap > 0
                             else None)
    cache_used = 0
    cache_frozen = False
    cache_upto = 0   # first batch index NOT in the cached prefix
    reship_bytes = [0]  # pass-B host->device traffic (mutated by gen)

    # Staging-buffer strategy. Runs that FEED the device cache may
    # retain shipped arrays indefinitely, so they keep fresh-copy
    # semantics: a fresh values buffer per batch, i32-mode planes
    # copied. Everything else (including percentile runs whose cache is
    # disabled or resumed away) stages into a rotating PAIR of buffer
    # sets and ships the narrowed planes without defensive copies:
    # ``device_put`` may zero-copy a numpy array, so a set is reused
    # only after the batch staged from it had its OUTPUTS fetched
    # (``StagingRing`` — a fetch proves the kernel consumed its
    # inputs), i.e. two batches later at the earliest.
    copy_mode = cache is not None
    ring = None if copy_mode else ingest.StagingRing(2)

    def batches(start_at=0, cancelled=None, ring=ring,
                track_reship=False):
        """Ships the deterministic batch sequence to the device; pass A
        and pass B (percentiles) iterate it identically, on the caller's
        thread (serial path) or on the executor's stager thread
        (``cancelled`` is the stager's teardown event). ``ring`` is the
        buffer-reuse gate: None means fresh-copy staging (retention
        safe — the pass-A path that feeds the device cache), a
        ``StagingRing`` means rotating buffer sets (pass A's default,
        and every pass-B reship sweep — retention is only needed while
        FEEDING the cache, which pass B never does). Tails past each
        shard cell's row count are re-zeroed on reuse (the kernel
        masks rows past n_valid, so no invariant rests on padding
        content — the zeroing just keeps shipped bytes deterministic).

        On a mesh the staging layout is [n_dev * pad_rows]: shard d's
        rows occupy cell d, and the one ``device_put`` places the
        arrays row-sharded over the mesh (cell boundaries = shard
        boundaries, so placement is a pure scatter). Yields
        (b, planes, values_d, nv, n_pid_planes) where ``nv`` is the
        device-ready valid-row count (scalar, or [n_dev] sharded)."""
        buf_len = n_dev * pad_rows
        copy = ring is None  # fresh-copy staging vs rotating buffers
        zeros_dev = None  # shared zero values for COUNT-style runs
        n_sets = 1 if copy else ring.n_slots
        pid_bufs = [np.zeros(buf_len, np.int32) for _ in range(n_sets)]
        pk_bufs = [np.zeros(buf_len, np.int32) for _ in range(n_sets)]
        vshape = ((buf_len, config.vector_size)
                  if config.vector_size else (buf_len,))
        val_bufs = ([np.zeros(vshape, np.float32) for _ in range(n_sets)]
                    if config.needs_values and not copy else None)
        offset = 0
        staged = 0
        for b in range(n_batches):
            ccounts = counts[b]
            if b < start_at:
                # Resume skip: already folded from the checkpoint —
                # advance the row cursor without staging or shipping.
                offset += int(ccounts.sum())
                continue
            if int(ccounts.sum()) == 0:
                continue
            if ring is not None:
                # Blocks until the set staged two batches ago has had
                # its outputs fetched; aborts promptly on teardown.
                ring.acquire(cancelled)
            # The span is the former perf_counter pair: same region
            # (after the ring gate, before the yield), same total.
            with tr.span("ingest.stage", cat="ingest", batch=b):
                s = staged % n_sets
                staged += 1
                pid_b, pk_b = pid_bufs[s], pk_bufs[s]
                if copy:
                    # Fresh values buffer every batch: the pass-B
                    # device cache may retain what ships, indefinitely.
                    values_b = (np.zeros(vshape, np.float32)
                                if config.needs_values else None)
                else:
                    values_b = (val_bufs[s] if val_bufs is not None
                                else None)
                # Narrow byte planes, padded on host to the uniform
                # batch shape (uniform = ONE compile for every batch).
                for d in range(n_dev):
                    cnt = int(ccounts[d])
                    rows = (slice(offset, offset + cnt) if order is None
                            else order[offset:offset + cnt])
                    offset += cnt
                    s0 = d * pad_rows
                    if not config.bounds_already_enforced:
                        pid_b[s0:s0 + cnt] = encoded.pid[rows]
                        pid_b[s0 + cnt:s0 + pad_rows] = 0
                    pk_b[s0:s0 + cnt] = encoded.pk[rows]
                    pk_b[s0 + cnt:s0 + pad_rows] = 0
                    if values_b is not None:
                        values_b[s0:s0 + cnt] = encoded.values[rows]
                        if not copy:
                            values_b[s0 + cnt:s0 + pad_rows] = 0
                pid_planes = je._narrow_ids(pid_b, pid_spec)
                n_pid_planes = len(pid_planes)
                host = [*pid_planes, *je._narrow_ids(pk_b, pk_spec)]
                if copy:
                    # _narrow_ids returns fresh plane arrays except in
                    # "i32" mode, where it returns the staging buffer
                    # itself — copy those so a retained (cached) ship
                    # list never aliases a reused buffer. In ring mode
                    # the slot gating makes reuse safe without the copy.
                    host = [p.copy() if (p is pid_b or p is pk_b) else p
                            for p in host]
                if values_b is not None:
                    host.append(values_b)
                if track_reship:
                    # Pass-B reship accounting: the host->device bytes
                    # this sweep pays past the cached prefix — the
                    # evidence the hybrid cache exists to shrink.
                    nb = sum(int(a.nbytes) for a in host)
                    reship_bytes[0] += nb
                    obs.inc("stream.pass_b_reshipped_bytes", nb)
                if row_sharding is None:
                    dev = jax.device_put(tuple(host))  # one transfer
                    nv = jnp.int32(int(ccounts[0]))
                else:
                    # put_global, NOT device_put: a raw device_put onto
                    # a multi-process sharding dispatches a hidden
                    # equality-check collective per batch that races
                    # with the kernel's all-reduces (see
                    # parallel/sharded.py:put_global).
                    dev = psh.put_global(tuple(host), row_sharding)
                    nv = psh.put_global(ccounts.astype(np.int32),
                                        row_sharding)
                if values_b is not None:
                    planes, values_d = dev[:-1], dev[-1]
                else:
                    planes = dev
                    if zeros_dev is None:
                        if row_sharding is not None:
                            zeros_dev = psh.put_global(
                                np.zeros(buf_len, np.float32),
                                row_sharding)
                        else:
                            zeros_dev = jnp.zeros(buf_len, jnp.float32)
                    values_d = zeros_dev
                obs.inc("ingest.batches_staged")
                if not track_reship:
                    # Heartbeat progress: rows/batches actually staged
                    # toward the pass-A plan (ingest.rows_ingested is
                    # the up-front plan, not progress). Pass-B RESHIP
                    # sweeps re-run this generator and must not count
                    # again — done would overtake planned and the
                    # pace verdict's rows/s would inflate; reships are
                    # tracked by the sweep counters instead.
                    obs.inc("progress.batches_staged")
                    obs.inc("progress.rows_staged", int(ccounts.sum()))
            yield b, planes, values_d, nv, n_pid_planes

    def fold_host(host, vec):
        """Folds one batch's FETCHED [C+1, P] block into the host
        accumulators (exact left-fold float64 sequence)."""
        nonlocal vec_acc
        # Loud failure if the kernel's packed column set ever diverges
        # from the host-side name mirror (a silent mismatch would hand
        # the release mislabeled accumulators).
        assert host.shape[0] == len(names) + 1, (
            f"kernel packed {host.shape[0]} columns, host expected "
            f"{names} + nseg")
        batch64 = {name: host[i].astype(np.int64)
                   for i, name in enumerate(names)}
        batch64["privacy_id_count_raw"] = host[-1].astype(np.int64)
        # Fold this chunk's lanes into EXACT float64 step totals — the
        # scale division happens ONCE over the combined total at
        # release, so the released low bits are invariant to the batch
        # boundaries (and therefore to the mesh size: the elastic
        # reshard-resume bit-parity depends on this).
        je._fold_fx_steps(config, batch64, fx_bits)
        acc["count"] += batch64["count"]
        acc["privacy_id_count_raw"] += batch64["privacy_id_count_raw"]
        for spec in layout:
            val_acc[spec.name] += batch64[spec.name]
        if vec is not None:
            if je._vector_fx(config):
                # Same discipline as the scalar lanes: fold this
                # chunk's [P, n_lanes*D] lane sums into EXACT [P, D]
                # float64 step totals (offsets removed with the
                # CHUNK's count — offset removal is linear, so
                # per-chunk removal equals one global removal
                # exactly) and defer the scale division to release.
                v64 = je._fold_vector_fx_steps(
                    config, np.asarray(vec), batch64["count"], fx_bits)
            else:
                v64 = np.asarray(vec).astype(np.float64)
            vec_acc = v64 if vec_acc is None else vec_acc + v64

    n_saves = 0
    # Folds between checkpoint writes; clamped to >= 1 (0 would divide
    # by zero below — disable checkpointing by not passing a store).
    ckpt_every = max(1, int(os.environ.get("PIPELINEDP_TPU_CKPT_EVERY",
                                           "1")))
    # The mid histogram accumulates at FOLD time (not launch time) so a
    # checkpoint written after folding batch j never includes batch
    # j+1's in-flight histogram — the left-fold order is unchanged.
    mid_acc = (jnp.asarray(mid_restore) if mid_restore is not None
               else None)

    # Every save carries the ORIGINAL run's assignment shape (constant
    # across elastic reshards) and the reshard history — the former is
    # what lets a future resume on a smaller mesh adopt the assignment,
    # the latter is the run's structured recovery trail.
    assign_meta = (dict(adopt) if adopt is not None else
                   {"n_batches": int(n_batches), "n_dev": int(n_dev),
                    "num_partitions": int(P_pad),
                    "fx_bits": int(fx_bits)})

    def save_ckpt(next_batch):
        nonlocal n_saves
        with tr.span("ckpt.save", cat="checkpoint",
                     next_batch=next_batch):
            arrays = {f"acc:{k}": v for k, v in acc.items()}
            arrays.update({f"val:{k}": v for k, v in val_acc.items()})
            if vec_acc is not None:
                arrays["vec"] = vec_acc
            if mid_acc is not None:
                arrays["mid"] = np.asarray(mid_acc)
            ckpt_store.save(ckpt_mod.StreamCheckpoint(
                ckpt_fp, next_batch, arrays, assign=assign_meta,
                reshards=list(reshard_history)))
        n_saves += 1

    def fold_item(item):
        """Fetch + fold one launched batch, in batch order. Runs on
        the caller's thread (serial path, one batch behind the launch)
        or on the executor's single ordered fold worker — either way
        the float64 operation sequence and the checkpoint-after-fold
        order are identical. The fetch BLOCKS until the batch's kernel
        finishes, which is what retires its staging-ring slot."""
        nonlocal mid_acc
        pb, packed, vec, mid = item
        with tr.span("ingest.fetch", cat="ingest", batch=pb):
            # Injectable WEDGE point: tests hold this fetch (the span
            # stays open, no activity follows) and assert the stall
            # watchdog diagnoses the blocked worker at its deadline.
            faults.check_fetch_hold(pb)
            host = np.asarray(packed)  # [C+1, P_pad] int32, 1 transfer
            if ring is not None:
                ring.retire()
        with tr.span("ingest.fold", cat="ingest", batch=pb):
            fold_host(host, vec)
            if mid is not None:
                mid_acc = mid if mid_acc is None else mid_acc + mid
        if ckpt_store is not None and (pb + 1) % ckpt_every == 0:
            save_ckpt(pb + 1)

    def launch(item):
        """Fault check + kernel dispatch for one staged batch (async:
        returns device futures) — always on the dispatch thread, so
        injected ``ChunkFailure``s sever the run at a deterministic
        chunk boundary in both executor modes."""
        nonlocal cache_used, cache_frozen, cache_upto
        b, planes, values_d, nv, n_pid_planes = item
        # Injectable kill point: tests sever the run at chunk b and
        # assert the checkpointed resume is bit-identical.
        faults.check_chunk(b)
        # Injectable MESH-LOSS point (before the beat: a participant
        # that dies here is detected by its peers' gates below, before
        # any of them enqueues the collective this batch would wedge).
        faults.check_device_loss()
        if sup is not None:
            sup.gate()
        # lint: disable=rng-purity(per-batch bound key: fold of the batch index)
        kb = jax.random.fold_in(k_bound, b)
        with obs.device_annotation("pdp.stream_partials"):
            if mesh is None:
                packed, vec, mid = _partials_kernel(
                    config, P_pad, planes, values_d, nv, kb, fx_bits,
                    n_pid_planes=n_pid_planes,
                    kernel_backend=kernel_backend)
            else:
                packed, vec, mid = _sharded_partials_kernel(
                    config, P_pad, mesh, planes, values_d, nv, kb,
                    fx_bits, n_pid_planes=n_pid_planes,
                    kernel_backend=kernel_backend)
        if cache is not None and not cache_frozen:
            # The budget is PER-DEVICE HBM: on a mesh the arrays are
            # row-sharded, so each device holds 1/n_dev of the bytes.
            batch_hbm = (sum(int(p.nbytes) for p in planes) +
                         int(values_d.nbytes)) // n_dev
            if cache_used + batch_hbm <= cache_cap:
                cache_used += batch_hbm
                cache.append((b, planes, values_d, nv, n_pid_planes))
                cache_upto = b + 1
            else:
                # Overflow FREEZES the cache instead of dropping it:
                # the resident prefix keeps serving pass B from HBM and
                # only the suffix reships per sweep (hybrid source).
                cache_frozen = True
                obs.inc("stream.cache_overflow")
                obs.event("stream.cache_overflow",
                          cache_bytes=int(cache_used + batch_hbm),
                          cap=int(cache_cap),
                          prefix_batches=len(cache))
        return b, packed, vec, mid

    with tr.span("ingest.pass_a", cat="ingest", n_batches=n_batches,
                 executor="overlapped" if use_executor
                 else "serial") as pass_a:
        if use_executor:
            # Overlapped pass A: the stager prepares batch b+1 while
            # the device computes batch b and the fold worker drains
            # finished batches — three phases in flight at once. Any
            # failure (including injected ChunkFailures) cancels both
            # workers and joins them before propagating: no orphan
            # threads, and the checkpoint on disk is a clean fold
            # prefix.
            folder = ingest.OrderedFoldWorker(fold_item, depth=2)
            try:
                with ingest.BackgroundStager(
                        lambda cancelled: batches(start_batch,
                                                  cancelled),
                        depth=1, name="stager-a") as stager:
                    for item in stager.items(
                            poll=folder.raise_if_failed):
                        folder.submit(launch(item))
                folder.finish()
            except BaseException:
                folder.cancel()
                raise
        else:
            # Serial pass A (the bit-parity reference): fold one batch
            # late, so batch b's transfer + kernel are in flight while
            # batch b-1's fetch waits.
            pending = None
            try:
                for item in batches(start_batch):
                    out = launch(item)
                    if pending is not None:
                        fold_item(pending)
                    pending = out
            except (faults.FaultInjected,
                    health_mod.MeshParticipantLost):
                # Quiesce before propagating: the previous batch's
                # collective is still in flight ON EVERY PROCESS. A
                # dying participant that exits without draining it
                # leaves its peers' fetch of that batch wedged forever;
                # a surviving participant that re-forms without
                # draining leaves the old mesh's collective queued
                # under the new program. Fetch-and-discard completes
                # it on this side either way (the result is NOT folded:
                # the checkpoint must stay a clean fold prefix).
                if pending is not None:
                    try:
                        np.asarray(pending[1])
                    except Exception:
                        pass  # the original fault is the report
                raise
            if pending is not None:
                fold_item(pending)
    t_loop = pass_a.duration
    # Overlap evidence for the bench: time the three host/device phases
    # spent busy vs the wall clock of the whole pass-A loop — all four
    # now derived views over the run tracer's spans, same names and
    # semantics as the former perf_counter accumulators. Serial
    # execution gives t_total ≈ busy (frac ~0); overlap hides phase
    # time inside the wall (t_total < busy, frac > 0).
    t_stage = tr.total("ingest.stage")
    t_device = tr.total("ingest.fetch")
    t_fold = tr.total("ingest.fold")
    busy_a = t_stage + t_device + t_fold
    overlap = {"t_stage": t_stage, "t_device": t_device,
               "t_fold": t_fold, "t_total": t_loop,
               "overlap_frac": (max(0.0, 1.0 - t_loop / busy_a)
                                if busy_a > 0 else 0.0),
               "executor": "overlapped" if use_executor else "serial"}

    part64: Dict[str, np.ndarray] = dict(acc)
    # ONE scale division over the combined step totals — bit-identical
    # to the single-batch kernel's release (which divides its one
    # whole-dataset total) for ANY chunking.
    for spec in layout:
        part64[spec.name] = val_acc[spec.name] / spec.scale
    if vec_acc is not None:
        part64["vector_sum"] = (
            vec_acc / je._vector_fx_scale(config)
            if je._vector_fx(config) else vec_acc)

    if config.selection is None:
        keep = np.ones(P_pad, bool)
    else:
        nseg = acc["privacy_id_count_raw"]
        if nseg.max(initial=0) >= int(
                knob_vec["select_units_cap"]):
            raise NotImplementedError(
                "more than 2^31 privacy units in one partition")
        # Selection never touches the percentile walk (that runs in
        # pass B below, from histograms, not rows): strip the percentile
        # list so _selection_and_metrics skips its row-based walk.
        sel_config = dataclasses.replace(config, percentiles=())
        with tr.span("ingest.select", cat="ingest"), \
                obs.device_annotation("pdp.partition_select"):
            keep = np.asarray(_select_kernel(
                sel_config, P_pad, jnp.asarray(nseg.astype(np.int32)),
                jnp.asarray(keep_table), jnp.float32(sel_threshold),
                jnp.float32(sel_scale), jnp.float32(sel_min_count),
                jnp.float32(sel_rows_per_uid), k_sel))
        # The streamed selection seam: populated partitions in vs kept
        # partitions out, onto the privacy audit record.
        je._record_selection_audit(config.selection,
                                   int((nseg > 0).sum()),
                                   int(keep.sum()), "streamed")
    stats = {"n_batches": n_batches, "chunk_rows": chunk,
             "fx_bits": fx_bits, "max_batch_rows": max_rows,
             "mesh_devices": n_dev,
             "fold_wait_s": t_device + t_fold, **overlap}
    if ckpt_store is not None:
        stats["resumed_from_batch"] = start_batch
        stats["checkpoint_saves"] = n_saves
    stats["mesh_reshards"] = len(reshard_history)
    if reshard_history:
        stats["reshard_history"] = list(reshard_history)
    if adopt is not None:
        stats["elastic_adopted_n_dev"] = int(adopt["n_dev"])

    if config.percentiles:
        # Pass B: walk the mid histogram's levels, then re-stream the
        # batches to count the chosen subtrees' leaves, then finish.
        # Node noise is keyed exactly like the single-batch kernel
        # (k_tree = fold_in(k_noise, 0x7ee) on the (pk, node) ids), so
        # with non-binding caps a streamed run matches the single-batch
        # percentile values for the same seed, up to f32 tie-breaking.
        # The histograms accumulate across chunks in device int32:
        # a partition with >= 2^31 kept rows would wrap a bucket, so
        # guard on the exact host-side per-partition counts.
        if int(acc["count"].max(initial=0)) >= int(
                knob_vec["tree_rows_cap"]):
            raise NotImplementedError(
                "streamed percentiles: a partition holds >= 2^31 kept "
                "rows — beyond the int32 tree-histogram capacity")
        # lint: disable=rng-purity(tree key: constant fold of the noise stream)
        k_tree = jax.random.fold_in(k_noise, 0x7ee)
        scale = jnp.float32(np.asarray(scales)[-1])
        with tr.span("walk.top", cat="walk"), \
                obs.device_annotation("pdp.walk_top"):
            lo, hi, target, leaf_lo, done = _walk_top_kernel(
                config, P_pad, mid_acc, k_tree, scale)
        # The walk state is tiny ([P, Q]); host-fetch it once so the
        # planner slices plain numpy tiles (and on a mesh the sharded
        # pass-B kernel's in_specs stay independent of what sharding
        # GSPMD chose for the top walk's outputs).
        lo, hi, target, leaf_lo, done = (
            np.asarray(lo), np.asarray(hi), np.asarray(target),
            np.asarray(leaf_lo), np.asarray(done))
        sub_start = leaf_lo
        # Batch sources per sweep: the device-cached prefix re-reads
        # from HBM (same (b, arrays) tuples -> identical kernel inputs,
        # zero link traffic); past it — overflow suffix (hybrid) or the
        # whole stream (reship) — batches re-ship from host through the
        # rotating StagingRing (fresh-copy retention is only needed
        # while FEEDING the cache, which pass B never does).
        prefix = cache or []
        complete = cache is not None and not cache_frozen
        stats["pass_b_source"] = ("device_cache" if complete
                                  else "hybrid" if prefix else "reship")
        Q = len(config.percentiles)
        vals = np.empty((P_pad, Q), np.float32)

        def run_sweep(consume):
            """ONE traversal of the batch stream, feeding every batch
            to ``consume(item, ring)`` — the single pass-B stream
            source (the ``nostager`` lint pins restreaming to this
            planner-driven loop, so per-tile restreaming cannot quietly
            come back)."""
            if prefix:
                obs.inc("stream.pass_b_cache_hit_batches", len(prefix))
            for item in prefix:
                consume(item, None)
            if complete:
                return
            obs.inc("stream.pass_b_reship_rounds")
            ring_b = ingest.StagingRing(2)
            if use_executor:
                # Overlapped re-ship: stage batch b+1 on the stager
                # thread while the device counts batch b's subtree
                # leaves (no folds in pass B — accumulation stays on
                # device, so only the stager is needed).
                with ingest.BackgroundStager(
                        lambda cancelled: batches(
                            cache_upto, cancelled, ring=ring_b,
                            track_reship=True),
                        depth=1, name="stager-b") as stager_b:
                    for item in stager_b.items():
                        consume(item, ring_b)
            else:
                for item in batches(cache_upto, ring=ring_b,
                                    track_reship=True):
                    consume(item, ring_b)

        # One stream sweep per PACK of (quantile-group, partition-
        # block) tiles: every tile in the sweep accumulates its
        # [Pb, Qc, span] block from the same batch pass, then the
        # bottom walk runs per tile off the packed result —
        # bit-identical to the per-tile loop by construction (node
        # noise is a pure function of the global (partition, node id),
        # and the per-tile histograms are the same integers).
        single_full = not plan.chunked
        for sweep in plan.sweeps:
            q0_s, qn, p0_s = sweep[0]
            Pb = min(plan.p_blk, P_pad - p0_s)
            with tr.span("ingest.pass_b_sweep", cat="ingest",
                         tiles=len(sweep), q0=q0_s, p0=p0_s):
                if single_full:
                    ss_dev = jnp.asarray(sub_start)
                    p_offs = None
                else:
                    ss_dev = jnp.asarray(np.stack(
                        [sub_start[p0:p0 + Pb, q0:q0 + qn]
                         for q0, _, p0 in sweep]))
                    p_offs = jnp.asarray(
                        np.asarray([p0 for _, _, p0 in sweep],
                                   np.int32))
                sub_cell = [None]

                # A pallas request on the un-chunked (single-full)
                # branch routes through the multi-tile kernels as a
                # T=1 pack — per tile the multi kernel IS the single
                # kernel's math, so the values are bit-identical, and
                # the request is either actually served by the Pallas
                # binner or visibly degraded with a kernel.fallback
                # event (the single-tile kernels have no dispatch
                # point, which would make "pallas requested, xla ran"
                # silent — the one thing the knob must never be).
                as_multi = (not single_full
                            or kernel_backend == "pallas")

                def consume(item, ring_b, ss_dev=ss_dev,
                            p_offs=p_offs, Pb=Pb):
                    b, planes, values_d, nv, n_pid_planes = item
                    # Injectable kill point for the pass-B drain tests
                    # (pass A re-uses the plain chunk indices, so a
                    # pass-A fault could never land here).
                    faults.check_pass_b_chunk(b)
                    faults.check_device_loss()
                    if sup is not None:
                        sup.gate()
                    # lint: disable=rng-purity(per-batch bound key: fold of the batch index)
                    kb = jax.random.fold_in(k_bound, b)
                    if single_full and as_multi:
                        ss_m = ss_dev[None]
                        p_offs_m = jnp.zeros(1, jnp.int32)
                    else:
                        ss_m, p_offs_m = ss_dev, p_offs
                    with obs.device_annotation("pdp.stream_pass_b"):
                        if not as_multi and mesh is None:
                            sub = _pct_sub_kernel(
                                config, P_pad, planes, values_d, nv,
                                kb, fx_bits,
                                n_pid_planes=n_pid_planes,
                                sub_start=ss_dev,
                                p_offset=jnp.int32(0), n_block=P_pad)
                        elif not as_multi:
                            sub = _sharded_pct_sub_kernel(
                                config, P_pad, mesh, planes, values_d,
                                nv, kb, fx_bits,
                                n_pid_planes=n_pid_planes,
                                sub_start=ss_dev,
                                p_offset=jnp.int32(0), n_block=P_pad)
                        elif mesh is None:
                            sub = _pct_multi_sub_kernel(
                                config, P_pad, planes, values_d, nv,
                                kb, fx_bits,
                                n_pid_planes=n_pid_planes,
                                sub_starts=ss_m, p_offsets=p_offs_m,
                                n_block=Pb,
                                kernel_backend=kernel_backend)
                        else:
                            sub = _sharded_pct_multi_sub_kernel(
                                config, P_pad, mesh, planes, values_d,
                                nv, kb, fx_bits,
                                n_pid_planes=n_pid_planes,
                                sub_starts=ss_m, p_offsets=p_offs_m,
                                n_block=Pb,
                                kernel_backend=kernel_backend)
                        if single_full and as_multi:
                            # Back to the single-full [Pb, Qc, span]
                            # shape the walk consumes.
                            sub = sub[0]
                    sub_cell[0] = (sub if sub_cell[0] is None
                                   else sub_cell[0] + sub)
                    if ring_b is not None:
                        # A one-element fetch of this batch's output
                        # proves its kernel (and so its input
                        # transfer) completed before the staging slot
                        # is reused — the pass-B analogue of the
                        # pass-A fold fetch retiring the slot.
                        np.asarray(sub[(0,) * sub.ndim])
                        ring_b.retire()

                run_sweep(consume)
                sub_acc = sub_cell[0]
                for ti, (q0, _, p0) in enumerate(sweep):
                    psl = slice(p0, p0 + Pb)
                    qsl = slice(q0, q0 + qn)
                    with tr.span("walk.bottom", cat="walk", p0=p0,
                                 q0=q0), \
                            obs.device_annotation("pdp.walk_bottom"):
                        vals_g = _walk_bottom_kernel(
                            config, Pb,
                            sub_acc if single_full else sub_acc[ti],
                            ss_dev if single_full else ss_dev[ti],
                            lo[psl, qsl], hi[psl, qsl],
                            target[psl, qsl], leaf_lo[psl, qsl],
                            done[psl, qsl], k_tree, scale,
                            jnp.int32(p0))
                        vals[psl, qsl] = np.asarray(vals_g)
                obs.inc("stream.pass_b_stream_sweeps")
                obs.inc("stream.pass_b_tiles", len(sweep))
        stats["pass_b_rounds"] = plan.n_sweeps
        stats["pass_b_sweeps"] = plan.n_sweeps
        # Pass-B wall seconds (sweep spans), beside the pass-A
        # breakdown in the request's timings.
        stats["pass_b_sweep_s"] = tr.total("ingest.pass_b_sweep")
        stats["pass_b_tiles"] = plan.n_tiles
        stats["pass_b_tiles_per_sweep"] = plan.tiles_per_sweep
        stats["pass_b_cached_batches"] = len(prefix)
        stats["pass_b_reshipped_bytes"] = reship_bytes[0]
        # The cross-quantile monotone step runs ONCE over the full
        # list (chunked walks must compose to the single-walk result).
        quantiles = np.asarray([p / 100.0 for p in config.percentiles],
                               np.float32)
        stats["percentile_values"] = np.asarray(
            je._monotone_in_q(jnp.asarray(vals), quantiles))

    # Includes pass-B restaging (the stage spans keep accumulating
    # through the re-ship rounds) — the same window the former
    # accumulator covered.
    stats["stage_s"] = tr.total("ingest.stage")
    if ckpt_store is not None:
        # The run released its outputs: the checkpoint must not survive
        # (resuming a FINISHED run into a fresh aggregation would skip
        # every batch and re-release — clear it so the next run with
        # this path starts clean).
        ckpt_store.clear()
    return keep, part64, stats
