"""The fused TPU aggregation path.

This is the performance core of the framework: the entire
``DPEngine.aggregate`` dataflow (reference call stack §3.1 of SURVEY.md —
extract → bound contributions → combine per key → select partitions →
noise) compiled into ONE XLA program over integer-encoded arrays:

    host:   extract + integer-encode (pid, pk, value); calibrate noise
    device: one sort by (pid, hash(pid, pk), rand), carrying pk and the
            values as payload                     [shuffle 1+2 fused]
            → segment boundaries per (pid, pk)
            → linf bound  = rank-in-segment < max_contributions_per_partition
            → per-segment accumulators (segment_sum)    [create_accumulator]
            → L0 bound    = random rank of segment within pid < l0
            → per-pk accumulators (segment_sum)         [merge/combine]
            → batched partition selection over the pk axis
            → batched percentile tree walk (when requested)
    host:   float64 scalar release via the shared dp_computations
            mechanisms (float32 device noise would quantize to a large
            aggregate's ULP grid); decode pk vocabulary, wrap
            MetricsTuple rows

Two-phase budget protocol: noise scales, selection tables/thresholds and
the PRNG key are *runtime inputs* to the compiled function — budgets are
computed after graph construction and never trigger recompilation. Shapes
are padded to powers of two so repeated runs with similar sizes reuse the
compile cache.

Supported in the fused plane: COUNT, PRIVACY_ID_COUNT, SUM (both clipping
modes), MEAN, VARIANCE, VECTOR_SUM, PERCENTILE, public and private
partitions, ``contribution_bounds_already_enforced``.

PERCENTILE never materializes dense per-partition trees (height-4 ×
branching-16 = 69,904 nodes per partition would be O(P·nodes) HBM): the
quantile walk runs level-by-level over ALL partitions at once, counting
each level's child buckets with one segment_sum over the rows, and node
noise is a pure function of (partition, node index) — one batched
counter-based threefry draw per level (``ops/counter_rng.py``), the
stateless equivalent of the host tree's noisy-count memoization
(reference ``pipeline_dp/combiners.py:402-476``; host twin
``ops/quantile_tree.py``). When the bottom walk's [P, Q, span] subtree
block exceeds ``_SUBHIST_BYTE_CAP``, the partition axis chunks into
blocks walked one at a time — bit-identical to the unchunked walk,
because the noise is keyed by GLOBAL (partition, node).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from pipelinedp_tpu import dp_computations
from pipelinedp_tpu.aggregate_params import (AggregateParams, NoiseKind,
                                             NormKind,
                                             PartitionSelectionStrategy)
from pipelinedp_tpu.combiners import _create_named_tuple_instance
from pipelinedp_tpu.obs.costs import instrumented_jit, scope, scoped
from pipelinedp_tpu.ops import partition_selection as ps_ops
from pipelinedp_tpu.ops import quantile_tree as quantile_tree_ops
from pipelinedp_tpu.ops import segment as seg_ops


def _pad_rows(n: int) -> int:
    """Row-axis padding: the next multiple of 8192 (a whole number of
    (8, 128) f32 tiles, and one shared compile shape for small tests).
    Rows used to pad to a power of two, which wastes up to 2x of every
    row-space op (sort, scatters, elementwise) — a 10M-row pipeline ran
    all its row passes at 2^24 = 16.8M rows; measured on v5e, sorts and
    scatters at a non-power-of-two length run at full speed, so the
    tight padding is a ~1.4-1.7x cut of the whole row plane. The
    partition axis keeps power-of-two padding (``_pad_pow2``): selection
    bit-parity on meshes relies on it."""
    return max(8192, -(-n // 8192) * 8192)


def _pad_pow2(n: int, minimum: int = 8) -> int:
    return max(minimum, 1 << (n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """Static (compile-time) configuration derived from AggregateParams."""
    metrics: Tuple[str, ...]  # subset of the fused metric names, in order
    noise_kind: NoiseKind
    linf: Optional[int]
    l0: int
    per_partition_bounds: bool  # SUM clips the per-(pid,pk) sum, not rows
    min_value: Optional[float]
    max_value: Optional[float]
    min_sum_per_partition: Optional[float]
    max_sum_per_partition: Optional[float]
    vector_size: Optional[int]
    vector_norm_kind: Optional[NormKind]
    vector_max_norm: Optional[float]
    selection: Optional[PartitionSelectionStrategy]  # None = public
    bounds_already_enforced: bool
    percentiles: Tuple[float, ...] = ()  # PERCENTILE(p) parameters, in order
    # Total-cap bounding: M rows per privacy unit across ALL partitions
    # (l0/linf are None in this mode).
    max_contributions: Optional[int] = None
    # VECTOR_SUM accumulator discipline: "f32" (plain float32
    # segment_sum — the historical path) or "fx" (24-bit fixed-point
    # coordinate lanes, exact). Resolved from the vector_accumulator
    # knob in from_params; a FusedConfig built elsewhere keeps the
    # historical default. Riding on the config (already a static jit
    # argument on every hot path) means a knob flip re-traces.
    vector_accumulator: str = "f32"
    # Pinned D tile for the wide-D vector segment-sum kernel (the
    # segsum_wide_d_block knob; 0 = the envelope's choice).
    wide_d_block: int = 0

    @property
    def selection_l0(self) -> int:
        """L0 for partition selection: a unit touches at most this many
        partitions in either bounding mode."""
        return (self.max_contributions if self.max_contributions is not None
                else self.l0)

    @property
    def needs_values(self) -> bool:
        """Whether any requested metric reads the value column (kept next
        to FUSABLE_METRICS so new metrics update both in one place)."""
        return bool(set(self.metrics) & _VALUE_METRICS
                    ) or self.per_partition_bounds

    @staticmethod
    def from_params(params: AggregateParams,
                    public: bool) -> "FusedConfig":
        names = []
        percentiles = []
        for m in params.metrics:
            if m.is_percentile:
                percentiles.append(float(m.parameter))
                if "PERCENTILE" not in names:
                    names.append("PERCENTILE")
            else:
                names.append(m.name)
        vector_accumulator = "f32"
        wide_d_block = 0
        if params.vector_size:
            from pipelinedp_tpu import plan as plan_mod
            vector_accumulator = str(
                plan_mod.knob_value("vector_accumulator"))
            wide_d_block = int(
                plan_mod.knob_value("segsum_wide_d_block"))
        return FusedConfig(
            metrics=tuple(names),
            percentiles=tuple(percentiles),
            noise_kind=params.noise_kind,
            linf=params.max_contributions_per_partition,
            l0=params.max_partitions_contributed,
            max_contributions=params.max_contributions,
            per_partition_bounds=params.bounds_per_partition_are_set,
            min_value=params.min_value,
            max_value=params.max_value,
            min_sum_per_partition=params.min_sum_per_partition,
            max_sum_per_partition=params.max_sum_per_partition,
            vector_size=params.vector_size,
            vector_norm_kind=params.vector_norm_kind,
            vector_max_norm=params.vector_max_norm,
            selection=(None if public else
                       params.partition_selection_strategy),
            bounds_already_enforced=(
                params.contribution_bounds_already_enforced),
            vector_accumulator=vector_accumulator,
            wide_d_block=wide_d_block,
        )


FUSABLE_METRICS = {"COUNT", "PRIVACY_ID_COUNT", "SUM", "MEAN", "VARIANCE",
                   "VECTOR_SUM", "PERCENTILE"}
# The fused metrics that read the value column (the rest only count rows
# or segments, so their kernels run on an all-zeros values array).
_VALUE_METRICS = {"SUM", "MEAN", "VARIANCE", "VECTOR_SUM", "PERCENTILE"}


def params_are_fusable(params: AggregateParams) -> bool:
    if params.custom_combiners:
        return False
    # (Total-cap ``max_contributions`` bounding is fused too, including
    # PERCENTILE: the engine rejects only VECTOR_SUM with it, and in
    # bounds-already-enforced mode no bounding runs anywhere.)
    for m in params.metrics:
        if m.is_percentile:
            # The quantile walk needs real tree bounds. min_value may be
            # None here (sum-per-partition bounds mode); a zero-width
            # range never arrives (AggregateParams rejects it for
            # percentiles at construction). A pathologically tiny (but
            # valid) range falls through to the generic host path: the
            # fused leaf arithmetic folds n_leaves/range into ONE f32
            # constant (see ``_qrows`` for why), which overflows for
            # range < ~1.9e-34 — the host tree computes in f64 and
            # handles those ranges fine.
            if (params.min_value is None or
                    not params.min_value < params.max_value):
                return False
            n_leaves = (quantile_tree_ops.DEFAULT_BRANCHING_FACTOR **
                        quantile_tree_ops.DEFAULT_TREE_HEIGHT)
            inv = n_leaves / (float(params.max_value) -
                              float(params.min_value))
            if inv > float(np.finfo(np.float32).max):
                return False
        elif m.name not in FUSABLE_METRICS:
            return False
    return True


# ---------------------------------------------------------------------------
# Host-side encoding
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ArrayDataset:
    """Columnar input: the zero-copy fast path into the fused plane.

    When the caller already has NumPy columns (a Parquet/CSV load, a
    feature pipeline), passing them as an ArrayDataset skips the
    per-row Python extractor loop entirely — encoding becomes a
    vectorized ``np.unique``. ``values`` may be [N] scalars or [N, D]
    vectors. ``DataExtractors`` are not needed (pass an empty one).

    Aggregating the same dataset repeatedly (multiple metrics, parameter
    tuning, utility-analysis sweeps) reuses the integer-encoded columns
    AND their on-device placement: the slow host<->device link is paid
    once, not per aggregation. The columns are therefore treated as
    immutable once the first aggregation runs — call
    ``invalidate_cache()`` after mutating them in place.
    """
    privacy_ids: Optional[np.ndarray]
    partition_keys: np.ndarray
    values: Optional[np.ndarray] = None

    def __len__(self):
        return len(self.partition_keys)

    def invalidate_cache(self) -> None:
        """Drops cached encodings/device buffers (after in-place edits)."""
        self.__dict__.pop("_encode_cache", None)

    def _cached_encode(self, key, build):
        cache = self.__dict__.setdefault("_encode_cache", {})
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def to_rows(self):
        """Row-tuple view for the generic (non-fused) backends."""
        n = len(self.partition_keys)
        pids = (self.privacy_ids if self.privacy_ids is not None else
                np.zeros(n, np.int64))
        vals = (self.values if self.values is not None else
                np.zeros(n, np.float64))
        return list(zip(pids.tolist(), self.partition_keys.tolist(),
                        vals.tolist()))


@dataclasses.dataclass
class EncodedData:
    """Integer-encoded rows + the pk vocabulary for decoding."""
    pid: np.ndarray  # int32 [N]
    pk: np.ndarray  # int32 [N]
    values: np.ndarray  # f32 [N] or [N, D]
    pk_vocab: List[Any]  # dense pk index -> original key
    n_rows: int


def _int_factorize(arr: np.ndarray):
    """Sort-free factorization for integer keys with a manageable range:
    O(n + range) via a presence table instead of np.unique's O(n log n)
    sort. Returns (uniq values ascending, int32 inverse) or None when the
    range is too wide to be worth a table."""
    if arr.dtype.kind not in "iu" or arr.size == 0:
        return None
    mn = int(arr.min())
    mx = int(arr.max())
    span = mx - mn + 1
    if span > max(4 * arr.size, 1 << 22):
        return None
    # Offsets must not be computed in a dtype that can overflow: narrow
    # signed dtypes wrap on `arr - mn`, and uint64 values above int64 max
    # wrap on the cast. Unsigned subtraction is exact (arr >= mn), signed
    # fits int64 by construction.
    if arr.dtype.kind == "u":
        offs = (arr - np.asarray(mn, arr.dtype)).astype(np.int64)
    else:
        offs = arr.astype(np.int64) - mn
    present = np.zeros(span, dtype=bool)
    present[offs] = True
    uniq_off = np.flatnonzero(present)
    lookup = np.empty(span, dtype=np.int32)
    lookup[uniq_off] = np.arange(len(uniq_off), dtype=np.int32)
    uniq = uniq_off.astype(arr.dtype) + np.asarray(mn, arr.dtype)
    return uniq, lookup[offs]


def _unique_inverse(arr: np.ndarray):
    """``np.unique(arr, return_inverse=True)`` with the native hash
    factorizer (``native/encode.cc``: O(N + U log U) vs the full O(N log N)
    sort) when the toolchain can build it; inverse always int32."""
    if arr.dtype.kind in "iu" and arr.dtype.itemsize <= 8:
        try:
            from pipelinedp_tpu import native
            if native.encode_available():
                # factorize_i64 itself rejects uint64 values that would
                # wrap; that ValueError lands in the fallback below.
                uniq, inv = native.factorize_i64(arr)
                return uniq.astype(arr.dtype), inv
        except Exception:  # never let the fast path break ingest
            pass
    uniq, inv = np.unique(arr, return_inverse=True)
    return uniq, inv.astype(np.int32)


def _pid_ids(pid_arr: np.ndarray) -> np.ndarray:
    """int32 ids for privacy units: any injective mapping works (the kernel
    only groups by equality), so in-range integer ids pass through without
    the np.unique sort. PAD_ID (int32 max) is reserved for padding rows."""
    if (pid_arr.dtype.kind in "iu" and pid_arr.size and
            pid_arr.min() >= 0 and pid_arr.max() < np.iinfo(np.int32).max):
        return pid_arr.astype(np.int32)
    fac = _int_factorize(pid_arr)
    if fac is not None:
        return fac[1]
    return _unique_inverse(pid_arr)[1]


def array_dataset_to_rows(ds: ArrayDataset, data_extractors,
                          require_pid: bool = True):
    """Columnar input on a generic (row-based) backend: expand to row
    tuples with positional extractors — shared by ``DPEngine._aggregate``
    and the histogram graph. Caller-supplied extractors are honored when
    a partition extractor is set."""
    import operator

    from pipelinedp_tpu.dp_engine import DataExtractors

    if ds.privacy_ids is None and require_pid:
        raise ValueError(
            "ArrayDataset.privacy_ids must be set unless "
            "contribution_bounds_already_enforced is True.")
    rows = ds.to_rows()
    if data_extractors.partition_extractor is None:
        data_extractors = DataExtractors(
            privacy_id_extractor=(None if not require_pid else
                                  operator.itemgetter(0)),
            partition_extractor=operator.itemgetter(1),
            value_extractor=operator.itemgetter(2))
    return rows, data_extractors


def pad_and_put(encoded: EncodedData, vector_size: Optional[int],
                with_values: bool = True):
    """One batched h2d transfer of the exact-size encoded columns; padding
    happens on device and the padding mask is derived from a scalar — the
    (slow, high-latency) host link moves only real rows in a single round
    trip. Id columns ship at their minimal byte width (the link runs at
    tens of MB/s, so bytes ARE wall time): uint16 when the ids fit,
    3xuint8 planes for ids in [2^16, 2^24) — dense-factorized vocabularies
    routinely land there — widened back to int32 on device.
    ``with_values=False`` skips the value column entirely (COUNT-style
    aggregations never read it). Returns (pid, pk, values, valid) padded
    to a power of two.

    The placed arrays are cached on the EncodedData: repeated
    aggregations of the same dataset (tuning sweeps, multi-metric
    pipelines) pay the host-to-device transfer once. Id columns and the
    value column cache INDEPENDENTLY — a COUNT pass followed by a SUM pass
    ships the ids once and then only adds the value transfer (still one
    batched device_put per call for whatever is missing)."""
    n = encoded.n_rows
    n_pad = _pad_rows(n)
    cache = encoded.__dict__.setdefault("_device_cache", {})
    vals_key = ("values", vector_size)
    need_ids = "ids" not in cache
    need_vals = with_values and vals_key not in cache

    if need_ids or need_vals:
        host = []
        pid_planes = pk_planes = ()
        if need_ids:
            pid_planes = _narrow_ids(encoded.pid)
            pk_planes = _narrow_ids(encoded.pk)
            host += list(pid_planes) + list(pk_planes)
        if need_vals:
            host.append(encoded.values)
        dev = jax.device_put(tuple(host))
        if need_ids:
            n_pid = len(pid_planes)
            pid = jnp.zeros(n_pad, jnp.int32).at[:n].set(
                _widen_ids(dev[:n_pid]))
            pk = jnp.zeros(n_pad, jnp.int32).at[:n].set(
                _widen_ids(dev[n_pid:n_pid + len(pk_planes)]))
            valid = jnp.arange(n_pad) < n
            cache["ids"] = (pid, pk, valid)
        if need_vals:
            shape = (n_pad, vector_size) if vector_size else (n_pad,)
            cache[vals_key] = jnp.zeros(shape, jnp.float32).at[:n].set(
                dev[-1])

    pid, pk, valid = cache["ids"]
    if with_values:
        values = cache[vals_key]
    else:
        zeros_key = ("zeros", vector_size)
        if zeros_key not in cache:
            shape = (n_pad, vector_size) if vector_size else (n_pad,)
            cache[zeros_key] = jnp.zeros(shape, jnp.float32)
        values = cache[zeros_key]
    return pid, pk, values, valid


def _plane_spec(max_id: int) -> str:
    """Byte-width tier for an id column: one policy for the single-batch
    and streaming ship paths (streaming decides ONCE from the global max
    so every batch shares a jit signature)."""
    if max_id < (1 << 16):
        return "u16"
    if max_id < (1 << 24):
        return "u8x3"
    return "i32"


def _narrow_ids(arr, spec: Optional[str] = None):
    """Minimal-byte-width host planes of a non-negative id column
    (encode() guarantees non-negative ids). ``spec`` forces a tier
    decided elsewhere (streaming's global-max decision)."""
    if spec is None:
        spec = _plane_spec(int(arr.max()) if arr.size else 0)
    if spec == "u16":
        return (arr.astype(np.uint16),)
    if spec == "u8x3":
        a32 = arr.astype(np.uint32)
        return (a32.astype(np.uint8), (a32 >> 8).astype(np.uint8),
                (a32 >> 16).astype(np.uint8))
    return (arr,)


def _widen_ids(planes) -> jnp.ndarray:
    if len(planes) == 1:
        return planes[0].astype(jnp.int32)
    b0, b1, b2 = (p.astype(jnp.int32) for p in planes)
    return b0 | (b1 << 8) | (b2 << 16)


def _encode_arrays(ds: ArrayDataset, vector_size: Optional[int],
                   public_partitions: Optional[Sequence],
                   require_pid: bool = True) -> EncodedData:
    """Vectorized encode of columnar input (no per-row Python)."""
    pk_arr = np.asarray(ds.partition_keys)
    n = pk_arr.shape[0]
    if ds.privacy_ids is None and require_pid:
        raise ValueError(
            "ArrayDataset.privacy_ids must be set unless "
            "contribution_bounds_already_enforced is True — without them "
            "all rows would be attributed to one privacy unit and almost "
            "all data silently dropped by contribution bounding.")
    pid_arr = (np.asarray(ds.privacy_ids) if ds.privacy_ids is not None
               else np.zeros(n, np.int64))
    values = (np.asarray(ds.values, dtype=np.float32)
              if ds.values is not None else np.zeros(n, np.float32))
    if public_partitions is not None:
        vocab = np.asarray(list(public_partitions))
        sorter = np.argsort(vocab, kind="stable")
        pos = np.searchsorted(vocab, pk_arr, sorter=sorter)
        pos = np.clip(pos, 0, len(vocab) - 1)
        candidate = sorter[pos]
        mask = vocab[candidate] == pk_arr
        pk_idx = candidate[mask].astype(np.int32)
        pid_arr = pid_arr[mask]
        values = values[mask]
        pk_vocab = list(vocab.tolist())
    else:
        fac = _int_factorize(pk_arr)
        if fac is not None:
            uniq, pk_idx = fac
        else:
            uniq, pk_idx = _unique_inverse(pk_arr)
        pk_vocab = list(uniq.tolist())
    pid_idx = _pid_ids(pid_arr)
    if vector_size:
        values = values.reshape(len(values), vector_size)
    return EncodedData(pid=pid_idx, pk=pk_idx,
                       values=values, pk_vocab=pk_vocab,
                       n_rows=len(pk_idx))


def _itemgetter_index(fn) -> Optional[int]:
    """The index a plain single-item ``operator.itemgetter`` selects, or
    None for any other callable. Resolved by probing with a recording
    object — exact-type-gated, so only true positional selectors (which
    can do nothing but index) qualify."""
    import operator
    if type(fn) is not operator.itemgetter:
        return None

    class _Probe:
        def __init__(self):
            self.indices = []

        def __getitem__(self, i):
            self.indices.append(i)
            return i

    probe = _Probe()
    try:
        result = fn(probe)
    except Exception:
        return None
    if len(probe.indices) == 1 and result == probe.indices[0]:
        return probe.indices[0]
    return None


def _rows_to_arrays(rows, data_extractors,
                    require_pid: bool) -> Optional[ArrayDataset]:
    """The vectorized extractor bridge: when every extractor is a plain
    ``operator.itemgetter`` over tuple rows, ingest transposes the rows
    once at C level (``zip(*rows)``) instead of paying three Python
    extractor calls per row, and the columns take the same vectorized
    encode as an ArrayDataset. Returns None when the rows/extractors
    don't qualify (arbitrary callables fall back to the row loop)."""
    if not isinstance(rows, (list, tuple)) or not rows:
        return None
    if not isinstance(rows[0], (tuple, list)):
        return None
    i_pid = _itemgetter_index(data_extractors.privacy_id_extractor)
    i_pk = _itemgetter_index(data_extractors.partition_extractor)
    i_val = _itemgetter_index(data_extractors.value_extractor)
    if i_pk is None:
        return None
    if require_pid and i_pid is None:
        return None
    if (data_extractors.value_extractor is not None and i_val is None):
        return None
    # Per-column extraction: a plain `[r[i] for r in rows]` comprehension
    # benches ~3.5x faster than both np.asarray(rows) and zip(*rows) for
    # multi-million-row lists (one bytecode-level loop per column, no
    # intermediate 2-D object array). Dtypes are probed on a small prefix
    # first so unsupported columns (string keys) bail out without paying
    # a full O(n) pass before the row-loop fallback.
    def col(i, probe=0):
        if i is None:
            return None
        try:
            sample = rows[:256] if probe else rows
            arr = np.asarray([r[i] for r in sample])
        except (IndexError, ValueError, TypeError):
            return None
        return None if arr.dtype == object else arr

    # Id columns must be numeric: np.unique on large string columns is
    # slower than the dict-based row loop, so strings keep that path.
    def usable(i, need_1d):
        a = col(i, probe=1)
        return (a is not None and a.dtype.kind in "iuf" and
                (not need_1d or a.ndim == 1))

    if not usable(i_pk, True):
        return None
    if i_pid is not None and not usable(i_pid, True):
        return None
    if i_val is not None and not usable(i_val, False):
        return None

    def full(i, need_1d):
        a = col(i)
        if (a is None or a.dtype.kind not in "iuf" or
                (need_1d and a.ndim != 1)):
            return None
        return a

    pk_arr = full(i_pk, True)
    if pk_arr is None:
        return None
    pid_arr = None
    if i_pid is not None:
        pid_arr = full(i_pid, True)
        if pid_arr is None:
            return None
    val_arr = None
    if i_val is not None:
        val_arr = full(i_val, False)
        if val_arr is None:
            return None
    return ArrayDataset(privacy_ids=pid_arr, partition_keys=pk_arr,
                        values=val_arr)


def encode(rows, data_extractors, vector_size: Optional[int],
           public_partitions: Optional[Sequence] = None,
           require_pid: bool = True) -> EncodedData:
    """Extract + integer-encode on host. With public partitions the pk
    vocabulary IS the public list — non-public rows are dropped and missing
    public partitions appear as all-zero accumulator rows for free."""
    if isinstance(rows, ArrayDataset):
        if public_partitions is None:
            # Cacheable: the encode is a pure function of the columns.
            # (Public-partition encodes depend on the passed list and are
            # not cached — the list has no cheap identity.)
            return rows._cached_encode(
                ("encode", vector_size, require_pid),
                lambda: _encode_arrays(rows, vector_size, None, require_pid))
        return _encode_arrays(rows, vector_size, public_partitions,
                              require_pid)
    bridged = _rows_to_arrays(rows, data_extractors, require_pid)
    if bridged is not None:
        return _encode_arrays(bridged, vector_size, public_partitions,
                              require_pid)
    pids, pks, vals = [], [], []
    pid_ex = data_extractors.privacy_id_extractor
    pk_ex = data_extractors.partition_extractor
    val_ex = data_extractors.value_extractor
    if pid_ex is None and require_pid:
        raise ValueError(
            "privacy_id_extractor must be set unless "
            "contribution_bounds_already_enforced is True.")
    for row in rows:
        pids.append(pid_ex(row) if pid_ex else 0)
        pks.append(pk_ex(row))
        vals.append(val_ex(row) if val_ex else 0.0)

    if public_partitions is not None:
        pk_vocab = list(public_partitions)
        pk_index = {k: i for i, k in enumerate(pk_vocab)}
        keep = [i for i, k in enumerate(pks) if k in pk_index]
        pids = [pids[i] for i in keep]
        vals = [vals[i] for i in keep]
        pk_idx = np.fromiter((pk_index[pks[i]] for i in keep),
                             dtype=np.int32, count=len(keep))
    else:
        uniq = sorted(set(pks), key=repr)
        pk_index = {k: i for i, k in enumerate(uniq)}
        pk_vocab = uniq
        pk_idx = np.fromiter((pk_index[k] for k in pks), dtype=np.int32,
                             count=len(pks))

    uniq_pids = {p: i for i, p in enumerate(dict.fromkeys(pids))}
    pid_idx = np.fromiter((uniq_pids[p] for p in pids), dtype=np.int32,
                          count=len(pids))
    if vector_size:
        values = np.asarray(vals, dtype=np.float32).reshape(
            len(vals), vector_size)
    else:
        values = np.asarray(vals, dtype=np.float32)
    return EncodedData(pid=pid_idx, pk=pk_idx, values=values,
                       pk_vocab=pk_vocab, n_rows=len(pid_idx))


# ---------------------------------------------------------------------------
# The fused kernel
# ---------------------------------------------------------------------------


def _fused_kernel_body(config: FusedConfig, num_partitions: int, pid, pk,
                       values, valid, noise_scales, keep_table,
                       sel_threshold, sel_scale, sel_min_count,
                       sel_rows_per_uid, key, fx_bits: int,
                       kernel_backend: str):
    """The un-jitted aggregation body: shared verbatim by the solo
    kernel and the serve-fusion batched kernel (one vmapped request
    axis over this exact graph), so a fused request's arithmetic IS the
    solo request's arithmetic."""
    # Seeded entry seam: the ONE root split into the bounding /
    # selection / noise streams, pure in the caller's key.
    # lint: disable=rng-purity(root split seam, pure in caller's key)
    k_bound, k_sel, k_noise = jax.random.split(key, 3)
    part, part_nseg, qrows = _partials(config, num_partitions, pid, pk,
                                       values, valid, k_bound, fx_bits,
                                       kernel_backend=kernel_backend)
    return _selection_and_metrics(config, num_partitions, part, part_nseg,
                                  noise_scales, keep_table, sel_threshold,
                                  sel_scale, sel_min_count,
                                  sel_rows_per_uid, k_sel, k_noise,
                                  qrows=qrows)


@instrumented_jit(phase="engine", static_argnames=("config",
                                                   "num_partitions",
                                                   "fx_bits",
                                                   "kernel_backend"))
def fused_aggregate_kernel(config: FusedConfig, num_partitions: int, pid,
                           pk, values, valid, noise_scales, keep_table,
                           sel_threshold, sel_scale, sel_min_count,
                           sel_rows_per_uid, key, fx_bits: int = 7,
                           kernel_backend: str = "xla"):
    """One compiled program for the whole aggregation. See module docstring.

    Runtime inputs:
      pid, pk: int32[N] (padded); values: f32[N] or f32[N, D]; valid:
      bool[N] row mask; noise_scales: f32[0 or 1] — only the percentile
      tree's per-level scale (the scalar release runs on host, see
      _host_release); keep_table: f32[T] truncated-
      geometric keep probabilities (unused for thresholding strategies);
      sel_threshold/sel_scale: f32 scalars for thresholding strategies;
      key: PRNG key.
    """
    return _fused_kernel_body(config, num_partitions, pid, pk, values,
                              valid, noise_scales, keep_table,
                              sel_threshold, sel_scale, sel_min_count,
                              sel_rows_per_uid, key, fx_bits,
                              kernel_backend)


@instrumented_jit(phase="serve_fused", static_argnames=("config",
                                                        "num_partitions",
                                                        "fx_bits",
                                                        "kernel_backend"))
def fused_aggregate_batch_kernel(config: FusedConfig,
                                 num_partitions: int, pid, pk, values,
                                 valid, noise_scales, keep_table,
                                 sel_threshold, sel_scale, sel_min_count,
                                 sel_rows_per_uid, keys,
                                 fx_bits: int = 7,
                                 kernel_backend: str = "xla"):
    """One compiled program serving a whole BATCH of requests: every
    runtime input gains a leading request axis (``pid``: int32[B, N],
    ``keys``: [B] PRNG keys, scalar selection inputs become f32[B], ...)
    and the solo kernel body vmaps over it. Request b's slice computes
    bit-identically to a solo ``fused_aggregate_kernel`` call with the
    same inputs (PARITY row 35): the body is shared, per-request noise
    keys keep the streams pure (counter RNG is keyed by content), and
    the per-request ``valid`` row masks plus the padding-invariant
    tie-breaks (``counter_rng.row_bits``) guarantee bucket padding can
    never leak into released values. Dispatched ONLY from the blessed
    serve-fusion seam (``serve/fusion.py``; the ``fusion-masking``
    lint) — batch mode and the streaming planes never see it. The
    distinct program name keys the cost observatory's ``device_costs``
    signatures apart from solo programs, so roofline verdicts stay
    per-program."""
    def one(pid, pk, values, valid, scales, table, thr, s_scale,
            min_count, rows_per_uid, key):
        return _fused_kernel_body(config, num_partitions, pid, pk,
                                  values, valid, scales, table, thr,
                                  s_scale, min_count, rows_per_uid, key,
                                  fx_bits, kernel_backend)

    return jax.vmap(one)(pid, pk, values, valid, noise_scales,
                         keep_table, sel_threshold, sel_scale,
                         sel_min_count, sel_rows_per_uid, keys)


def _partials(config: FusedConfig, num_partitions: int, pid, pk, values,
              valid, key, fx_bits: int = 7,
              kernel_backend: str = "xla"):
    """Contribution bounding + per-pk accumulator partials. Shardable by
    privacy id: every pid's rows must live in one shard, pks may be
    spread — partials then combine across shards by plain addition
    (psum).

    Scatter-minimal design: on TPU a segment_sum/scatter over the row axis
    costs ~10x an elementwise op, so the kernel sorts ONCE by
    (pid, hash(pid, pk, salt), random) — pk itself is not a key: for a
    fixed pid the hash is injective in pk, so segments are contiguous
    already — with pk and rank-1 values riding the sort as payload (no
    gather applies its order), and then derives every per-segment
    quantity in row space
    with cumsum/cummax (runs are contiguous after the sort). The hash key
    makes the within-pid segment
    order a fresh uniform permutation per run and per pid, so "ordinal
    within pid < l0" IS the L0 cross-partition sample — in (l0, linf)
    mode no second sort and no per-segment scatter are needed; the only
    scatters are the final per-pk reductions (and, for per-partition
    -bound sums, one per-segment total). Total-cap mode
    (``max_contributions``) pays one extra sort + row-space scatter
    for its uniform per-pid row sample — see the branch below."""
    n = pid.shape[0]
    P = num_partitions

    if config.bounds_already_enforced:
        # No privacy ids: every row is its own "segment"; no sampling.
        row_keep = valid
        with scope("bound_caps"):
            pk_safe = jnp.where(valid, pk, 0)
            clipped = _clip_values(config, values)
            masked = jnp.where(_expand(row_keep, clipped), clipped, 0.0)
            if config.per_partition_bounds:
                # One row = one segment: the per-segment sum clip is a
                # row clip.
                masked = jnp.where(
                    row_keep,
                    jnp.clip(masked, config.min_sum_per_partition,
                             config.max_sum_per_partition), 0.0)
        qrows = (_qrows(config, pk_safe, values, row_keep)
                 if config.percentiles else None)
        part, _ = _reduce_per_pk(config, pk_safe, masked, row_keep, masked,
                                 P, fx_bits=fx_bits,
                                 kernel_backend=kernel_backend)
        # Without pids every row counts as its own privacy unit
        # (reference dp_engine.py:341-348 works off row counts).
        part_nseg = part["count"]
        return part, part_nseg, qrows

    from pipelinedp_tpu import obs
    from pipelinedp_tpu.ops import counter_rng

    # Blessed seam: tie-break/salt/sample bits for contribution
    # bounding, all derived from the bounding stream's key. Row-space
    # tie-breaks come from the counter generator keyed by ROW POSITION
    # (``counter_rng.row_bits``), not ``jax.random.bits`` — the
    # latter's counter pairing depends on the padded length, which
    # would couple the sampled contribution subsets to how far the row
    # axis is padded. Content-keyed bits make every released value a
    # pure function of (key, real rows): padding the same request to a
    # larger pow2 fusion bucket is bit-identical to its solo padding
    # (PARITY row 35, asserted in tests/test_fusion.py).
    with scope("bound_sort"):
        # lint: disable=rng-purity(bounding tie-break bits, keyed by k_bound)
        k_tie, k_salt, k_m = jax.random.split(key, 3)
        # lint: disable=rng-purity(per-run salt from the bounding stream)
        salt = jax.random.bits(k_salt, (), dtype=jnp.uint32)
        tiebreak = counter_rng.row_bits(k_tie, n)
        big_pid = jnp.where(valid, pid, seg_ops.PAD_ID)
        big_pk = jnp.where(valid, pk, seg_ops.PAD_ID)
        # Sampling priority of segment (pid, pk): an independent uniform
        # permutation of each pid's partitions (salted per run).
        hpk = seg_ops.fmix32(
            seg_ops.fmix32(big_pid.astype(jnp.uint32) ^ salt) ^
            big_pk.astype(jnp.uint32))
        # For fixed (pid, salt), pk -> hpk is injective (fmix32 is a
        # bijection on uint32 composed with an xor by a per-pid
        # constant), so (pid, hpk) already identifies the (pid, pk)
        # segment — pk itself is redundant as a sort key, cutting one
        # operand from the sort network.
        #
        # The columns bounding reads ride the sort as payload operands:
        # the network moves them with the keys, where a gather by the
        # sorted order would move one element at a time (~8x the sort
        # on a TPU v5e). Rank-1 values ride too; VECTOR_SUM's [N, D]
        # values cannot (``lax.sort``'s operands share one shape), so
        # they keep a gather by the order, as the total-cap branch's
        # keep bits do. The row index is the last key: rows tying on the
        # three keys (one segment, equal 32 tie bits) keep their row
        # order, as in a stable sort, which is what makes the released
        # values independent of how far the rows are padded. With every
        # key tuple distinct the sort need not be stable, and unstable
        # it compiles in two thirds of the stable sort's time.
        ride_values = config.needs_values and values.ndim == 1
        gather_values = config.needs_values and not ride_values
        payload = (big_pk, values) if ride_values else (big_pk,)
        out = jax.lax.sort(
            (big_pid, hpk, tiebreak, jax.lax.iota(jnp.int32, n), *payload),
            num_keys=4, is_stable=False)
        spid, sort_idx, spk = out[0], out[3], out[4]
        svalues = out[5] if ride_values else values
    obs.inc("bound.payload_sort")
    if gather_values or config.max_contributions is not None:
        obs.inc("bound.gathers")
    if gather_values:
        with scope("bound_permute"):
            svalues = values[sort_idx]
    with scope("bound_caps"):
        idx = jnp.arange(n)
        # Valid rows sort before padding (PAD_ID keys): no gather needed.
        svalid = idx < jnp.sum(valid.astype(jnp.int32))

        new_pid = (idx == 0) | (spid != jnp.roll(spid, 1))
        new_seg = new_pid | (spk != jnp.roll(spk, 1))
        if config.max_contributions is not None:
            # Total-cap mode: a uniform without-replacement sample of M
            # rows per privacy unit, across all its partitions (the fused
            # twin of SamplingPerPrivacyIdContributionBounder). The
            # sample must be uniform over the unit's ROWS, not follow the
            # hpk segment order, so rank rows by an independent random
            # key in a second sort and carry the keep bits back through
            # the permutations.
            with scope("bound_sort"):
                tie_m = counter_rng.row_bits(k_m, n)
                mpid, _, order_m = jax.lax.sort((big_pid, tie_m, idx),
                                                num_keys=3, is_stable=False)
            new_pid_m = (idx == 0) | (mpid != jnp.roll(mpid, 1))
            keep_sorted = (seg_ops.rank_in_run(new_pid_m) <
                           config.max_contributions)
            with scope("bound_permute"):
                keep_m = jnp.zeros(n, bool).at[order_m].set(keep_sorted)
                keep_row = svalid & keep_m[sort_idx]
            # First KEPT row of each segment marks the (pid, pk) pair as
            # contributing; fully-sampled-away segments must not count
            # toward the privacy-id count or selection.
            wk = jnp.cumsum(keep_row.astype(jnp.int32))
            seg_start = seg_ops.run_starts(new_seg)
            kept_before_seg = wk[seg_start] - keep_row[seg_start]
            seg_marker = keep_row & (wk == kept_before_seg + 1)
        else:
            # Linf bound: keep the first linf (randomly ordered) rows per
            # segment.
            linf_cap = config.linf if config.linf is not None else n
            row_keep = svalid & (seg_ops.rank_in_run(new_seg) < linf_cap)
            # L0 bound: the segment's ordinal within its pid — uniform by
            # the hpk sort key — must be < l0.
            keep_l0 = seg_ops.run_ordinal_in_group(new_seg,
                                                   new_pid) < config.l0
            keep_row = row_keep & keep_l0
            # Kept-segment indicator on the segment's first row: the
            # per-pk sum of these is the privacy-id count (row_count in
            # the reference's compound accumulator, dp_engine.py:339).
            seg_marker = new_seg & svalid & keep_l0

        clipped = _clip_values(config, svalues)
        masked = jnp.where(_expand(keep_row, clipped), clipped, 0.0)
        pk_safe = jnp.where(svalid, spk, 0)

        if config.per_partition_bounds:
            # Clip each (pid, pk) segment's SUM, contributed once per
            # segment. seg_ord is monotone, so this segment_sum is the
            # one per-segment scatter this mode still needs;
            # precision-safe (no cumsum diff).
            seg_ord = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
            seg_total = jax.ops.segment_sum(masked, seg_ord,
                                            num_segments=n)
            tot_row = seg_total[seg_ord]
            contrib = jnp.where(
                seg_marker,
                jnp.clip(tot_row, config.min_sum_per_partition,
                         config.max_sum_per_partition), 0.0)

    if config.per_partition_bounds:
        part, part_nseg = _reduce_per_pk(config, pk_safe, masked, keep_row,
                                         contrib, P, seg_marker=seg_marker,
                                         fx_bits=fx_bits,
                                         kernel_backend=kernel_backend)
    else:
        part, part_nseg = _reduce_per_pk(config, pk_safe, masked, keep_row,
                                         None, P, seg_marker=seg_marker,
                                         fx_bits=fx_bits,
                                         kernel_backend=kernel_backend)

    qrows = (_qrows(config, spk, svalues, keep_row)
             if config.percentiles else None)
    return part, part_nseg, qrows


# Fixed-point value accumulation: quantization grid (2^23 steps over the
# clip bound) split into integer lanes whose int32 segment sums stay
# EXACT. The lane width adapts to the (global) row count: a lane of
# ``bits`` bits accumulates up to 2^31/(2^bits - 1) rows exactly, so
# small datasets ride two wide 12-bit lanes (narrower scatter payload)
# and huge ones six 4-bit lanes (capacity 2^27 rows across the mesh).
_FX_STEPS = 1 << 23
_FX_OFFSET = 1 << 23
_FX_PAYLOAD_BITS = 24  # offset-shifted u fits 24 bits (u <= 2^24 - 1)

# int32 lane-sum capacity. Module-level seam so boundary tests can
# inject a small cap and pin the exact guard cliff (the way the lane
# plan's 524,417-row boundary is pinned) without 2^27-row datasets.
_LANE_SUM_CAP = 1 << 31


def _fx_max_rows() -> int:
    """Largest per-batch GLOBAL row count the narrowest (4-bit) lane
    plan accumulates exactly — the streaming chunk sizer caps per-batch
    targets here so value pipelines never plan an impossible batch."""
    return (_LANE_SUM_CAP - 1) // 15


def _fx_plan(n_rows_total: int) -> Tuple[int, int]:
    """(lane_bits, n_lanes) for a pipeline with ``n_rows_total`` rows
    across all devices — the cross-device psum adds per-shard lane sums,
    so capacity is a GLOBAL row bound."""
    bits = 12
    while bits > 4 and n_rows_total * ((1 << bits) - 1) >= _LANE_SUM_CAP:
        bits -= 1
    if n_rows_total * ((1 << bits) - 1) >= _LANE_SUM_CAP:
        raise NotImplementedError(
            f"fixed-point value lanes support up to 2^27 rows per "
            f"BATCH (got {n_rows_total}). The engine streams larger "
            "pipelines automatically (pipelinedp_tpu.streaming, "
            "including percentiles, with or without a mesh); reaching "
            "this from the streaming path means one privacy unit owns "
            "that many rows (its rows cannot split across batches)")
    return bits, -(-_FX_PAYLOAD_BITS // bits)


@dataclasses.dataclass(frozen=True)
class _FxSpec:
    """One fixed-point accumulated value column."""
    name: str
    bound: float  # |y| <= bound
    signed: bool  # signed columns ship offset by _FX_OFFSET
    count_col: str  # column holding the number of contributing entries

    @property
    def scale(self) -> float:
        return (_FX_STEPS - 1) / self.bound if self.bound > 0 else 1.0


def _fixedpoint_layout(config: FusedConfig) -> List[_FxSpec]:
    """The value columns the kernel accumulates in fixed point. Static in
    the config, so kernel and host release agree on the encoding."""
    names = set(config.metrics)
    if "VECTOR_SUM" in names or not (names & {"SUM", "MEAN", "VARIANCE"}):
        return []
    if config.per_partition_bounds:
        bound = max(abs(config.min_sum_per_partition),
                    abs(config.max_sum_per_partition))
        # One contribution per kept (pid, pk) segment.
        return [_FxSpec("sum", bound, True, "privacy_id_count_raw")]
    r = (config.max_value - config.min_value) / 2.0
    specs = [_FxSpec("nsum", r, True, "count")]
    if "VARIANCE" in names:
        specs.append(_FxSpec("nsumsq", r * r, False, "count"))
    return specs


def _vector_fx(config: FusedConfig) -> bool:
    """Whether VECTOR_SUM accumulates in fixed-point coordinate lanes
    (the ``vector_accumulator`` knob resolved onto the config). Static
    in the config, so kernel, host fold and streaming sizer agree."""
    return ("VECTOR_SUM" in config.metrics
            and config.vector_accumulator == "fx")


def _vector_fx_scale(config: FusedConfig) -> float:
    """Quantization scale of the vector coordinate grid: 2^23 - 1 steps
    over the static norm clip bound. The quantizer's clamp doubles as a
    per-row coordinate clamp at ±vector_max_norm — a contraction applied
    BEFORE aggregation (never increases sensitivity; the release still
    norm-clips the per-partition sum at the same bound), and one of the
    two documented ways 'fx' and 'f32' releases may differ (README
    "Vector aggregation")."""
    bound = float(config.vector_max_norm or 0.0)
    return (_FX_STEPS - 1) / bound if bound > 0 else 1.0


@scoped("segsum")
def _reduce_per_pk(config: FusedConfig, pk_safe, masked, keep_row,
                   per_partition_sum_contrib, P, seg_marker=None,
                   fx_bits: int = 7, kernel_backend: str = "xla"):
    """The fused shuffle 3: per-pk accumulator columns straight from row
    space, returned as (columns dict, privacy-id-count column).

    Every scalar column accumulates in int32 (VECTOR_SUM is the one
    exception — see below) — in ONE multi-feature segment_sum
    up to 2^24 rows (the scatter's addressing pass is shared; only the
    payload widens), and in independent per-column scatters beyond that
    (XLA tile-pads a [N, C] operand's C dim to 128 lanes and materializes
    a 21x remat copy at 2^25 rows):

    * counts + kept-segment markers directly — float32 addition saturates
      at 2^24 (1.0 + 16777216.0 == 16777216.0), silently under-counting
      huge partitions; int32 is exact to 2^31;
    * value columns in FIXED POINT: the normalized value
      (x - midpoint, and its square for variance — normalizing on device
      also kills the f32 cancellation of the sumsq recombination) is
      quantized to a 2^23-step grid over its static clip bound and split
      into four 7-bit lanes, each an exact int32 segment sum; the host
      release reassembles lanes in float64 (``_fold_fixedpoint``). Unlike
      a monolithic f32 segment_sum — whose sequential rounding drifts
      unboundedly with partition size (saturating outright at 2^24 equal
      values) — the only error is the per-row quantization, bounded by
      bound/2^23 per row independent of partition size, far below the
      f32 representation error of the inputs themselves.

    TPU-first rationale: the chip has no fast f64; exact integer lanes +
    one wide scatter beat both emulated f64 (x64 flag, 2x sort payload)
    and compensated-float scans (sequential chunk loop, still drifts on
    adversarial equal-value streams).
    """
    names = set(config.metrics)
    int_cols = [keep_row.astype(jnp.int32)]
    lane_names: List[str] = []
    if seg_marker is not None:
        int_cols.append(seg_marker.astype(jnp.int32))

    layout = _fixedpoint_layout(config)
    n_lanes = -(-_FX_PAYLOAD_BITS // fx_bits)
    if (layout or _vector_fx(config)) and max(
            pk_safe.shape[0] - 8191, 1) * (
            (1 << fx_bits) - 1) >= (1 << 31):
        # Loud trace-time guard for direct kernel callers: lane sums past
        # int32 capacity would wrap silently. The kernel only sees the
        # PADDED shape (real rows + at most 8191 padding rows, which are
        # masked to zero and consume no capacity), hence the 8191-row
        # allowance; _run_fused_kernel sizes fx_bits from the real global
        # row count, so the engine path never trips this.
        raise NotImplementedError(
            f"{pk_safe.shape[0]} (padded) rows overflow {fx_bits}-bit "
            "fixed-point lanes; pass a smaller fx_bits (see _fx_plan)")
    for spec in layout:
        if spec.name == "sum":  # per-partition-bound mode
            y = per_partition_sum_contrib
            mask = seg_marker if seg_marker is not None else keep_row
        elif spec.name == "nsum":
            middle = dp_computations.compute_middle(config.min_value,
                                                    config.max_value)
            y = masked - middle
            mask = keep_row
        else:  # nsumsq
            middle = dp_computations.compute_middle(config.min_value,
                                                    config.max_value)
            y = (masked - middle) * (masked - middle)
            mask = keep_row
        # Clamp after rounding: f32 rounding of y*scale at the clip
        # boundary can land one step past ±(2^23 - 1), which would need a
        # 25th payload bit; the clamp costs one grid step of accuracy at
        # the exact boundary and keeps u <= 2^24 - 1 in 24 bits.
        q = jnp.clip(jnp.round(y * spec.scale), -(_FX_STEPS - 1),
                     _FX_STEPS - 1).astype(jnp.int32)
        u = jnp.where(mask, q + (_FX_OFFSET if spec.signed else 0), 0)
        for k in range(n_lanes):
            int_cols.append((u >> (k * fx_bits)) & ((1 << fx_bits) - 1))
            lane_names.append(f"{spec.name}_fx{k}")

    if len(int_cols) == 1:
        ints = [jax.ops.segment_sum(int_cols[0], pk_safe, num_segments=P)]
    elif pk_safe.shape[0] >= (1 << 25):
        # Past 2^24 rows XLA materializes a tile-padded remat copy of the
        # [N, C] stack (the C-sized dim pads to 128 lanes — a 21x, 16GB
        # blowup at 2^25); independent per-column scatters keep every
        # operand rank-1 and densely tiled.
        ints = [jax.ops.segment_sum(c, pk_safe, num_segments=P)
                for c in int_cols]
    else:
        # One multi-feature scatter: the addressing pass is shared.
        # The ``kernel_backend`` knob swaps in the Pallas lane-packed
        # segment sum here (bit-identical int32 totals — PARITY row
        # 33); off-envelope shapes or a Pallas-less host fall back to
        # the XLA scatter with a ``kernel.fallback`` event.
        from pipelinedp_tpu.ops import kernels as hot_kernels
        stack = jnp.stack(int_cols, axis=1)
        stacked = hot_kernels.try_segment_sum_lanes(
            stack, pk_safe, P, kernel_backend)
        if stacked is None:
            stacked = jax.ops.segment_sum(stack, pk_safe,
                                          num_segments=P)
        ints = [stacked[:, i] for i in range(len(int_cols))]
    part = {"count": ints[0]}
    col = 1
    if seg_marker is not None:
        nseg = ints[col]
        col += 1
    else:
        nseg = None
    for i, name in enumerate(lane_names):
        part[name] = ints[col + i]

    if "VECTOR_SUM" in names:
        if _vector_fx(config):
            # Fixed-point coordinate lanes — the scalar columns'
            # discipline at [N, D] width: each coordinate quantizes to
            # the 2^23-step grid over the norm clip bound, the
            # offset-shifted payload splits into n_lanes int32 lane
            # planes concatenated lane-major ([N, n_lanes*D]), and ONE
            # wide segment sum reduces them per partition — exact
            # int32 totals, backend- and mesh-bit-identical (PARITY
            # row 39). The host fold (_fold_vector_fx_steps)
            # reassembles float64 coordinates.
            scale = _vector_fx_scale(config)
            q = jnp.clip(jnp.round(masked * scale), -(_FX_STEPS - 1),
                         _FX_STEPS - 1).astype(jnp.int32)
            u = jnp.where(keep_row[:, None], q + _FX_OFFSET, 0)
            lanes = jnp.concatenate(
                [(u >> (k * fx_bits)) & ((1 << fx_bits) - 1)
                 for k in range(n_lanes)], axis=1)
            from pipelinedp_tpu.ops import kernels as hot_kernels
            vec = hot_kernels.try_segment_sum_wide(
                lanes, pk_safe, P, kernel_backend,
                d_block=config.wide_d_block)
            if vec is None:
                vec = jax.ops.segment_sum(lanes, pk_safe,
                                          num_segments=P)
            part["vector_sum"] = vec
        else:
            # Vector coordinates accumulate in float32 (the historical
            # default; the 'fx' accumulator above retires the hazard).
            # The f32 drift/saturation hazard the lanes eliminate for
            # scalars still applies per coordinate past ~2^24 equal
            # contributions in one partition (README "Scaling
            # limits"). The Pallas wide-D kernel never dispatches here
            # — an f32 matmul's partial-sum order differs from the XLA
            # scatter's, so bit-identity would not hold; a pallas
            # request degrades visibly instead.
            if kernel_backend == "pallas":
                from pipelinedp_tpu import obs
                obs.inc("kernel.fallbacks")
                obs.event("kernel.fallback", site="segment_sum_wide",
                          reason="vector_f32_accumulator",
                          P=int(P), D=int(masked.shape[1]),
                          rows=int(pk_safe.shape[0]))
            part["vector_sum"] = jax.ops.segment_sum(masked, pk_safe,
                                                     num_segments=P)
    return part, nseg


def _fold_fx_steps(config: FusedConfig, part64, fx_bits: int) -> None:
    """Reassembles the fixed-point lane columns into EXACT step totals
    (mutates ``part64``): steps = sum of lanes * 2^(bits*k) - entries *
    offset. Every term is an integer below 2^53, so the float64 result
    is exact — which is what lets the streaming fold accumulate these
    across chunks and divide by the (non-power-of-two) scale ONCE at
    release: a per-chunk division would round per chunk, making the
    released low bits a function of the batch boundaries (and therefore
    of the mesh size — the elastic reshard-resume parity would only
    hold by luck)."""
    n_lanes = -(-_FX_PAYLOAD_BITS // fx_bits)
    for spec in _fixedpoint_layout(config):
        total = np.zeros_like(part64[spec.count_col], dtype=np.float64)
        for k in range(n_lanes):
            total += part64.pop(f"{spec.name}_fx{k}").astype(
                np.float64) * float(1 << (k * fx_bits))
        if spec.signed:
            total -= part64[spec.count_col].astype(np.float64) * _FX_OFFSET
        part64[spec.name] = total


def _fold_vector_fx_steps(config: FusedConfig, lanes, count,
                          fx_bits: int):
    """Reassembles the [n, n_lanes*D] vector lane sums into EXACT
    float64 step totals [n, D]: steps = sum of lane planes * 2^(bits*k)
    - count * offset. Same exactness contract as
    :func:`_fold_fx_steps` — every term is an integer below 2^53, so
    the streaming fold may accumulate step totals across chunks and
    divide by the scale ONCE at release (batch-boundary invariant; the
    elastic reshard-resume parity depends on it)."""
    n_lanes = -(-_FX_PAYLOAD_BITS // fx_bits)
    D = int(config.vector_size)
    lanes = np.asarray(lanes)
    total = np.zeros((lanes.shape[0], D), dtype=np.float64)
    for k in range(n_lanes):
        total += lanes[:, k * D:(k + 1) * D].astype(
            np.float64) * float(1 << (k * fx_bits))
    total -= np.asarray(count).astype(np.float64)[:, None] * _FX_OFFSET
    return total


def _fold_fixedpoint(config: FusedConfig, part64, fx_bits: int) -> None:
    """Reassembles the fixed-point lane columns into float64 values
    (mutates ``part64``): value = (sum of lanes * 2^(bits*k) - entries *
    offset) / scale. ``entries`` (the per-partition count of contributing
    rows/segments) is exact int, so the offset removal is exact. The
    vector lanes fold the same way ([n, D] coordinates from the
    lane-major [n, n_lanes*D] sums, offsets removed via the count
    column)."""
    _fold_fx_steps(config, part64, fx_bits)
    for spec in _fixedpoint_layout(config):
        part64[spec.name] = part64[spec.name] / spec.scale
    if _vector_fx(config) and "vector_sum" in part64:
        part64["vector_sum"] = _fold_vector_fx_steps(
            config, part64["vector_sum"], part64["count"],
            fx_bits) / _vector_fx_scale(config)


@scoped("walk")
def _qrows(config: FusedConfig, pk, values, kept):
    """Percentile row view: (pk, leaf index, kept mask) per row, in
    whatever row order the caller is in. The leaf mapping mirrors the host
    tree (``ops/quantile_tree.py:_leaf_index``).

    The leaf arithmetic is one f32 subtract and one f32 multiply by a
    host-folded constant — deliberately: the streamed pass-A and pass-B
    kernels are SEPARATE XLA programs that re-derive each row's leaf, and
    a division (whose lowering can vary with fusion context) or a
    fusible mul+add pair (FMA) could round differently across programs,
    silently mis-bucketing boundary values between the passes. Neither
    op here is re-fusible (sub->mul is not an FMA pattern), so every
    program computes the identical IEEE sequence."""
    b = quantile_tree_ops.DEFAULT_BRANCHING_FACTOR
    height = quantile_tree_ops.DEFAULT_TREE_HEIGHT
    n_leaves = b**height
    lower, upper = config.min_value, config.max_value
    v = jnp.clip(values, lower, upper)
    rng = float(upper) - float(lower)
    inv_range = np.float32(float(n_leaves) / rng) if rng > 0 else None
    # ``params_are_fusable`` routes degenerate (lower >= upper) and
    # pathologically tiny ranges (f32-overflowing constant) to the host
    # path, which computes in f64; a non-finite constant here means a
    # FusedConfig was constructed around that guard.
    assert inv_range is not None and np.isfinite(inv_range), (
        f"fused percentile range [{lower}, {upper}] has no finite f32 "
        "leaf constant — params_are_fusable should have rejected it")
    leaf = jnp.minimum(((v - lower) * inv_range).astype(jnp.int32),
                       n_leaves - 1)
    return (jnp.where(kept, pk, 0), leaf, kept)


def _selection_and_metrics(config: FusedConfig, num_partitions: int, part,
                           part_nseg, noise_scales, keep_table,
                           sel_threshold, sel_scale, sel_min_count,
                           sel_rows_per_uid, k_sel, k_noise, qrows=None,
                           pk_axis=None, pk_axis_size=1, pk_topo=None):
    """Batched partition selection + metric noising.

    Single-chip: ``num_partitions`` is the full pk axis. Multi-chip
    (``pk_axis`` set): the partition axis is SHARDED — ``part``/
    ``part_nseg`` are this device's owned block of ``num_partitions``
    partitions (out of ``num_partitions * pk_axis_size`` global), after
    the ``psum_scatter`` exchange in ``parallel.sharded``. Selection
    randomness is drawn over the GLOBAL axis and sliced, so the mesh
    computes bit-identical keep decisions to a single device with the
    same key whenever the global axis equals the single-chip padded axis
    (any power-of-two mesh; see ``sharded_fused_aggregate``'s rounding
    note)."""
    from pipelinedp_tpu.ops import noise as noise_ops

    P = num_partitions
    if pk_axis is None:
        offset = None
        P_total = P
    else:
        offset = jax.lax.axis_index(pk_axis) * P
        P_total = P * pk_axis_size

    def owned(draw_fn):
        """Draws a [P_total] random vector, returns this device's block."""
        with scope("noise"):
            full = draw_fn((P_total,))
            if offset is None:
                return full
            return jax.lax.dynamic_slice(full, (offset,), (P,))

    # --- partition selection (batched over all partitions) ---
    if config.selection is None:
        keep_pk = jnp.ones(P, dtype=bool)
        # (The public-partition empty-accumulator sum adjustment happens
        # in the float64 host release, _host_release.)
    else:
        with scope("select"):
            # Without privacy ids one row is not one user; the conservative
            # user-count estimate is ceil(rows / max_rows_per_privacy_id)
            # (reference dp_engine.py:341-348).
            est_users = jnp.ceil(part_nseg.astype(jnp.float32) /
                                 sel_rows_per_uid)
            counts = est_users.astype(jnp.int32)
            if config.selection == (
                    PartitionSelectionStrategy.TRUNCATED_GEOMETRIC):
                idx = jnp.clip(counts, 0, keep_table.shape[0] - 1)
                p_keep = keep_table[idx]
                # Selection draws route through the blessed noise module
                # (unit scale here; sel_scale applies outside the draw).
                keep_pk = owned(
                    lambda s: noise_ops.jax_uniform(k_sel, s)) < p_keep
            else:
                if config.selection == (
                        PartitionSelectionStrategy.LAPLACE_THRESHOLDING):
                    noise_sel = owned(
                        lambda s: noise_ops.jax_laplace(k_sel, s, 1.0)
                    ) * sel_scale
                else:
                    noise_sel = owned(
                        lambda s: noise_ops.jax_gaussian(k_sel, s, 1.0)
                    ) * sel_scale
                keep_pk = ((est_users + noise_sel) >= sel_threshold) & (
                    est_users >= sel_min_count)  # pre-threshold hard floor
            keep_pk = keep_pk & (part_nseg > 0)

    # --- accumulator partials out; the scalar release happens on HOST in
    # float64 (see LazyFusedResult._host_release): float32 noise on a
    # large aggregate quantizes to the value's ULP grid, which both
    # distorts the calibrated distribution and leaks through rounding
    # (the reference's release path is float64 end-to-end). Percentiles
    # stay on device: their noisy node counts are small integers where
    # float32 granularity is irrelevant, and the walk needs the rows.
    out = dict(part)
    out["privacy_id_count_raw"] = part_nseg
    if config.percentiles:
        # Percentile noise scale is the last _noise_scales entry; the tree
        # key is independent of the selection key stream.
        with scope("walk"):
            # lint: disable=rng-purity(tree key: constant fold of the noise stream)
            k_tree = jax.random.fold_in(k_noise, 0x7ee)
        if pk_axis is None:
            vals, walk_stats = _percentile_values(config, P, qrows,
                                                  noise_scales[-1], k_tree)
            if walk_stats is not None:
                out[WALK_STATS] = walk_stats
        else:
            vals = _percentile_values_owned(config, P, qrows,
                                            noise_scales[-1], k_tree,
                                            pk_axis, pk_axis_size,
                                            topo=pk_topo)
        for qi, name in enumerate(_percentile_field_names(
                config.percentiles)):
            out[name] = vals[:, qi]
    return keep_pk, out


def _percentile_field_names(percentiles) -> List[str]:
    """Same formatting as ``QuantileCombiner.metrics_names`` (reference
    ``combiners.py:434-445``)."""
    names = []
    for p in percentiles:
        int_p = int(round(p))
        text = str(int_p) if int_p == p else str(p).replace(".", "_")
        names.append(f"percentile_{text}")
    return names


@scoped("noise")
def _node_noise(noise_kind: NoiseKind, key, node_ids, pk_index=None):
    """One noise draw per (partition, tree node), as a pure function of
    the indices: every quantile walk that visits a node sees the same
    noisy count — the stateless form of the host tree's memoization
    (``ops/quantile_tree.py::compute_quantiles``). Realized as ONE
    batched counter-based threefry pass per call
    (``ops/counter_rng.py``): the (partition, node) pair IS the
    counter, so the draw is identical wherever and however often the
    pair appears — visited-node-only draws, the root-level broadcast
    and partition-block-chunked walks are all bit-exact restructurings
    by construction. ``node_ids`` is int32 [P, Q, b]; ``pk_index``
    overrides the per-partition counter lane (the GLOBAL partition ids
    when the pk axis is sharded or block-chunked, so mesh, streamed and
    chunked noise all match the single-chip draw bit-for-bit)."""
    from pipelinedp_tpu.ops import counter_rng

    P = node_ids.shape[0]
    if pk_index is None:
        pk_index = jnp.arange(P, dtype=jnp.uint32)
    x0 = jnp.broadcast_to(
        pk_index.astype(jnp.uint32).reshape(
            (P,) + (1,) * (node_ids.ndim - 1)), node_ids.shape)
    x1 = node_ids.astype(jnp.uint32)
    if noise_kind == NoiseKind.LAPLACE:
        return counter_rng.laplace(key, x0, x1)
    return counter_rng.normal(key, x0, x1)


# HBM cap for the per-quantile subtree histogram (int32 [P, Q, span]);
# above it the walk chunks the partition axis into blocks and walks
# block-by-block (bit-identical to the unchunked walk — node noise is a
# pure function of (partition, node id)). Registered as the
# ``subhist_byte_cap`` knob; reads flow through ``plan.knobs`` (env >
# this seam when test-mutated > this default) and the module name
# survives as the test seam (``make noknobs``).
_SUBHIST_BYTE_CAP = 600 << 20

#: The ``vector_accumulator`` knob's module seam (plan/knobs.py
#: registers it, dp-unsafe): VECTOR_SUM's 'f32' vs
#: 'fx' accumulator discipline, resolved onto FusedConfig at
#: from_params time.
_VECTOR_ACCUMULATOR = "f32"

# The single-batch walk unrolls its partition blocks INSIDE one XLA
# program, so the block count is bounded: each block costs ~3 O(n)
# elementwise passes + Q compacted scatters, so 16 blocks stay well
# under the per-level row-scatter fallback's cost envelope while
# covering subtree blocks to 16x the byte cap (~10 GB at the default
# cap — past any single chip's HBM); beyond that the per-level
# fallback both bounds the program size and does fewer row passes.
# (The streamed walk needs no such bound: its blocks are separate
# kernel launches, and re-streaming is its only completion path.)
_MAX_WALK_BLOCKS = 16

#: The fused program's output (int32 [2]) that says how the bottom
#: walk's compaction went: the rows that fell in some chosen subtree,
#: and how many subtree blocks overflowed the compaction's static
#: prefix and took the full-row scatters. Not a partition column: the
#: release fetches it in the compact fetch's meta row.
WALK_STATS = "walk_stats"


@scoped("walk")
def _percentile_values(config: FusedConfig, P, qrows, scale, key):
    """Batched DP quantile-tree descent over every partition at once
    (single-chip; the sharded twin is ``_percentile_values_owned``).
    Returns (values [P, Q], the ``WALK_STATS`` vector, or None on the
    paths that do not compact).

    Level l needs, per (partition, quantile), the noisy counts of the
    ``b`` children of the walk's current node. Rather than materializing
    per-partition trees, each level counts its children with one
    segment_sum over the rows (a row lands in child ``leaf//w - base``
    of its partition's walk, or nowhere). The arithmetic (rank targeting,
    child pick, interpolation, early stop when no noisy signal remains,
    monotone post-processing) mirrors ``QuantileTree.compute_quantiles``.
    """
    qpk, leaf, kept = qrows
    b, height, n_mid, bucket_w = quantile_tree_ops.tree_constants()
    quantiles = np.asarray([p / 100.0 for p in config.percentiles],
                           np.float32)
    Q = quantiles.shape[0]
    lower = float(config.min_value)
    upper = float(config.max_value)

    # Fast path: one [P, b^2] histogram (bucket width b^(height-2)),
    # built with ONE row scatter, serves the top two levels via P-space
    # sums/gathers — full-row scatters are the walk's dominant cost, so
    # this trades 2 of the 4 away. Wider histograms don't pay: [P, b^3]
    # is a 536M-segment scatter plus 2GB temps.
    hist = None
    if height >= 2:
        hist = jax.ops.segment_sum(
            kept.astype(jnp.int32),
            qpk * n_mid + jnp.minimum(leaf // bucket_w, n_mid - 1),
            num_segments=P * n_mid).reshape(P, n_mid)

    def counts_at(w, base):
        """Noiseless child counts [P, Q, b] of the walk nodes whose
        children have width ``w``."""
        if hist is not None and w >= bucket_w:
            return _mid_level_counts(hist, base, w, bucket_w, b)
        # Fallback for the lower levels: per-quantile row passes (an
        # interleaved [n*Q] scatter benches slower than Q separate [n]
        # scatters on TPU).
        counts = []
        for q in range(Q):
            slot = leaf // w - base[:, q][qpk]
            ok = kept & (slot >= 0) & (slot < b)
            seg = qpk * b + jnp.clip(slot, 0, b - 1)
            counts.append(
                jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                    num_segments=P * b).reshape(P, b))
        return jnp.stack(counts, axis=1).astype(jnp.float32)

    lo = jnp.full((P, Q), lower, jnp.float32)
    hi = jnp.full((P, Q), upper, jnp.float32)
    target = jnp.broadcast_to(quantiles[None, :], (P, Q))
    leaf_lo = jnp.zeros((P, Q), jnp.int32)
    done = jnp.zeros((P, Q), bool)
    level_offset = 0
    stats = None
    # Top levels: served by the mid histogram (node width >= bucket_w —
    # levels 0 and 1 for any height >= 2).
    n_top = min(2, height) if hist is not None else 0
    for level in range(n_top):
        w = b**(height - 1 - level)
        base = leaf_lo // w  # [P, Q] first-child index at this level
        lo, hi, target, leaf_lo, done = _walk_level(
            config.noise_kind, key, scale, counts_at(w, base), base,
            level_offset, lo, hi, target, leaf_lo, done, b, w)
        level_offset += b**(level + 1)

    if n_top < height:
        # Bottom levels: ONE leaf-granularity scatter per quantile over
        # the chosen subtree (span = w*b leaves at the first bottom
        # level) serves ALL remaining levels via in-register group sums
        # — halving the walk's dominant cost, the full-row scatters.
        w1 = b**(height - 1 - n_top)
        span = w1 * b
        # Which bottom-walk path fires is a STATIC (shape-driven)
        # decision made here, at jit-trace time — so the ledger records
        # it once per compiled shape (scope="compile"), exactly when
        # the choice happens; cached executions re-use the program and
        # the recorded choice with it.
        from pipelinedp_tpu import obs
        from pipelinedp_tpu import plan as plan_mod
        # The registry's resolution of the subhist byte cap (env > test
        # seam > default; the module constant survives as the seam).
        # Host code at jit-trace time, so the choice is recorded once
        # per compiled shape like the walk path itself.
        subhist_cap = int(plan_mod.knob_value("subhist_byte_cap"))
        if P * Q * span * 4 <= subhist_cap:
            obs.inc("walk.path_subhist")
            obs.event("walk.path", path="subhist", scope="compile",
                      P=int(P), Q=int(Q), span=int(span))
            sub_start = leaf_lo  # [P, Q] first leaf of each subtree
            sub_hist, stats = _build_sub_hist(qpk, leaf, kept, sub_start,
                                              P, Q, span, b, height)
            for level in range(n_top, height):
                w = b**(height - 1 - level)
                raw = _sub_level_counts(sub_hist, sub_start, leaf_lo, w, b)
                lo, hi, target, leaf_lo, done = _walk_level(
                    config.noise_kind, key, scale, raw, leaf_lo // w,
                    level_offset, lo, hi, target, leaf_lo, done, b, w)
                level_offset += b**(level + 1)
        else:
            blk = 0
            if Q * span * 4 <= subhist_cap:
                blk = min(P, 1 << ((subhist_cap //
                                    (Q * span * 4)).bit_length() - 1))
            if blk and -(-P // blk) <= _MAX_WALK_BLOCKS:
                obs.inc("walk.path_partition_block_chunked")
                obs.event("walk.path", path="partition_block_chunked",
                          scope="compile", blk=int(blk), P=int(P))
                # The full [P, Q, span] block would blow the HBM cap:
                # chunk the partition axis into blocks and walk
                # block-by-block (the streamed pass B's q-chunk loop
                # shape, turned along the partition axis), each block's
                # histogram built with the SAME compacted machinery as
                # the one-block walk (rows outside the block are simply
                # masked out of the relevance flags). Node noise is a
                # pure function of the GLOBAL (partition, node id) —
                # passed via ``pk_index`` — and the per-partition
                # histogram content is unchanged, so the chunked walk
                # is bit-identical to the unchunked one.
                outs, stats = [], None
                for p0 in range(0, P, blk):
                    Pb = min(blk, P - p0)
                    psl = slice(p0, p0 + Pb)
                    ss = leaf_lo[psl]
                    rel_pk = qpk - p0
                    kept_b = kept & (rel_pk >= 0) & (rel_pk < Pb)
                    pk_b = jnp.clip(rel_pk, 0, Pb - 1)
                    sub, blk_stats = _build_sub_hist(pk_b, leaf, kept_b, ss,
                                                     Pb, Q, span, b, height)
                    if blk_stats is not None:
                        stats = (blk_stats if stats is None
                                 else stats + blk_stats)
                    lo_b, hi_b, tg_b = lo[psl], hi[psl], target[psl]
                    ll_b, dn_b = leaf_lo[psl], done[psl]
                    pk_idx = (p0 + jnp.arange(Pb)).astype(jnp.uint32)
                    lvo = level_offset
                    for level in range(n_top, height):
                        w = b**(height - 1 - level)
                        raw = _sub_level_counts(sub, ss, ll_b, w, b)
                        lo_b, hi_b, tg_b, ll_b, dn_b = _walk_level(
                            config.noise_kind, key, scale, raw,
                            ll_b // w, lvo, lo_b, hi_b, tg_b, ll_b,
                            dn_b, b, w, pk_index=pk_idx)
                        lvo += b**(level + 1)
                    outs.append(lo_b + (hi_b - lo_b) * tg_b)
                return _monotone_in_q(jnp.concatenate(outs, axis=0),
                                      quantiles), stats
            # Past _MAX_WALK_BLOCKS (or a cap below one partition's
            # [1, Q, span] block — necessarily test-shrunken):
            # per-level per-quantile row scatters, the rows being
            # device-resident here.
            obs.inc("walk.path_per_level_scatter")
            obs.event("walk.path", path="per_level_scatter",
                      scope="compile", P=int(P), Q=int(Q))
            for level in range(n_top, height):
                w = b**(height - 1 - level)
                base = leaf_lo // w
                lo, hi, target, leaf_lo, done = _walk_level(
                    config.noise_kind, key, scale, counts_at(w, base),
                    base, level_offset, lo, hi, target, leaf_lo, done,
                    b, w)
                level_offset += b**(level + 1)
    vals = lo + (hi - lo) * target  # [P, Q]
    return _monotone_in_q(vals, quantiles), stats


def _build_sub_hist(qpk, leaf, kept, sub_start, P, Q, span, b, height):
    """The [P, Q, span] leaf-granularity subtree histograms of the
    bottom walk, with the prefix-sum row compaction (r5): the chosen
    subtrees jointly cover ~Q/n_blocks of the leaf space, so typically
    ~1% of rows land in ANY sub-histogram — compact the relevant rows
    to a static n/8 prefix first so the per-quantile scatters scan 8x
    fewer rows. Values on a coarse grid break that: every row of a
    grade shares one leaf, so a subtree holds whole grades (a quarter
    of the rows of half-star ratings) and the full-row scatters run.

    Returns (histograms, ``WALK_STATS``: [relevant rows, 1 where they
    overflowed the prefix]), the stats None where nothing compacts."""
    n_blocks = (b**height) // span
    # The descent so far only added multiples of widths >= span, so
    # every walk's subtree start is span-ALIGNED: membership is "the
    # row's span-block == the walk's block id", the in-subtree offset
    # is just the low leaf bits, and the scatter key is the SAME for
    # every quantile — only the membership mask differs. The Q per-row
    # block ids (each < n_blocks <= 256 for the default tree) pack
    # 4-per-int32, so the per-row cost is ceil(Q/4) gathers + byte
    # compares instead of Q gathers.
    shift = span.bit_length() - 1  # span is a power of two
    mid = leaf >> shift
    lo_bits = leaf & (span - 1)
    blk = sub_start >> shift  # [P, Q] block ids

    def row_masks(qpk_r, mid_r, kept_r):
        """Per-quantile membership masks of the given rows, via the
        packed block tables."""
        masks = []
        for g in range(0, Q, 4):
            packed = jnp.zeros(P, jnp.int32)
            for j, q in enumerate(range(g, min(g + 4, Q))):
                packed |= blk[:, q] << (8 * j)
            pr = packed[qpk_r]  # ONE gather per 4 quantiles
            for j, q in enumerate(range(g, min(g + 4, Q))):
                masks.append(kept_r & (
                    mid_r == ((pr >> (8 * j)) & 0xFF)))
        return masks

    def subs_over(qpk_r, mid_r, lo_r, kept_r):
        seg = qpk_r * span + lo_r  # q-independent key
        return jnp.stack([
            jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                num_segments=P * span
                                ).reshape(P, span)
            for ok in row_masks(qpk_r, mid_r, kept_r)
        ], axis=1)  # [P, Q, span] int32

    if n_blocks <= 256 and shift <= 22:
        # Compact the relevant rows to a static n/8 prefix by
        # PREFIX-SUM scatter: each relevant row's destination is its
        # rank among relevant rows (cumsum), so two O(n) passes replace
        # the former stable argsort's ~log^2 n bitonic stages (the
        # walk's furthest-from-roofline op, r4 README). The
        # destinations are unique and monotone — the scatter coalesces;
        # irrelevant rows target index ``cap`` and drop out of bounds,
        # as do relevant rows past the cap (data concentrated enough to
        # overflow falls back to full-row scatters via lax.cond). The
        # three row fields pack into one int32 (mid <= 8 bits by the
        # n_blocks gate, lo_bits < span = 2^shift <= 2^22 by the shift
        # gate, kept 1 bit), so compaction is exactly two int32
        # scatters.
        n_rows = leaf.shape[0]
        cap = max(8192, n_rows // 8)
        rel_any = jnp.zeros(n_rows, bool)
        for ok in row_masks(qpk, mid, kept):
            rel_any |= ok
        n_rel = jnp.sum(rel_any.astype(jnp.int32))

        def compacted(_):
            # Built INSIDE the branch: cond operands are computed
            # unconditionally, so hoisting these would make the
            # overflow fallback pay for both paths.
            dest = jnp.where(
                rel_any,
                jnp.cumsum(rel_any.astype(jnp.int32)) - 1,
                cap)
            packed_row = (
                mid | (lo_bits << 8) |
                (kept.astype(jnp.int32) << (8 + shift)))
            qpk_c = jnp.zeros(cap, jnp.int32).at[dest].set(
                qpk, mode="drop")
            row_c = jnp.zeros(cap, jnp.int32).at[dest].set(
                packed_row, mode="drop")
            return subs_over(qpk_c, row_c & 0xFF,
                             (row_c >> 8) & (span - 1),
                             (row_c >> (8 + shift)).astype(bool))

        def full(_):
            return subs_over(qpk, mid, lo_bits, kept)

        fits = n_rel <= cap
        return (jax.lax.cond(fits, compacted, full, None),
                jnp.stack([n_rel, 1 - fits.astype(jnp.int32)]))
    if n_blocks <= 256:
        # Exotic tree shapes whose packed row would overflow int32: no
        # compaction, full-row scatters.
        return subs_over(qpk, mid, lo_bits, kept), None
    # Non-default tree shapes: block ids > 8 bits.
    return _subtree_counts(qpk, leaf, kept, sub_start, P, span), None


def _mid_level_counts(mid, base, w, bucket_w, b):
    """Child counts [P, Q, b] of width-``w`` walk nodes read from the
    [P, n_mid] mid-level histogram (``w >= bucket_w``): children are
    contiguous groups of ``w/bucket_w`` buckets. The group sum runs in
    transposed layout ([groups, g, P]) — a [P, groups, g] reshape would
    leave a tiny trailing dim that TPU tiling pads ~8x. SHARED by the
    single-batch top-histogram path and the streamed top walk."""
    P, n_mid = mid.shape
    g = w // bucket_w
    lvl = mid if g == 1 else mid.T.reshape(n_mid // g, g, P).sum(1).T
    idx = base[..., None] + jnp.arange(b)  # [P, Q, b]
    return lvl[jnp.arange(P)[:, None, None], idx].astype(jnp.float32)


def _sub_level_counts(sub, sub_start, leaf_lo, w, b):
    """Child counts [P, Q, b] of width-``w`` nodes read from the
    [P, Q, span] subtree leaf histograms: children occupy w-groups
    [off + c] for c < b, where off is the node's group offset inside
    the subtree. SHARED by the single-batch sub-histogram path and the
    streamed bottom walk."""
    P, Q, span = sub.shape
    g = sub if w == 1 else sub.reshape(P, Q, span // w, w).sum(-1)
    off = (leaf_lo - sub_start) // w  # [P, Q]
    idx = off[..., None] + jnp.arange(b)  # [P, Q, b]
    return jnp.take_along_axis(g, idx, axis=2).astype(jnp.float32)


def _walk_level(noise_kind, key, scale, raw, base, level_offset, lo, hi,
                target, leaf_lo, done, b, w, pk_index=None):
    """One walk level from its raw child counts: node-id-keyed noise +
    descent step. SHARED by the single-batch walk, the owner-sharded
    walk (which passes its GLOBAL partition ids as ``pk_index``) and
    the streamed two-pass walk — the mesh/streamed/single-batch
    bit-parity guarantees rest on this being the one copy of the
    noise-keying + step arithmetic.

    At the ROOT level every quantile shares base 0, so the [P, Q, b]
    node ids are Q identical copies — and node noise is a pure function
    of (partition, node id), so the draws are too: draw once per
    (partition, child) and broadcast, skipping (Q-1)/Q of the root's
    threefry work with bit-identical values."""
    node_ids = (level_offset + base)[..., None] + jnp.arange(
        b, dtype=jnp.int32)
    if level_offset == 0:
        noise = jnp.broadcast_to(
            _node_noise(noise_kind, key, node_ids[:, :1, :], pk_index),
            node_ids.shape)
    else:
        noise = _node_noise(noise_kind, key, node_ids, pk_index)
    noisy = jnp.maximum(raw + noise * scale, 0.0)
    return _walk_step(noisy, lo, hi, target, leaf_lo, done, b, w)


@scoped("walk")
def _subtree_counts(qpk, leaf, kept, sub_start, P, span, p_offset=None):
    """Leaf counts of each quantile's chosen subtree from row data:
    [P, Q, span] int32 (one masked scatter per quantile). Shared by the
    single-batch generic fallback and the streamed pass-B kernel. With
    ``p_offset`` set, ``P`` is a partition BLOCK size and only rows of
    partitions [p_offset, p_offset + P) count — the partition-block-
    chunked walk's histogram, whose per-partition content is identical
    to the full scatter's."""
    if p_offset is not None:
        rel_pk = qpk - p_offset
        in_blk = kept & (rel_pk >= 0) & (rel_pk < P)
        pk_b = jnp.clip(rel_pk, 0, P - 1)
    else:
        in_blk, pk_b = kept, qpk
    subs = []
    for q in range(sub_start.shape[1]):
        rel = leaf - sub_start[:, q][pk_b]
        ok = in_blk & (rel >= 0) & (rel < span)
        seg = pk_b * span + jnp.clip(rel, 0, span - 1)
        subs.append(jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                        num_segments=P * span
                                        ).reshape(P, span))
    return jnp.stack(subs, axis=1)


@scoped("walk")
def _subtree_counts_multi(qpk, leaf, kept, sub_starts, p_offsets, Pb,
                          span, kernel_backend: str = "xla"):
    """Several tiles' subtree-leaf counts from ONE pass over the rows:
    ``sub_starts`` is [T, Pb, Qc] (each tile's walk-start leaves),
    ``p_offsets`` [T] (each tile's first global partition), output
    [T, Pb, Qc, span] int32. The multi-tile pass-B kernels call this so
    one batch recompute (bounding + leaf mapping) serves every tile the
    sweep planner packed into the round — per tile it is EXACTLY
    ``_subtree_counts`` on the same rows, so the packed result is
    bit-identical to the per-tile loop by construction.

    ``kernel_backend`` (the dp-safe knob, resolved by the caller OUTSIDE
    jit so a backend switch re-traces) selects the Pallas multi-tile
    binner — bit-identical integers (PARITY row 33) — with automatic
    XLA fallback (``kernel.fallback``) off-envelope or sans Pallas."""
    from pipelinedp_tpu.ops import kernels as hot_kernels
    binned = hot_kernels.try_hist_bin_multi(
        qpk, leaf, kept, sub_starts, p_offsets, Pb, span,
        kernel_backend)
    if binned is not None:
        return binned
    return jnp.stack([
        _subtree_counts(qpk, leaf, kept, sub_starts[t], Pb, span,
                        p_offset=p_offsets[t])
        for t in range(sub_starts.shape[0])])


def _walk_step(noisy, lo, hi, target, leaf_lo, done, b, w):
    """One level of the quantile descent: pick the child bucket whose
    cumulative noisy count crosses the rank target, re-normalize the
    target into it (``QuantileTree.compute_quantiles`` arithmetic).

    The prefix sums add the children one at a time, in child order:
    a ``sum``/``cumsum`` reduction lets the compiler pick its own
    association per program and layout, and on a TPU the single-batch,
    streamed and mesh programs then round differently, so a walk near
    a tie could pick a different child in each."""
    acc = noisy[..., 0]
    prefix = [acc]
    for k in range(1, b):
        acc = acc + noisy[..., k]
        prefix.append(acc)
    incl = jnp.stack(prefix, axis=-1)
    total = acc
    rank = target * total
    ge = incl >= rank[..., None]
    child = jnp.where(ge.any(-1), jnp.argmax(ge, -1), b - 1)
    c = jnp.take_along_axis(noisy, child[..., None], -1)[..., 0]
    cum = jnp.take_along_axis(incl, child[..., None], -1)[..., 0] - c
    width = (hi - lo) / b
    new_lo = lo + child * width
    new_target = jnp.where(
        c <= 0, 0.0,
        jnp.clip((rank - cum) / jnp.maximum(c, 1e-30), 0.0, 1.0))
    stop = done | (total <= 0)
    lo = jnp.where(stop, lo, new_lo)
    hi = jnp.where(stop, hi, new_lo + width)
    target = jnp.where(stop, target, new_target)
    leaf_lo = jnp.where(stop, leaf_lo, leaf_lo + child * w)
    return lo, hi, target, leaf_lo, stop


def _monotone_in_q(vals, quantiles):
    """Monotone in q, like the host post-processing step."""
    order = np.argsort(quantiles, kind="stable")
    mono = jax.lax.cummax(vals[:, order], axis=1)
    return mono[:, np.argsort(order)]


@scoped("walk")
def _percentile_values_owned(config: FusedConfig, P_own, qrows, scale,
                             key, axis, n_dev, topo=None):
    """The quantile descent with the partition axis SHARDED over the
    mesh: each device walks only its owned block of ``P_own`` partitions
    (global partition ``axis_index * P_own + i``).

    Per level the collective protocol is: gather the owned walk bases
    (small [P, Q] int32 — every device's rows may hit any partition's
    walk), count children locally from this device's rows, then
    owner-scatter the [P, Q, b] counts so each owner receives exactly
    its block's totals — per-device ICI traffic O(P/n_dev·Q·b)
    instead of the replicated psum's O(P·Q·b). Both collectives go
    through ``parallel.sharded``'s topology-aware helpers (``topo``
    from the caller's mesh), so a hierarchical mesh keeps the scatter
    stage on ICI. Node noise is keyed by GLOBAL partition index, so
    the mesh walk is bit-identical to the single-chip walk given the
    same PRNG key."""
    # Lazy: parallel.sharded imports this module at module scope, and
    # this path only traces under a mesh sharded.py itself set up.
    from pipelinedp_tpu.parallel import sharded as psh
    qpk, leaf, kept = qrows
    b = quantile_tree_ops.DEFAULT_BRANCHING_FACTOR
    height = quantile_tree_ops.DEFAULT_TREE_HEIGHT
    quantiles = np.asarray([p / 100.0 for p in config.percentiles],
                           np.float32)
    Q = quantiles.shape[0]
    P = P_own * n_dev
    offset = jax.lax.axis_index(axis) * P_own
    pk_index = (offset + jnp.arange(P_own)).astype(jnp.uint32)

    lo = jnp.full((P_own, Q), float(config.min_value), jnp.float32)
    hi = jnp.full((P_own, Q), float(config.max_value), jnp.float32)
    target = jnp.broadcast_to(quantiles[None, :], (P_own, Q))
    leaf_lo = jnp.zeros((P_own, Q), jnp.int32)
    done = jnp.zeros((P_own, Q), bool)
    level_offset = 0
    for level in range(height):
        w = b**(height - 1 - level)
        base_own = leaf_lo // w  # [P_own, Q]
        with scope("combine"):
            base = psh.gather_blocks(base_own, axis, dim=0,
                                     topo=topo)  # [P, Q]
        counts = []
        for q in range(Q):
            slot = leaf // w - base[:, q][qpk]
            ok = kept & (slot >= 0) & (slot < b)
            seg = qpk * b + jnp.clip(slot, 0, b - 1)
            counts.append(
                jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                    num_segments=P * b).reshape(P, b))
        stacked = jnp.stack(counts, axis=1)
        with scope("combine"):
            raw = psh.scatter_to_owner(stacked, axis, dim=0, topo=topo)
        raw = raw.astype(jnp.float32)
        lo, hi, target, leaf_lo, done = _walk_level(
            config.noise_kind, key, scale, raw, base_own, level_offset,
            lo, hi, target, leaf_lo, done, b, w, pk_index=pk_index)
        level_offset += b**(level + 1)
    vals = lo + (hi - lo) * target  # [P_own, Q]
    return _monotone_in_q(vals, quantiles)


def _expand(mask, like):
    """Broadcasts a [N] mask against [N] or [N, D] data."""
    if like.ndim == 2:
        return mask[:, None]
    return mask


def _clip_values(config: FusedConfig, values):
    # Vectors are norm-clipped on the per-pk sum (like the reference's
    # add_noise_vector); per-partition-bound sums are clipped after the
    # segment sum. Only per-value bounds clip row-wise here.
    if (config.vector_size or config.per_partition_bounds or
            config.min_value is None):
        return values
    return jnp.clip(values, config.min_value, config.max_value)



def _release_noise_params(config: FusedConfig,
                          spec) -> dp_computations.ScalarNoiseParams:
    """The exact ScalarNoiseParams the generic combiners would build for
    this metric's spec — one noise calculus for both planes."""
    return dp_computations.ScalarNoiseParams(
        eps=spec.eps, delta=spec.delta,
        min_value=config.min_value, max_value=config.max_value,
        min_sum_per_partition=config.min_sum_per_partition,
        max_sum_per_partition=config.max_sum_per_partition,
        max_partitions_contributed=config.l0,
        max_contributions_per_partition=config.linf,
        noise_kind=config.noise_kind,
        max_contributions=config.max_contributions)


def _host_release(config: FusedConfig, specs, part, nseg,
                  rng: Optional[np.random.Generator],
                  rng_seed: Optional[int] = None, pk_index=None):
    """The scalar DP release, on host in float64: literally the
    ``dp_computations.compute_dp_*`` mechanisms the generic combiners
    call, vectorized over the pk axis. Reusing them (instead of a
    float32 device twin) keeps one release implementation for both
    planes, draws noise at full precision — float32 noise quantizes to
    a large aggregate's ULP grid — and inherits the hardened host noise
    path when ``set_secure_host_noise(True)``. ``part`` holds float64
    views of the fetched accumulator columns.

    VECTOR_SUM is the exception: its per-coordinate draws are batched
    DEVICE counter RNG (``ops/vector_noise.py``) keyed by the GLOBAL
    partition vocab index (``pk_index`` — kept indices in compact
    release, arange(P) otherwise) and the coordinate, so streamed,
    single-batch, fused and mesh releases of the same partition draw
    the same noise. ``rng_seed`` is the engine seed the vector key
    derives from; secure host noise keeps the hardened numpy path."""
    names = set(config.metrics)
    out = {}
    if "VARIANCE" in names or "MEAN" in names:
        snp = _release_noise_params(config, specs["mean_var"])
        # The device accumulated the normalized sums directly (fixed
        # point); everything here is float64.
        nsum = part["nsum"]
        if "VARIANCE" in names:
            dp_count, dp_sum, dp_mean, dp_var = (
                dp_computations.compute_dp_var(part["count"], nsum,
                                               part["nsumsq"], snp, rng))
            out["variance"] = dp_var
        else:
            dp_count, dp_sum, dp_mean = dp_computations.compute_dp_mean(
                part["count"], nsum, snp, rng)
        if "MEAN" in names:
            out["mean"] = dp_mean
        if "COUNT" in names:
            out["count"] = dp_count
        if "SUM" in names:
            out["sum"] = dp_sum
    else:
        if "COUNT" in names:
            out["count"] = dp_computations.compute_dp_count(
                part["count"], _release_noise_params(config,
                                                     specs["count"]), rng)
        if "SUM" in names:
            if config.per_partition_bounds:
                raw_sum = part["sum"]
                if config.selection is None:
                    # Public-partition parity with the generic path:
                    # every public partition receives one empty
                    # accumulator whose clipped sum is
                    # clip(0, min_sum, max_sum) (reference
                    # _add_empty_public_partitions +
                    # SumCombiner.create([])).
                    raw_sum = raw_sum + float(
                        np.clip(0.0, config.min_sum_per_partition,
                                config.max_sum_per_partition))
            else:
                # Raw sum from the normalized sum: sum(x) = sum(x - mid)
                # + count * mid, exactly, in float64.
                middle = dp_computations.compute_middle(
                    config.min_value, config.max_value)
                raw_sum = part["nsum"] + part["count"].astype(
                    np.float64) * middle
            out["sum"] = dp_computations.compute_dp_sum(
                raw_sum, _release_noise_params(config, specs["sum"]),
                rng)
    if "PRIVACY_ID_COUNT" in names:
        out["privacy_id_count"] = dp_computations.compute_dp_privacy_id_count(
            nseg, _release_noise_params(config, specs["privacy_id_count"]),
            rng)
    if "VECTOR_SUM" in names:
        spec = specs["vector_sum"]
        noise_params = dp_computations.AdditiveVectorNoiseParams(
            eps_per_coordinate=spec.eps / config.vector_size,
            delta_per_coordinate=spec.delta / config.vector_size,
            max_norm=config.vector_max_norm,
            l0_sensitivity=config.l0,
            linf_sensitivity=config.linf,
            norm_kind=config.vector_norm_kind,
            noise_kind=config.noise_kind)
        from pipelinedp_tpu.ops import noise as noise_ops
        if (noise_ops.secure_host_noise_enabled() and rng is None):
            # Hardened release: the snapping/discrete mechanisms live
            # on host — same batched call the generic combiner makes.
            out["vector_sum"] = dp_computations.add_noise_vector(
                part["vector_sum"], noise_params, rng)
        else:
            # Norm-clip on host float64 (identical to
            # add_noise_vector's clip), then batched device
            # counter-RNG draws keyed by (partition vocab index,
            # coordinate) scaled by the same calibrated per-coordinate
            # scale the numpy path uses.
            from pipelinedp_tpu.ops import vector_noise
            clipped = dp_computations._clip_vector(
                np.asarray(part["vector_sum"], dtype=np.float64),
                config.vector_max_norm, config.vector_norm_kind)
            out["vector_sum"] = vector_noise.add_vector_noise(
                clipped, noise_params, rng_seed, pk_index)
    return out


# ---------------------------------------------------------------------------
# Budget -> runtime inputs
# ---------------------------------------------------------------------------


def _noise_scales(config: FusedConfig,
                  specs: Dict[str, Any]) -> np.ndarray:
    """Device-side noise-scale inputs. Since the scalar release moved to
    the host float64 path (_host_release), the only scale the kernel
    still consumes is the percentile tree's per-level node-noise scale —
    always the LAST entry (consumed as ``noise_scales[-1]``). The budget
    is split evenly across tree levels, like the host tree
    (ops/quantile_tree.py:159-171)."""
    from pipelinedp_tpu.ops import noise as noise_ops

    if not config.percentiles:
        return np.zeros(0, dtype=np.float32)
    l0, linf = dp_computations.count_sensitivity_pair(
        config.l0, config.linf, config.max_contributions)
    spec = specs["percentile"]
    height = quantile_tree_ops.DEFAULT_TREE_HEIGHT
    eps_l = spec.eps / height
    if config.noise_kind == NoiseKind.LAPLACE:
        scale = noise_ops.laplace_scale(
            eps_l, dp_computations.compute_l1_sensitivity(l0, linf))
    else:
        scale = noise_ops.gaussian_sigma(
            eps_l, spec.delta / height,
            dp_computations.compute_l2_sensitivity(l0, linf))
    return np.asarray([scale], dtype=np.float32)


def selection_inputs(config: FusedConfig, eps: float, delta: float,
                     pre_threshold: Optional[int]):
    """(keep_table, threshold, scale, min_count) runtime inputs for the
    selection stage. Only the entries relevant to the configured strategy
    matter."""
    if config.selection is None:
        return np.zeros(2, np.float32), 0.0, 1.0, 0.0
    strategy = ps_ops.create_partition_selection_strategy(
        config.selection, eps, delta, config.selection_l0, pre_threshold)
    if isinstance(strategy, ps_ops.TruncatedGeometricPartitionStrategy):
        # probabilities() already folds in pre-thresholding; materialize
        # the effective table over [0, saturation + pre_threshold].
        size = strategy.keep_table.size + (pre_threshold or 0)
        table = strategy.probabilities(np.arange(size)).astype(np.float32)
        return table, 0.0, 1.0, 0.0
    thr = strategy.threshold
    min_count = 0.0
    if pre_threshold is not None:
        # Thresholding with pre-threshold: never keep below the
        # pre-threshold, else noisy(n - pre + 1) >= T
        # <=> noisy(n) >= T + pre - 1.
        thr = thr + pre_threshold - 1
        min_count = float(pre_threshold)
    if isinstance(strategy, ps_ops.LaplaceThresholdingPartitionStrategy):
        return np.zeros(2, np.float32), thr, strategy.noise_scale, min_count
    return np.zeros(2, np.float32), thr, strategy.noise_stddev, min_count


# ---------------------------------------------------------------------------
# Driver: budget wiring + lazy execution
# ---------------------------------------------------------------------------


def _metric_field_order(config: FusedConfig) -> List[str]:
    """MetricsTuple field order mirroring the reference compound combiner
    (VARIANCE > MEAN fold count/sum; then privacy_id_count, vector_sum)."""
    names = set(config.metrics)
    fields = []
    if "VARIANCE" in names:
        # Matches VarianceCombiner.compute_metrics dict-insertion order
        # (variance, count, sum, mean) so positional consumers see the
        # same layout on every backend.
        fields.append("variance")
        if "COUNT" in names:
            fields.append("count")
        if "SUM" in names:
            fields.append("sum")
        if "MEAN" in names:
            fields.append("mean")
    elif "MEAN" in names:
        fields.append("mean")
        if "COUNT" in names:
            fields.append("count")
        if "SUM" in names:
            fields.append("sum")
    else:
        if "COUNT" in names:
            fields.append("count")
        if "SUM" in names:
            fields.append("sum")
    if "PRIVACY_ID_COUNT" in names:
        fields.append("privacy_id_count")
    if "VECTOR_SUM" in names:
        fields.append("vector_sum")
    fields.extend(_percentile_field_names(config.percentiles))
    return fields


def request_budgets(config: FusedConfig, params: AggregateParams,
                    budget_accountant) -> Dict[str, Any]:
    """Requests exactly the budgets the reference combiner factory would
    (``combiners.py:652-721``): one mechanism per metric group, with the
    aggregation's budget weight."""
    mechanism_type = params.noise_kind.convert_to_mechanism_type()
    weight = params.budget_weight
    names = set(config.metrics)
    specs: Dict[str, Any] = {}

    def request(metric: str, internal_splits: int = 1):
        # Same split declarations as the generic factory: the release path
        # divides the granted budget evenly into this many sub-mechanisms,
        # which the PLD accountant composes individually. ``metric``
        # labels the mechanism in the privacy audit record, matching the
        # generic factory's labels.
        return budget_accountant.request_budget(
            mechanism_type, weight=weight, internal_splits=internal_splits,
            metric=metric)

    if "VARIANCE" in names:
        specs["mean_var"] = request("variance", internal_splits=3)
    elif "MEAN" in names:
        specs["mean_var"] = request("mean", internal_splits=2)
    else:
        if "COUNT" in names:
            specs["count"] = request("count")
        if "SUM" in names:
            specs["sum"] = request("sum")
    if "PRIVACY_ID_COUNT" in names:
        specs["privacy_id_count"] = request("privacy_id_count")
    if "VECTOR_SUM" in names:
        specs["vector_sum"] = request(
            "vector_sum", internal_splits=int(config.vector_size))
    if config.percentiles:
        # One budget for all percentiles, requested last — same order as
        # the generic factory (combiners.py:552-558).
        specs["percentile"] = request(
            "percentile",
            internal_splits=quantile_tree_ops.DEFAULT_TREE_HEIGHT)
    return specs


# Kept partitions fetched via the packed compact block; beyond this the
# (rare) full fetch runs instead. 8192 rows x ~10 columns x 4B = 320KB.
_COMPACT_FETCH_CAP = 8192


@instrumented_jit(phase="fetch", static_argnames=("num_partitions",
                                                  "cap"))
@scoped("compact")
def _compact_fetch_kernel(keep_pk, cols, num_partitions, cap, stats=None):
    """Device-side output compaction: stable-sorts kept partitions first
    (ascending pk index), gathers the first ``cap`` of every column and
    packs [meta; kept indices; columns...] into one int32 block — the
    kept count, the index map and all metric columns cross the
    high-latency host link in a single transfer. ``stats`` (int32 [k],
    the walk's ``WALK_STATS``) ride in the meta row after the kept
    count, the block widened to 1 + k where ``cap`` is narrower."""
    keep = keep_pk[:num_partitions].astype(jnp.int32)
    order = jnp.argsort(1 - keep, stable=True)
    sel = order[:cap]
    if stats is not None and cap < 1 + stats.shape[0]:
        sel = jnp.pad(sel, (0, 1 + stats.shape[0] - cap))
    width = sel.shape[0]
    meta = jnp.zeros(width, jnp.int32).at[0].set(jnp.sum(keep))
    if stats is not None:
        meta = meta.at[1:1 + stats.shape[0]].set(stats)
    gathered = [c[:num_partitions][sel] for c in cols]
    return jnp.stack([meta, sel.astype(jnp.int32)] + gathered)


def _assemble_output(config: FusedConfig, vocab, metric_arrays, rel_sel,
                     vocab_idx):
    """Released metric columns -> [(partition_key, MetricsTuple)].
    Column-wise conversion: one C-level tolist() per metric instead of a
    Python float() call per (partition, metric)."""
    fields = _metric_field_order(config)
    columns = []
    for f in fields:
        arr = metric_arrays[f]
        if arr.ndim == 1:
            columns.append(arr[rel_sel].tolist())
        else:
            columns.append(list(arr[rel_sel, :]))
    tuple_fields = tuple(fields)
    return [
        (vocab[i], _create_named_tuple_instance(
            "MetricsTuple", tuple_fields, vals))
        for i, vals in zip(vocab_idx.tolist(), zip(*columns))
    ]


def _record_selection_audit(strategy, pre: int, post: int,
                            path: str) -> None:
    """The selection-seam audit counters: pre/post-selection partition
    counts + one structured event per selection, feeding the run
    report's ``privacy.partition_selection`` section. Gated on the
    audit knob (``PIPELINEDP_TPU_AUDIT``); pure host-side bookkeeping —
    DP outputs are bit-identical on or off."""
    from pipelinedp_tpu import obs
    if not obs.audit.audit_enabled():
        return
    obs.inc("selection.partitions_pre", int(pre))
    obs.inc("selection.partitions_post", int(post))
    obs.event("selection.applied", strategy=str(strategy.value),
              pre=int(pre), post=int(post), path=path)


def _audit_expected_errors(config: FusedConfig, specs, metric_arrays,
                           rel_sel) -> None:
    """Per-metric expected relative error into the audit registry: the
    calibrated noise stddev (where the standard predictors apply)
    against the mean |released aggregate| — the machine-readable twin of
    the utility-analysis engine's ``error_expected``, captured at the
    release seam where both sides are known. Never raises."""
    from pipelinedp_tpu import obs
    if not obs.audit.audit_enabled():
        return
    try:
        names = set(config.metrics)
        stds: Dict[str, float] = {}
        if "VARIANCE" in names or "MEAN" in names:
            # The combiner splits the granted budget evenly into its
            # count / normalized-sum (/ sum-of-squares) sub-mechanisms;
            # predict the count leg's noise at that per-sub share.
            spec = specs["mean_var"]
            k = 3 if "VARIANCE" in names else 2
            sub = dataclasses.replace(
                _release_noise_params(config, spec),
                eps=spec.eps / k, delta=(spec.delta or 0.0) / k)
            stds["count"] = dp_computations.compute_dp_count_noise_std(sub)
        else:
            if "COUNT" in names:
                stds["count"] = dp_computations.compute_dp_count_noise_std(
                    _release_noise_params(config, specs["count"]))
            if "SUM" in names:
                stds["sum"] = dp_computations.compute_dp_sum_noise_std(
                    _release_noise_params(config, specs["sum"]))
        if "PRIVACY_ID_COUNT" in names:
            snp = _release_noise_params(config,
                                        specs["privacy_id_count"])
            l0, linf = snp.pid_count_sensitivities()
            stds["privacy_id_count"] = dp_computations._noise_std(
                snp.eps, snp.delta, l0, linf, snp.noise_kind)
        for field in _metric_field_order(config):
            arr = metric_arrays.get(field)
            if arr is None:
                continue
            arr = np.asarray(arr)
            if arr.ndim != 1:
                continue  # vector metrics: no scalar scale
            released = arr[rel_sel] if len(rel_sel) else arr[:0]
            scale = (float(np.mean(np.abs(released)))
                     if released.size else None)
            std = stds.get(field)
            rec = {"metric": field, "noise_stddev": std,
                   "aggregate_scale": scale,
                   "partitions": int(released.size)}
            if std is not None and scale:
                rec["expected_relative_error"] = float(std / scale)
            obs.audit.record_metric_error(rec)
    except Exception:
        pass  # an error estimate must never take the release down


def _record_walk_stats(timings, stats) -> None:
    """One release's ``WALK_STATS`` (host copy): the ``obs`` counter
    ``walk.compacted`` or ``walk.full_scatter``, and
    ``timings["walk_full_scatter"]`` (0 or 1: whether any subtree block
    took the full-row scatters) and ``timings["walk_relevant_rows"]``."""
    from pipelinedp_tpu import obs
    relevant, overflowed = (int(x) for x in stats)
    full = int(overflowed > 0)
    obs.inc("walk.full_scatter" if full else "walk.compacted")
    timings["walk_full_scatter"] = full
    timings["walk_relevant_rows"] = relevant


def _maybe_append_run_ledger(name: str = "engine.aggregate",
                             mesh=None) -> None:
    """Traced engine runs persist their run report into the durable
    ledger store (when a store directory resolves — see
    ``obs.store.ledger_dir``): the per-request audit record that
    otherwise dies with the process. Each append carries only this
    request's delta; ``mesh`` keys the fingerprint on the mesh shape
    the request actually ran on."""
    from pipelinedp_tpu import obs
    if not obs.trace_enabled():
        return
    obs.store.maybe_append_run_report(name, mesh=mesh)


def fused_fx_bits(config: FusedConfig, padded_rows: int) -> int:
    """The fixed-point lane width for a fused bucket, sized from the
    bucket's PADDED row edge — an upper bound on every member's real
    rows, so the batched kernel's static capacity guard holds for the
    whole bucket. A solo request sizes from its real row count instead
    and may pick wider lanes; both encodings are exact integer
    decompositions of the same quantized per-row values, so the folded
    float64 release is bit-identical either way (the lane plan is a
    capacity choice, never a precision choice)."""
    if _fixedpoint_layout(config) or _vector_fx(config):
        return _fx_plan(max(int(padded_rows), 1))[0]
    return 12


@dataclasses.dataclass
class FusionPrep:
    """One request's host-side preparation for a fused batch: exactly
    the runtime inputs a solo dispatch would feed the kernel, before
    any bucket padding. Built only by ``LazyFusedResult.prepare_fused``
    (after ``compute_budgets()``); consumed by the serve-fusion layer
    (``serve/fusion.py``), which pads members to the bucket edge and
    stacks them along the leading request axis."""
    lazy: "LazyFusedResult"
    encoded: EncodedData
    P: int
    P_pad: int
    scales: np.ndarray
    keep_table: np.ndarray
    thr: float
    s_scale: float
    min_count: float
    rows_per_uid: float
    key: Any

    def stack_signature(self) -> Tuple:
        """Aux-input shapes that must agree for requests to stack:
        bucketing already fixed (rows, partitions, fx_bits), but the
        selection keep-table length varies with the request's
        (eps, delta) and the scales vector with the metric set — the
        executor sub-groups a bucket's batch on this, so a mismatch
        splits the batch instead of failing it."""
        return (self.scales.shape, self.keep_table.shape,
                int(np.asarray(self.encoded.values).ndim))


class LazyFusedResult:
    """Iterable of (partition_key, MetricsTuple); runs the fused kernel on
    first iteration — after ``compute_budgets()``, honoring the two-phase
    protocol. Iterating again reuses the cached result."""

    def __init__(self, rows, params: AggregateParams, config: FusedConfig,
                 data_extractors, public_partitions, specs,
                 selection_spec, rng_seed: Optional[int] = None,
                 mesh=None, checkpoint=None, ingest_executor=None,
                 stream_cache=None):
        self._ingest_executor = ingest_executor
        self._stream_cache = stream_cache
        self._rows = rows
        self._params = params
        self._config = config
        self._extractors = data_extractors
        self._public = public_partitions
        self._specs = specs
        self._selection_spec = selection_spec
        self._rng_seed = rng_seed
        self._mesh = mesh
        self._checkpoint = checkpoint
        self._cache = None
        #: Serve-fusion seam: an EncodedData a fusion offer already
        #: built for exactly these rows/extractors — _execute consumes
        #: it instead of re-encoding, so a fused request that falls
        #: back to solo execution (singleton window, unfusable prep)
        #: never pays the O(rows) host encode twice.
        self._encoded_hint: Optional[EncodedData] = None
        #: host/device timing split of the last _execute, for bench.py.
        self.timings: Optional[Dict[str, float]] = None

    def __iter__(self):
        # Generator function: the body (and thus execution) is deferred
        # until the first next() — downstream generator expressions call
        # iter() at construction time, which must not trigger the kernel
        # before compute_budgets().
        if self._cache is None:
            self._cache = self._execute()
        yield from self._cache

    def rebind_rows(self, rows) -> None:
        """Sketch-first seam (``sketch/engine.py``): phase 2 of the
        two-phase unbounded-key path replaces the full input with the
        candidate-filtered rows before first iteration — budgets were
        registered against the ORIGINAL graph build, which is exactly
        the two-phase protocol's contract (specs are lazy; only the
        rows narrow). Refuses after execution: the cache would already
        embody the old rows."""
        if self._cache is not None:
            raise RuntimeError(
                "cannot rebind rows after the fused result executed")
        self._rows = rows
        self._encoded_hint = None

    def _execute(self):
        from pipelinedp_tpu import obs

        config = self._config
        params = self._params
        # Span-derived timing: the host_encode_s / device_s /
        # host_decode_s fields keep their names and semantics; they are
        # now views over the run tracer's "engine.*" span totals.
        tr = obs.run_tracer()
        with tr.span("engine.encode", cat="engine"):
            encoded = (self._encoded_hint if self._encoded_hint
                       is not None else
                       encode(self._rows, self._extractors,
                              config.vector_size, self._public,
                              require_pid=not
                              config.bounds_already_enforced))
        self.timings = {"host_encode_s": tr.total("engine.encode"),
                        "device_s": 0.0, "host_decode_s": 0.0}
        P = len(encoded.pk_vocab)
        if P == 0:
            return []
        scales = _noise_scales(config, self._specs)
        # Without privacy ids the selection user-count estimate divides by
        # the max rows one user may own (reference dp_engine.py:163-169).
        if config.bounds_already_enforced:
            rows_per_uid = float(params.max_contributions or
                                 params.max_contributions_per_partition)
        else:
            rows_per_uid = 1.0
        if self._selection_spec is not None:
            keep_table, thr, s_scale, min_count = selection_inputs(
                config, self._selection_spec.eps,
                self._selection_spec.delta, params.pre_threshold)
        else:
            keep_table, thr, s_scale, min_count = selection_inputs(
                config, 1.0, 1e-9, None)

        from pipelinedp_tpu import streaming
        if streaming.should_stream(config, encoded.n_rows, self._mesh):
            # Multi-batch ingest: the dataset exceeds one device batch.
            # Partials accumulate on host (int64 / folded float64),
            # selection runs once on device, release below as usual.
            with tr.span("engine.device", cat="engine",
                         path="streamed"):
                keep_np, part64, stream_stats = (
                    streaming.stream_partials_and_select(
                        config, encoded, scales, keep_table, thr,
                        s_scale, min_count, rows_per_uid,
                        self._rng_seed, mesh=self._mesh,
                        checkpoint=self._checkpoint,
                        executor=self._ingest_executor,
                        cache_bytes=self._stream_cache))
            self.timings["device_s"] = tr.total("engine.device")
            self.timings["stream_batches"] = stream_stats["n_batches"]
            if "resumed_from_batch" in stream_stats:
                self.timings["stream_resumed_from"] = (
                    stream_stats["resumed_from_batch"])
                self.timings["stream_checkpoint_saves"] = (
                    stream_stats["checkpoint_saves"])
            # Elastic recovery trail: reshard count + history reach the
            # run report/bench record, so a run that survived a device
            # loss says so (and at which chunk) instead of
            # masquerading as an uneventful capture.
            if stream_stats.get("mesh_reshards"):
                self.timings["stream_mesh_reshards"] = (
                    stream_stats["mesh_reshards"])
                self.timings["stream_reshard_history"] = (
                    stream_stats["reshard_history"])
            # Transfer/compute split: staging+enqueue wall vs the time
            # blocked waiting for kernel results (the overlap evidence).
            self.timings["stream_stage_s"] = stream_stats["stage_s"]
            self.timings["stream_fold_wait_s"] = stream_stats["fold_wait_s"]
            # Per-phase pass-A breakdown from the ingest executor: busy
            # time per phase vs the loop wall clock; overlap_frac > 0
            # means phase time was hidden inside the wall.
            for k in ("t_stage", "t_fold", "t_device", "t_total",
                      "overlap_frac", "executor"):
                self.timings[f"stream_{k}"] = stream_stats[k]
            if "pass_b_source" in stream_stats:
                self.timings["stream_pass_b"] = stream_stats["pass_b_source"]
                self.timings["stream_pass_b_rounds"] = (
                    stream_stats["pass_b_rounds"])
                # Sweep-planner evidence: how many stream traversals
                # pass B actually paid for how many (quantile-group x
                # partition-block) tiles, and the bytes re-shipped over
                # the host link past the device cache's prefix.
                for k in ("pass_b_sweeps", "pass_b_tiles",
                          "pass_b_tiles_per_sweep",
                          "pass_b_cached_batches",
                          "pass_b_reshipped_bytes",
                          "pass_b_sweep_s"):
                    self.timings[f"stream_{k}"] = stream_stats[k]
            with tr.span("engine.release", cat="engine"):
                part64 = {k: v[:P] for k, v in part64.items()}
                if self._public is not None:
                    rel_sel = vocab_idx = np.arange(P)
                else:
                    # Release ONLY the kept partitions, in ascending pk
                    # order — the same host-noise draw sequence as the
                    # single-batch compact fetch path, so a streamed
                    # run and a single-batch run with the same seed
                    # release bit-identical scalar values whenever
                    # their kept sets and accumulators agree.
                    kept_idx = np.flatnonzero(keep_np[:P])
                    part64 = {k: v[kept_idx]
                              for k, v in part64.items()}
                    rel_sel = np.arange(len(kept_idx))
                    vocab_idx = kept_idx
                # lint: disable=rng-purity(host-release rng seeded by the engine seed)
                rng = (np.random.default_rng(self._rng_seed)
                       if self._rng_seed is not None else None)
                metric_arrays = _host_release(
                    config, self._specs, part64,
                    part64["privacy_id_count_raw"], rng,
                    rng_seed=self._rng_seed, pk_index=vocab_idx)
                for qi, name in enumerate(
                        _percentile_field_names(config.percentiles)):
                    vals_q = stream_stats["percentile_values"][:P, qi]
                    metric_arrays[name] = (
                        vals_q if self._public is not None
                        else vals_q[vocab_idx])
                out = _assemble_output(config, encoded.pk_vocab,
                                       metric_arrays, rel_sel,
                                       vocab_idx)
            self.timings["host_decode_s"] = tr.total("engine.release")
            _audit_expected_errors(config, self._specs, metric_arrays,
                                   rel_sel)
            _maybe_append_run_ledger(mesh=self._mesh)
            return out

        with tr.span("engine.device", cat="engine", path="single_batch"):
            keep_pk, raw, fx_bits = _run_fused_kernel(
                config, encoded, scales, keep_table, thr, s_scale,
                min_count, rows_per_uid, self._rng_seed, self._mesh,
                tracer=tr)

            # From the kernel's dispatch to the blocking fetch's end: the
            # wait for the device program, the compaction and the d2h.
            with tr.span("engine.fetch", cat="engine"):
                # Fetching the outputs forces device execution; the fetch
                # is attributed to device_s, the float64 release + row
                # assembly to decode_s. All rank-1 outputs ride ONE stacked
                # transfer: one device-to-host round trip, not one per
                # column. The stack is int32 with float
                # columns BITCAST into it: integer lanes move bit-exactly,
                # whereas small ints bitcast to float32 become subnormals
                # that TPUs flush to zero (and a float32 CAST would corrupt
                # counts above 2^24).
                walk_stats = raw.pop(WALK_STATS, None)
                flat = sorted(k for k, v in raw.items() if v.ndim == 1)
                cols = []
                for name in flat:
                    arr = raw[name]
                    cols.append(arr if arr.dtype == jnp.int32 else
                                jax.lax.bitcast_convert_type(
                                    arr.astype(jnp.float32), jnp.int32))
                # With private selection most partitions are usually
                # dropped: compact ON DEVICE and fetch kept count + kept
                # indices + kept columns as ONE packed block — a single
                # transfer instead of a full [K, P] fetch plus extra round
                # trips. Past the static cap (a huge keyspace where
                # selection keeps >8192 keys) every column is fetched and
                # the kept rows are taken on host. Either way the release
                # holds ONLY the kept rows in ascending pk order, so the
                # seeded host noise draws are the same as the streamed
                # path's (and the serve-fusion path's) at any kept count.
                if self._public is None:
                    cap = min(P, _COMPACT_FETCH_CAP)
                    packed = np.asarray(_compact_fetch_kernel(
                        keep_pk, tuple(cols), P, cap, walk_stats))
                    n_keep = int(packed[0, 0])
                    if walk_stats is not None:
                        walk_stats = packed[0, 1:1 + walk_stats.shape[0]]
                    if n_keep <= cap:
                        stacked = packed[1:, :n_keep]
                    else:
                        full = np.asarray(jnp.stack(
                            [keep_pk.astype(jnp.int32)] + cols))[:, :P]
                        kept = np.flatnonzero(full[0] > 0)
                        stacked = np.concatenate(
                            [kept[None].astype(np.int32), full[1:, kept]])
                    kept_idx = stacked[0]
                    rel_sel = np.arange(len(kept_idx))
                    vocab_idx = kept_idx
                    kept_order = jnp.asarray(kept_idx)  # rank-2 gathers
                else:
                    lines = [keep_pk.astype(jnp.int32)] + cols
                    if walk_stats is not None:
                        # A last row, as wide as the padded axis (at
                        # least 8), carries the stats in the same fetch.
                        lines.append(jnp.zeros_like(lines[0]).at[
                            :walk_stats.shape[0]].set(walk_stats))
                    block = np.asarray(jnp.stack(lines))
                    if walk_stats is not None:
                        walk_stats = block[-1, :walk_stats.shape[0]]
                        block = block[:-1]
                    stacked = block[:, :P]
                    kept_idx = np.flatnonzero(stacked[0] > 0)
                    rel_sel = vocab_idx = np.arange(P)
                    kept_order = None
                fetched = {}
                for i, name in enumerate(flat):
                    col = stacked[1 + i]
                    fetched[name] = (col if raw[name].dtype == jnp.int32
                                     else col.view(np.float32))
                for name, arr in raw.items():  # rank-2 (vector) outputs
                    if arr.ndim != 1:
                        fetched[name] = (np.asarray(arr)[:P]
                                         if kept_order is None
                                         else np.asarray(arr[kept_order]))
        self.timings["device_s"] = tr.total("engine.device")
        self.timings["h2d_s"] = tr.total("engine.h2d")
        self.timings["fetch_s"] = tr.total("engine.fetch")
        if self._mesh is not None:
            self.timings["reshard_s"] = tr.total("engine.reshard")
        if walk_stats is not None:
            _record_walk_stats(self.timings, walk_stats)
        if config.selection is not None:
            # The selection seam: every vocab entry is a populated
            # partition, so P is the pre-selection count and the kept
            # index set is the post-selection count.
            _record_selection_audit(config.selection, P, len(kept_idx),
                                    "single_batch")

        return self._finish_release(encoded, P, fetched, fx_bits,
                                    rel_sel, vocab_idx)

    def _finish_release(self, encoded: EncodedData, P: int, fetched,
                        fx_bits: int, rel_sel, vocab_idx):
        """The scalar DP release tail, in float64 via the shared
        mechanisms — ONE implementation for the solo single-batch path
        and the serve-fusion batch path (bit-identity between them is
        the PARITY row 35 contract, so they must share this code, not
        mirror it). Integer columns stay integral: the hardened noise
        path dispatches on dtype (discrete Laplace for counts — no
        float noise bits), exactly like the generic combiners' int
        accumulators. ``fetched`` holds host copies of the device
        outputs already restricted to the rows ``rel_sel`` releases
        (kept rows in compact mode, the full [:P] axis otherwise)."""
        from pipelinedp_tpu import obs

        config = self._config
        tr = obs.run_tracer()
        with tr.span("engine.release", cat="engine"):
            part64 = {
                k: (v.astype(np.int64) if v.dtype.kind in "iu" else
                    v.astype(np.float64)) for k, v in fetched.items()
            }
            # Reassemble fixed-point value lanes into float64 columns.
            _fold_fixedpoint(config, part64, fx_bits)
            # lint: disable=rng-purity(host-release rng seeded by the engine seed)
            rng = (np.random.default_rng(self._rng_seed)
                   if self._rng_seed is not None else None)
            # The release rows ARE ``vocab_idx`` (the kept set, or
            # every row for public partitions): its global vocab
            # indices key the vector noise counters.
            metric_arrays = _host_release(config, self._specs, part64,
                                          part64["privacy_id_count_raw"],
                                          rng, rng_seed=self._rng_seed,
                                          pk_index=np.asarray(vocab_idx))
            for name in _percentile_field_names(config.percentiles):
                metric_arrays[name] = fetched[name]
            out = _assemble_output(config, encoded.pk_vocab,
                                   metric_arrays, rel_sel, vocab_idx)
        if self.timings is not None:
            self.timings["host_decode_s"] = tr.total("engine.release")
        _audit_expected_errors(config, self._specs, metric_arrays, rel_sel)
        _maybe_append_run_ledger(mesh=self._mesh)
        return out

    # --- serve-fusion seams (phase 1 / phase 2 of a fused execution) ---

    def prepare_fused(self, encoded: Optional[EncodedData] = None
                      ) -> Optional["FusionPrep"]:
        """Serve-fusion seam, phase 1: the host-side preparation a solo
        ``_execute`` would do before device dispatch — encode, noise
        scales, selection inputs, the per-request PRNG key — WITHOUT
        dispatching. Must run after ``compute_budgets()`` (the two-phase
        protocol), exactly like iteration. Returns None when this
        request cannot join a fused batch (sharded backend, streamed
        scale, empty vocabulary): the fusion layer then falls back to
        solo execution, visibly."""
        from pipelinedp_tpu import obs
        from pipelinedp_tpu.ops import noise as noise_ops

        config = self._config
        if self._mesh is not None:
            return None
        tr = obs.run_tracer()
        with tr.span("engine.encode", cat="engine"):
            if encoded is None:
                encoded = encode(
                    self._rows, self._extractors, config.vector_size,
                    self._public,
                    require_pid=not config.bounds_already_enforced)
        P = len(encoded.pk_vocab)
        if P == 0:
            return None
        from pipelinedp_tpu import streaming
        if streaming.should_stream(config, encoded.n_rows, self._mesh):
            return None
        self.timings = {"host_encode_s": tr.total("engine.encode"),
                        "device_s": 0.0, "host_decode_s": 0.0,
                        "fused": True}
        scales = _noise_scales(config, self._specs)
        if config.bounds_already_enforced:
            rows_per_uid = float(
                self._params.max_contributions or
                self._params.max_contributions_per_partition)
        else:
            rows_per_uid = 1.0
        if self._selection_spec is not None:
            keep_table, thr, s_scale, min_count = selection_inputs(
                config, self._selection_spec.eps,
                self._selection_spec.delta, self._params.pre_threshold)
        else:
            keep_table, thr, s_scale, min_count = selection_inputs(
                config, 1.0, 1e-9, None)
        seed = (self._rng_seed if self._rng_seed is not None else
                int(noise_ops._host_rng.integers(0, 2**31 - 1)))
        # lint: disable=rng-purity(seed protocol root key, pure in rng_seed)
        key = jax.random.PRNGKey(seed)
        return FusionPrep(
            lazy=self, encoded=encoded, P=P, P_pad=_pad_pow2(P),
            scales=np.asarray(scales), keep_table=np.asarray(keep_table),
            thr=float(thr), s_scale=float(s_scale),
            min_count=float(min_count), rows_per_uid=float(rows_per_uid),
            key=key)

    def finish_from_fused(self, prep: "FusionPrep", keep_np, raw_np,
                          fx_bits: int):
        """Serve-fusion seam, phase 2: finish THIS request from its
        slice of the batched kernel's outputs. Releases the rows the
        solo path releases (every row for public partitions, else the
        kept rows in ascending pk order) — which rows consume a seeded
        host rng's draws is part of the bit-identity contract — then
        runs the shared release tail
        and installs the result as the lazy cache (iteration returns
        it without dispatching a solo program)."""
        config = self._config
        P = prep.P
        raw_np = dict(raw_np)
        walk_stats = raw_np.pop(WALK_STATS, None)
        if walk_stats is not None and self.timings is not None:
            _record_walk_stats(self.timings, np.asarray(walk_stats))
        keep = np.asarray(keep_np)[:P]
        kept_idx = np.flatnonzero(keep > 0)
        if self._public is not None:
            fetched = {k: np.asarray(v)[:P] for k, v in raw_np.items()}
            rel_sel = vocab_idx = np.arange(P)
        else:
            # As the solo path: release ONLY the kept rows, ascending
            # pk order.
            fetched = {k: np.asarray(v)[:P][kept_idx]
                       for k, v in raw_np.items()}
            rel_sel = np.arange(len(kept_idx))
            vocab_idx = kept_idx
        if config.selection is not None:
            _record_selection_audit(config.selection, P, len(kept_idx),
                                    "fused_batch")
        out = self._finish_release(prep.encoded, P, fetched, fx_bits,
                                   rel_sel, vocab_idx)
        self._cache = out
        return out


def _run_fused_kernel(config: FusedConfig, encoded: EncodedData, scales,
                      keep_table, thr, s_scale, min_count, rows_per_uid,
                      rng_seed, mesh, tracer=None):
    """Shared encode→seed→dispatch scaffolding of the lazy results: one
    place owns the kernel/sharded invocation and the seed protocol.
    ``tracer`` (an ``obs`` tracer) times the transfer of the inputs,
    until they have landed, as ``engine.h2d``, and on a mesh the host
    re-shard before it as ``engine.reshard``."""
    from pipelinedp_tpu import obs
    from pipelinedp_tpu.ops import noise as noise_ops

    tracer = tracer if tracer is not None else obs.NOOP_TRACER

    P = len(encoded.pk_vocab)
    P_pad = _pad_pow2(P)
    seed = (rng_seed if rng_seed is not None else
            int(noise_ops._host_rng.integers(0, 2**31 - 1)))
    # lint: disable=rng-purity(seed protocol root key, pure in rng_seed)
    key = jax.random.PRNGKey(seed)
    # Lane plan from the GLOBAL row count (the mesh's cross-device psum
    # adds per-shard lane sums, so capacity is a global bound; padding
    # rows are masked to zero and never consume capacity); the same value
    # drives the host-side lane fold. Pipelines with no fixed-point value
    # columns (COUNT/PRIVACY_ID_COUNT-only, PERCENTILE, VECTOR_SUM,
    # select_partitions) skip the plan entirely — their int32 count
    # columns are exact to 2^31 rows and must not inherit the lane cap.
    # VECTOR_SUM joins the plan when its accumulator is 'fx' (the
    # coordinate lanes share the scalar capacity arithmetic).
    if _fixedpoint_layout(config) or _vector_fx(config):
        fx_bits, _ = _fx_plan(max(encoded.n_rows, 1))
    else:
        fx_bits = 12
    # The kernel-backend knob resolves HERE, outside jit, and rides in
    # as a static argument: jit caches by signature, so an env or seam
    # switch between calls re-traces instead of silently reusing
    # the other backend's program (and the cost observatory's table
    # keys the two signatures apart for before/after verdicts).
    from pipelinedp_tpu import plan as plan_mod
    kernel_backend = str(plan_mod.knob_value("kernel_backend"))
    if kernel_backend == "pallas" and config.percentiles:
        # The single-batch quantile walk builds its subtree counts
        # through the compacted/block-chunked ``_subtree_counts``
        # paths, which have no Pallas twin (only streamed pass B's
        # multi-tile binner does) — say so, out loud: a requested
        # backend silently not running is the one thing the knob must
        # never do. The fused per-pk reduction in this same program
        # still dispatches Pallas.
        obs.inc("kernel.fallbacks")
        obs.event("kernel.fallback", site="walk_subtree_counts",
                  reason="single_batch_walk",
                  percentiles=len(config.percentiles))
    if mesh is not None:
        from pipelinedp_tpu.parallel import sharded_fused_aggregate
        with obs.device_annotation("pdp.sharded_fused_aggregate"):
            keep_pk, raw = sharded_fused_aggregate(
                mesh, config, P_pad, encoded.pid, encoded.pk,
                encoded.values if config.needs_values else None,
                scales, keep_table, thr,
                s_scale, min_count, rows_per_uid, key, fx_bits,
                kernel_backend=kernel_backend, tracer=tracer)
        return keep_pk, raw, fx_bits
    with tracer.span("engine.h2d", cat="engine"):
        placed = pad_and_put(encoded, config.vector_size,
                             with_values=config.needs_values)
        scalars = (jnp.asarray(scales), jnp.asarray(keep_table),
                   jnp.float32(thr), jnp.float32(s_scale),
                   jnp.float32(min_count), jnp.float32(rows_per_uid))
        # The span ends when the inputs have landed, not when the
        # transfer is enqueued; the kernel waits for them anyway. A
        # dataset whose columns the device cache holds reads ~0 here.
        jax.block_until_ready((placed, scalars))
    with obs.device_annotation("pdp.fused_aggregate"):
        keep_pk, raw = fused_aggregate_kernel(
            config, P_pad, *placed, *scalars, key, fx_bits=fx_bits,
            kernel_backend=kernel_backend)
    return keep_pk, raw, fx_bits


class LazySelectResult:
    """Iterable of kept partition keys; runs the fused kernel (with an
    empty metric set — only bounding + selection) on first iteration."""

    def __init__(self, rows, params, data_extractors, spec, rng_seed,
                 mesh):
        self._rows = rows
        self._params = params
        self._extractors = data_extractors
        self._spec = spec
        self._rng_seed = rng_seed
        self._mesh = mesh
        self._cache = None

    def __iter__(self):
        if self._cache is None:
            self._cache = self._execute()
        yield from self._cache

    def _execute(self):
        params = self._params
        config = FusedConfig(
            metrics=(), noise_kind=NoiseKind.LAPLACE, linf=None,
            l0=params.max_partitions_contributed,
            per_partition_bounds=False, min_value=None, max_value=None,
            min_sum_per_partition=None, max_sum_per_partition=None,
            vector_size=None, vector_norm_kind=None, vector_max_norm=None,
            selection=params.partition_selection_strategy,
            bounds_already_enforced=False)
        encoded = encode(self._rows, self._extractors, None, None)
        P = len(encoded.pk_vocab)
        if P == 0:
            return []
        keep_table, thr, s_scale, min_count = selection_inputs(
            config, self._spec.eps, self._spec.delta, params.pre_threshold)
        from pipelinedp_tpu import streaming
        if streaming.should_stream(config, encoded.n_rows, self._mesh):
            keep_np, _, _ = streaming.stream_partials_and_select(
                config, encoded, np.zeros(1, np.float32), keep_table,
                thr, s_scale, min_count, 1.0, self._rng_seed,
                mesh=self._mesh)
            vocab = encoded.pk_vocab
            out = [vocab[i] for i in np.flatnonzero(keep_np[:P])]
            _maybe_append_run_ledger("engine.select_partitions",
                                     mesh=self._mesh)
            return out
        keep_pk, _, _ = _run_fused_kernel(
            config, encoded, np.zeros(0, np.float32), keep_table, thr,
            s_scale, min_count, 1.0, self._rng_seed, self._mesh)
        vocab = encoded.pk_vocab
        # Same packed compact fetch as the aggregation path: kept count
        # + kept indices in one small transfer instead of the full
        # [P] keep vector (selection typically keeps a tiny fraction).
        cap = min(P, _COMPACT_FETCH_CAP)
        packed = np.asarray(_compact_fetch_kernel(keep_pk, (), P, cap))
        n_keep = int(packed[0, 0])
        if n_keep > cap:
            keep_np = np.asarray(keep_pk)[:P]
            out = [vocab[i] for i in np.flatnonzero(keep_np)]
        else:
            out = [vocab[i] for i in packed[1, :n_keep].tolist()]
        _record_selection_audit(config.selection, P, len(out),
                                "select_partitions")
        _maybe_append_run_ledger("engine.select_partitions",
                                 mesh=self._mesh)
        return out


def build_fused_select_partitions(col, params, data_extractors,
                                  budget_accountant, report_gen,
                                  rng_seed=None,
                                  mesh=None) -> LazySelectResult:
    """Fused ``select_partitions`` (reference ``dp_engine.py:204-278``):
    the L0 bound over distinct (pid, pk) pairs and the batched selection
    are exactly the aggregation kernel with no metrics requested."""
    from pipelinedp_tpu.aggregate_params import MechanismType

    spec = budget_accountant.request_budget(
        mechanism_type=MechanismType.GENERIC, metric="partition_selection")
    strategy = params.partition_selection_strategy
    report_gen.add_stage(
        f"Cross-partition contribution bounding: for each privacy_id "
        f"randomly select max(actual_partition_contributed, "
        f"{params.max_partitions_contributed}) partitions (fused on "
        "device).")
    report_gen.add_stage(
        lambda: f"Private Partition selection: using {strategy.value} "
        f"method with (eps={spec.eps}, delta={spec.delta}) — batched over "
        "all partitions")
    return LazySelectResult(col, params, data_extractors, spec, rng_seed,
                            mesh)


def build_fused_aggregation(col, params: AggregateParams, data_extractors,
                            public_partitions, budget_accountant,
                            report_gen, rng_seed=None,
                            mesh=None, checkpoint=None,
                            ingest_executor=None,
                            stream_cache=None) -> LazyFusedResult:
    """Engine entry point for the fused plane: requests budgets (same
    pattern as the generic path, so the privacy semantics are identical),
    registers report stages, returns the lazy result."""
    from pipelinedp_tpu.aggregate_params import MechanismType

    public = public_partitions is not None
    config = FusedConfig.from_params(params, public)
    specs = request_budgets(config, params, budget_accountant)
    selection_spec = None
    if not public:
        selection_spec = budget_accountant.request_budget(
            mechanism_type=MechanismType.GENERIC,
            metric="partition_selection")

    if not config.bounds_already_enforced:
        if config.max_contributions is not None:
            report_gen.add_stage(
                f"User contribution bounding: randomly selected not more "
                f"than {config.max_contributions} contributions (fused on "
                "device).")
        else:
            report_gen.add_stage(
                f"Per-partition contribution bounding: for each privacy_id "
                f"and each partition, randomly select "
                f"max(actual_contributions_per_partition, {config.linf}) "
                f"contributions (fused on device).")
            report_gen.add_stage(
                f"Cross-partition contribution bounding: for each "
                f"privacy_id randomly select "
                f"max(actual_partition_contributed, {config.l0}) "
                "partitions (fused on device).")
    if public:
        report_gen.add_stage(
            "Public partition selection: dropped non public partitions; "
            "missing public partitions added as empty (dense pk axis).")
    else:
        strategy = params.partition_selection_strategy
        report_gen.add_stage(
            lambda: f"Private Partition selection: using {strategy.value} "
            f"method with (eps={selection_spec.eps}, "
            f"delta={selection_spec.delta}) — batched over all partitions")
    report_gen.add_stage(
        lambda: "Computed metrics "
        f"{sorted(set(m.lower() for m in config.metrics))} in one fused "
        "XLA program")
    return LazyFusedResult(col, params, config, data_extractors,
                           public_partitions, specs, selection_spec,
                           rng_seed=rng_seed, mesh=mesh,
                           checkpoint=checkpoint,
                           ingest_executor=ingest_executor,
                           stream_cache=stream_cache)
