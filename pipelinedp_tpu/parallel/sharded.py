"""Multi-chip fused aggregation via ``shard_map`` over a device mesh.

The reference's "distributed runtime" is the host framework's shuffle
(Beam ``GroupByKey``/Spark ``groupByKey`` — SURVEY.md §2.2/§5.8). The
TPU-native equivalent implemented here:

* Rows are sharded **by privacy id** over the mesh's ``data`` axis (host
  assigns ``hash(pid) % n_devices``), so contribution bounding — which
  must see all of one privacy unit's rows — is shard-local. This replaces
  shuffles 1 and 2 of the reference call stack with a local sort.
* The partition axis is sharded too: device ``d`` OWNS the contiguous
  block of ``P/n_devices`` partition ids starting at ``d*P/n_devices``
  (ids are dense-factorized, so block ownership is balanced). Each shard
  computes dense per-pk partials from its rows, then ONE
  ``psum_scatter`` over ICI hands every owner exactly its block's totals
  — the reference's shuffle 3 (``CombinePerKey`` key exchange,
  ``pipeline_backend.py:300-305``) as a collective. Per-device
  accumulator state and ICI traffic are O(P/n_devices), so adding chips
  adds partition capacity, not just row throughput.
* Partition selection and the percentile walk then run per-owner on the
  owned blocks. Selection randomness is drawn over the global axis and
  sliced, and percentile node noise is keyed by global partition index,
  so the mesh's keep decisions and walk match a single device with the
  same PRNG key bit-for-bit. The scalar DP release happens later, on
  host in float64 (``jax_engine.LazyFusedResult._host_release``) — the
  arrays returned here are raw (un-noised) accumulators, reassembled
  from the owner shards.

The same code runs on a virtual CPU mesh
(``--xla_force_host_platform_device_count``) for tests and on real
TPU slices; multi-host meshes extend the same program over DCN via jax's
global device mesh.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PSpec

from pipelinedp_tpu import jax_engine
from pipelinedp_tpu.obs.costs import instrumented_jit, scope

from jax import shard_map

# ---------------------------------------------------------------------------
# Mesh topology: the two-axis ("dcn", "ici") view over the 1-D data axis
# ---------------------------------------------------------------------------

#: Knob seam (plan/knobs.py "mesh_topology"): "flat" (one exchange over
#: the whole axis — the historical default, cold start byte-identical),
#: "hier" (two-stage ICI-then-DCN exchange) or "auto" (hier iff the
#: mesh spans more than one host). Kept as a module constant purely as
#: the registry's test seam — consumers go through knobs.value().
_MESH_TOPOLOGY = "flat"

#: Simulated host count for single-process meshes (tests/bench): splits
#: the device list into N contiguous "hosts" so the hierarchical
#: exchange — and the DCN byte attribution — can be exercised on the
#: 8-device CPU proxy without a second process.
_MESH_HOSTS_ENV = "PIPELINEDP_TPU_MESH_HOSTS"


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """How a mesh's 1-D ``data`` axis maps onto the physical fabric.

    Under ``hier`` the mesh's device order is INTERLEAVED: position
    ``p = j * n_hosts + h`` holds host ``h``'s ``j``-th device. That
    makes the per-host ("ici") groups the strided position sets
    ``{p : p % n_hosts == h}`` and the cross-host ("dcn") groups the
    contiguous runs ``[j * n_hosts, (j+1) * n_hosts)`` — and it is
    exactly what makes the two-stage owner-block reduction land
    position ``p`` on global block ``p``, the same owner mapping as
    the flat single-stage ``psum_scatter`` (see
    :func:`combine_shards`). It also means ``reform_mesh``'s
    divisor-prefix shrink policy regroups survivors WITHIN their host
    first: a prefix of the interleaved order is itself a valid
    interleaved order at the same host count."""
    mode: str            #: "flat" | "hier"
    n_hosts: int
    per_host: int
    simulated: bool = False  #: hosts simulated via _MESH_HOSTS_ENV

    @property
    def hierarchical(self) -> bool:
        """True when the two-stage exchange actually differs from the
        flat one (both axes non-degenerate)."""
        return (self.mode == "hier" and self.n_hosts > 1
                and self.per_host > 1)

    @property
    def multi_host(self) -> bool:
        return self.n_hosts > 1

    @property
    def n_devices(self) -> int:
        return self.n_hosts * self.per_host


def _flat_topology(n_devices: int, n_hosts: int = 1,
                   simulated: bool = False) -> MeshTopology:
    n_hosts = max(1, n_hosts)
    return MeshTopology("flat", n_hosts,
                        max(1, n_devices // n_hosts), simulated)


#: Registered topology per mesh, keyed by the ORDERED global device-id
#: tuple. The interleaved hier order differs from the flat order on
#: every non-degenerate mesh, so distinct topologies produce distinct
#: meshes (and distinct static jit signatures — a knob flip re-traces).
#: Meshes built directly by tests (plain ``Mesh(...)``) are absent and
#: fall back to flat: exactly the pre-topology behavior.
_TOPOLOGIES: Dict[Tuple[int, ...], MeshTopology] = {}


def _mesh_key(mesh: Mesh) -> Tuple[int, ...]:
    return tuple(int(d.id) for d in mesh.devices.reshape(-1))


def topology_of(mesh: Optional[Mesh]) -> MeshTopology:
    """The topology registered for ``mesh`` at :func:`make_mesh` /
    :func:`reform_mesh` time, or a flat fallback for meshes built
    elsewhere (test back-compat: a plain ``Mesh`` behaves exactly as
    before this layer existed)."""
    if mesh is None:
        return _flat_topology(1)
    topo = _TOPOLOGIES.get(_mesh_key(mesh))
    if topo is not None:
        return topo
    return _flat_topology(int(mesh.devices.size))


def _host_groups(devices) -> Tuple[List[List], bool]:
    """(device groups by host, simulated?). Real grouping is by
    ``process_index`` (CPU proxy: processes are "hosts" — the same
    boundary jax.distributed's collectives cross over DCN); the
    ``PIPELINEDP_TPU_MESH_HOSTS`` env splits a single-process device
    list into N contiguous simulated hosts instead, so the two-stage
    exchange is testable in one process."""
    raw = os.environ.get(_MESH_HOSTS_ENV, "")
    if raw:
        try:
            n = int(raw)
        except ValueError:
            n = 0
        if n > 1 and len(devices) % n == 0:
            k = len(devices) // n
            return [list(devices[h * k:(h + 1) * k])
                    for h in range(n)], True
    groups: Dict[int, List] = {}
    for d in devices:
        groups.setdefault(int(getattr(d, "process_index", 0)),
                          []).append(d)
    return [groups[pi] for pi in sorted(groups)], False


def resolved_topology_mode() -> str:
    """The ``mesh_topology`` knob in force (env > seam > plan >
    default) — the string bench stamps on records."""
    from pipelinedp_tpu.plan import knobs
    return str(knobs.value("mesh_topology"))


def _build_topology(devices) -> Tuple[List, MeshTopology]:
    """(possibly reordered device list, topology) for a new mesh under
    the resolved ``mesh_topology`` knob. ``hier`` interleaves the
    device order (see :class:`MeshTopology`); unequal per-host counts
    fall back to flat with a ``mesh.topology_fallback`` event —
    the two-stage reduction needs a rectangular (dcn, ici) grid."""
    from pipelinedp_tpu import obs
    mode = resolved_topology_mode()
    hosts, simulated = _host_groups(devices)
    n_hosts = len(hosts)
    if mode == "auto":
        mode = "hier" if n_hosts > 1 else "flat"
    if mode != "hier" or n_hosts <= 1:
        return list(devices), _flat_topology(len(devices), n_hosts,
                                             simulated)
    sizes = {len(g) for g in hosts}
    if len(sizes) != 1:
        obs.event("mesh.topology_fallback", reason="ragged_hosts",
                  hosts=n_hosts, sizes=sorted(sizes))
        return list(devices), _flat_topology(len(devices), n_hosts,
                                             simulated)
    k = len(hosts[0])
    order = [hosts[h][j] for j in range(k) for h in range(n_hosts)]
    return order, MeshTopology("hier", n_hosts, k, simulated)


def _register(mesh: Mesh, topo: MeshTopology) -> None:
    _TOPOLOGIES[_mesh_key(mesh)] = topo


def _ici_groups(topo: MeshTopology) -> List[List[int]]:
    """One group per host: the strided positions of that host's
    devices under the interleaved order (member index j = the device's
    within-host slot)."""
    H, k = topo.n_hosts, topo.per_host
    return [[j * H + h for j in range(k)] for h in range(H)]


def _dcn_groups(topo: MeshTopology) -> List[List[int]]:
    """One group per within-host slot: the contiguous position run
    ``[j*H, (j+1)*H)`` — exactly one device of every host (member
    index h = the host)."""
    H, k = topo.n_hosts, topo.per_host
    return [[j * H + h for h in range(H)] for j in range(k)]


# --- comms accounting -------------------------------------------------------

def _payload_bytes(x) -> int:
    try:
        return int(x.size) * int(np.dtype(x.dtype).itemsize)
    except Exception:
        return 0


def _record_exchange(kind: str, per_device_bytes: int, group_size: int,
                     crosses_hosts: bool, n_groups: int = 1) -> None:
    """Analytic byte estimate for one traced collective: a
    reduce-scatter or all-gather of B per-device bytes over a group of
    g moves ~B*(g-1) bytes per group (ring schedule); an all-reduce
    (psum) moves twice that. A group that spans hosts is attributed
    entirely to DCN, a within-host group entirely to ICI — the
    attribution that makes ``dcn_bytes(hier) < dcn_bytes(flat)`` a
    measured number on a multi-host mesh (and dcn_bytes == 0 on a true
    single-host one).

    Recorded at TRACE time, once per compiled exchange (warm re-
    dispatches of a cached executable reuse the traced program): an
    analytic estimate for the heartbeat/bench artifacts, not a
    per-dispatch wire meter."""
    if group_size <= 1:
        return
    per_group = per_device_bytes * (group_size - 1)
    if kind == "psum":
        per_group *= 2
    total = per_group * max(1, n_groups)
    from pipelinedp_tpu import obs
    obs.inc("comms.collectives")
    obs.inc("comms.dcn_bytes" if crosses_hosts else "comms.ici_bytes",
            int(total))


# --- the exchange policy ----------------------------------------------------

def combine_shards(x, axis, dim, replicate, topo=None):
    """The ONE cross-shard exchange policy for every streaming kernel:
    owner-block ``psum_scatter`` along ``dim`` (state/ICI O(P/n_dev))
    when each device should keep only its owned partition block, a
    replicating ``psum`` (every device holds the full result) when the
    output must be host-addressable everywhere — multi-process meshes
    (another process's owner block is not host-addressable) and pass-B
    tile blocks (at most the sub-histogram byte cap by construction,
    and ``psum`` has no divisibility constraint on the block size).

    With a hierarchical ``topo`` the exchange splits into a fixed-order
    two-stage reduction: an owner-block ``psum_scatter`` over each
    host's ``ici`` group first, then one batch-boundary block exchange
    over the ``dcn`` groups — per-host scatter traffic stays on ICI
    and only ``1/per_host`` of the payload crosses DCN. Both stages
    run XLA's deterministic fixed reduction tree per group, and every
    payload this policy combines on the parity-tested paths is exact
    integer data (packed int32 lane stacks, histograms, subtree
    counts), so hier and flat land on BIT-IDENTICAL results — the
    mesh_topology knob's dp-safety (PARITY row 43). The one documented
    exception is the float32 ``vector_accumulator='f32'`` plane, whose
    partial-sum grouping was already regroup-sensitive (use ``fx`` for
    exactness — PARITY row 39).

    Owner mapping under ``hier``: position ``p = j*H + h`` scatters to
    ici-group member ``j`` (k-way block ``j``), then to dcn-group
    member ``h`` (H-way sub-block ``h`` of block ``j``) — i.e. global
    block ``j*H + h == p``, exactly the flat mapping."""
    topo = topo if topo is not None else _flat_topology(1)
    if not topo.hierarchical:
        n_dev = topo.n_devices
        if replicate:
            _record_exchange("psum", _payload_bytes(x), n_dev,
                             topo.multi_host)
        else:
            _record_exchange("reduce_scatter", _payload_bytes(x),
                             n_dev, topo.multi_host)
        if replicate:
            return jax.lax.psum(x, axis)
        return jax.lax.psum_scatter(x, axis, scatter_dimension=dim,
                                    tiled=True)
    H, k = topo.n_hosts, topo.per_host
    ici, dcn = _ici_groups(topo), _dcn_groups(topo)
    size = int(x.shape[dim])
    bytes_in = _payload_bytes(x)
    if replicate:
        if size % k:
            # The replicating psum has no divisibility constraint and
            # the callers rely on that (pass-B tile blocks); a payload
            # the ici split cannot tile keeps the flat exchange.
            _record_exchange("psum", bytes_in, topo.n_devices, True)
            return jax.lax.psum(x, axis)
        # reduce-scatter on ICI, block all-reduce on DCN, all-gather
        # back on ICI: the full payload crosses DCN only as 1/k blocks.
        _record_exchange("reduce_scatter", bytes_in, k, False,
                         n_groups=H)
        y = jax.lax.psum_scatter(x, axis, scatter_dimension=dim,
                                 axis_index_groups=ici, tiled=True)
        _record_exchange("psum", bytes_in // k, H, True, n_groups=k)
        y = jax.lax.psum(y, axis, axis_index_groups=dcn)
        _record_exchange("all_gather", bytes_in // k, k, False,
                         n_groups=H)
        return jax.lax.all_gather(y, axis, axis=dim,
                                  axis_index_groups=ici, tiled=True)
    # Owner-block scatter: stage 1 within each host (ICI), stage 2
    # across hosts (DCN) on the k-times-smaller blocks.
    _record_exchange("reduce_scatter", bytes_in, k, False, n_groups=H)
    y = jax.lax.psum_scatter(x, axis, scatter_dimension=dim,
                             axis_index_groups=ici, tiled=True)
    _record_exchange("reduce_scatter", bytes_in // k, H, True,
                     n_groups=k)
    return jax.lax.psum_scatter(y, axis, scatter_dimension=dim,
                                axis_index_groups=dcn, tiled=True)


def gather_blocks(x, axis, dim=0, topo=None):
    """Hierarchy-aware ``all_gather`` along ``dim`` (tiled): the
    reassembly dual of :func:`combine_shards`'s owner-block scatter,
    used by the percentile walk's per-level base fetch and the
    megasweep's multi-process output replication. Under ``hier`` the
    small owner blocks cross DCN first (one device per host fetches
    each foreign block once), then fan out within each host over ICI —
    concatenation order is position order in both stages, so the
    result is byte-identical to the flat gather."""
    topo = topo if topo is not None else _flat_topology(1)
    if not topo.hierarchical:
        _record_exchange("all_gather", _payload_bytes(x),
                         topo.n_devices, topo.multi_host)
        return jax.lax.all_gather(x, axis, axis=dim, tiled=True)
    H, k = topo.n_hosts, topo.per_host
    bytes_in = _payload_bytes(x)
    # DCN first: dcn group j's members hold blocks [j*H, (j+1)*H) —
    # gathering over the contiguous group concatenates a contiguous
    # global run. Then each host's ici group holds runs j=0..k-1 in
    # member order; gathering concatenates them into the full axis.
    _record_exchange("all_gather", bytes_in, H, True, n_groups=k)
    y = jax.lax.all_gather(x, axis, axis=dim,
                           axis_index_groups=_dcn_groups(topo),
                           tiled=True)
    _record_exchange("all_gather", bytes_in * H, k, False, n_groups=H)
    return jax.lax.all_gather(y, axis, axis=dim,
                              axis_index_groups=_ici_groups(topo),
                              tiled=True)


def scatter_to_owner(x, axis, dim=0, topo=None):
    """Owner-block reduce-scatter along ``dim`` — :func:`combine_shards`
    with ``replicate=False``, named for call sites (the walk's
    per-level count exchange) that are always owner-sharded."""
    return combine_shards(x, axis, dim, False, topo=topo)


def make_mesh(n_devices: Optional[int] = None, axis_name: str = "data"
              ) -> Mesh:
    from pipelinedp_tpu import obs
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    devices, topo = _build_topology(devices)
    obs.event("mesh.created", n_devices=len(devices),
              axis_name=axis_name,
              platform=devices[0].platform if devices else None,
              topology=topo.mode, hosts=topo.n_hosts,
              per_host=topo.per_host, simulated_hosts=topo.simulated)
    mesh = Mesh(np.asarray(devices), (axis_name,))
    _register(mesh, topo)
    return mesh


def reform_mesh(mesh: Mesh, axis_name: Optional[str] = None
                ) -> Optional[Mesh]:
    """Re-form ``mesh`` after a participant loss: return a smaller mesh
    over the surviving devices, or None when nothing survives to shrink
    to (a 1-device mesh has no survivors to re-form from — the caller
    re-raises the loss).

    Policy — the new size always DIVIDES the old size, which is what
    lets the elastic resume regroup the saved per-(batch, shard) row
    counts by summing contiguous cell groups (``ingest.assign.
    regroup_cells``) instead of recomputing the assignment:

    * multi-process mesh (a ``jax.distributed`` peer died): fall back
      to THIS process's local devices. The survivor's local mesh is
      single-process, so the streaming kernels switch back to the
      owner-block ``psum_scatter`` exchange and no collective ever
      waits on the dead peer again.
    * single-controller mesh (a device dropped): keep the largest
      proper-divisor prefix of the device list — half, for the
      power-of-two meshes the replay guarantee already assumes.

    Topology is preserved across the shrink: the interleaved ``hier``
    order puts host ``h``'s ``j``-th device at position ``j*H + h``,
    so a prefix whose length divides by the host count is itself a
    valid ``hier`` interleave at the same host count — survivors
    regroup WITHIN their host first (each host sheds its highest-slot
    devices), and the two-stage exchange keeps working on the smaller
    mesh. A prefix the host count does not divide — or a multi-process
    death, where the survivor falls back to its own local (single-host)
    devices — degrades to ``flat``.
    """
    from pipelinedp_tpu import obs
    axis_name = axis_name or mesh.axis_names[0]
    old_topo = topology_of(mesh)
    old_n = int(mesh.devices.size)
    if getattr(mesh, "is_multi_process", False):
        devices = list(jax.local_devices())
    else:
        if old_n <= 1:
            return None
        survivors = int(max(d for d in range(1, old_n)
                            if old_n % d == 0))
        devices = list(mesh.devices.reshape(-1)[:survivors])
    if not devices or len(devices) >= old_n:
        return None
    if (old_topo.mode == "hier" and old_topo.n_hosts > 1
            and not getattr(mesh, "is_multi_process", False)
            and len(devices) % old_topo.n_hosts == 0):
        new_topo = MeshTopology("hier", old_topo.n_hosts,
                                len(devices) // old_topo.n_hosts,
                                old_topo.simulated)
    else:
        new_topo = _flat_topology(len(devices))
    new = Mesh(np.asarray(devices), (axis_name,))
    _register(new, new_topo)
    obs.inc("mesh.reformed")
    obs.event("mesh.reformed", old_devices=old_n,
              new_devices=int(new.devices.size), axis_name=axis_name,
              platform=devices[0].platform,
              topology=new_topo.mode, hosts=new_topo.n_hosts,
              per_host=new_topo.per_host)
    return new


def put_global(host, sharding):
    """Place ``host`` (one array, or a tuple of arrays) onto
    ``sharding`` WITHOUT jax's hidden cross-process collective.

    ``jax.device_put`` of an uncommitted array onto a non-fully-
    addressable sharding first runs ``multihost_utils.assert_equal`` —
    a broadcast-and-compare that dispatches a full-array psum over the
    GLOBAL mesh per call. Those hidden collectives (a) ship every
    staged batch across DCN a second time, and (b) interleave with the
    kernel's own all-reduces on the asynchronous dispatch stream, where
    a reordering makes the two processes' gloo pairs exchange
    mismatched ops (``op.preamble.length <= op.nbytes`` aborts — the
    historical multihost flake the rendezvous rewrite alone could not
    close). Every caller here already stages the IDENTICAL host array
    on every process (the staging layout is a deterministic function of
    the shared dataset), so the equality check buys nothing: build the
    global array from each device's own slice instead — zero
    collectives dispatched.
    """
    if isinstance(host, (tuple, list)):
        return tuple(put_global(a, sharding) for a in host)
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(host, sharding)
    arr = np.asarray(host)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


@instrumented_jit(phase="engine", static_argnames=(
    "config", "num_partitions", "mesh", "fx_bits", "kernel_backend"))
def _sharded_kernel(config, num_partitions, mesh, pid, pk, values, valid,
                    noise_scales, keep_table, sel_threshold, sel_scale,
                    sel_min_count, sel_rows_per_uid, key, fx_bits=7,
                    kernel_backend="xla"):
    """``num_partitions`` is the GLOBAL (padded) pk axis, a multiple of
    the mesh size; outputs come back partition-sharded over the mesh."""
    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    topo = topology_of(mesh)

    def local_fn(pid, pk, values, valid, noise_scales, keep_table,
                 sel_threshold, sel_scale, sel_min_count,
                 sel_rows_per_uid, key):
        # The SAME 3-way key split as the single-chip kernel
        # (``fused_aggregate_kernel``) and the streamed path, so the
        # mesh's selection draws and percentile node noise are
        # bit-identical to both for the same seed; only the bounding
        # stream is folded per shard (each shard needs distinct
        # sampling randomness, and with non-binding caps bounding
        # keeps every row regardless).
        # lint: disable=rng-purity(root split seam, pure in the run seed)
        k_bound_g, k_sel, k_noise = jax.random.split(key, 3)
        # lint: disable=rng-purity(per-shard bound key: fold of the shard index)
        k_bound = jax.random.fold_in(k_bound_g, jax.lax.axis_index(axis))
        part, part_nseg, qrows = jax_engine._partials(
            config, num_partitions, pid, pk, values, valid, k_bound,
            fx_bits, kernel_backend=kernel_backend)
        # Cross-chip exchange: each device keeps only the accumulator
        # block it owns (the percentile walk runs its own per-level
        # gather + owner-scatter protocol internally, with the same
        # topology).
        def to_owner(x):
            with scope("combine"):
                return combine_shards(x, axis, 0, False, topo=topo)

        part = jax.tree.map(to_owner, part)
        part_nseg = to_owner(part_nseg)
        return jax_engine._selection_and_metrics(
            config, num_partitions // n_dev, part, part_nseg,
            noise_scales, keep_table, sel_threshold, sel_scale,
            sel_min_count, sel_rows_per_uid, k_sel, k_noise, qrows=qrows,
            pk_axis=axis, pk_axis_size=n_dev, pk_topo=topo)

    shard = PSpec(axis)
    repl = PSpec()
    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(shard, shard, shard, shard, repl, repl, repl, repl,
                  repl, repl, repl),
        out_specs=shard,
        check_vma=False)
    return mapped(pid, pk, values, valid, noise_scales, keep_table,
                  sel_threshold, sel_scale, sel_min_count,
                  sel_rows_per_uid, key)


def reshard_rows(pid: np.ndarray, columns, n_dev: int):
    """Rows of every column grouped by shard ``fmix32(pid) % n_dev``,
    input order kept inside a shard, each shard zero-padded to
    ``per_shard = _pad_rows(largest shard)`` rows. Returns (the columns
    as ``n_dev * per_shard`` rows, rows per shard, per_shard).

    Hash before the modulo: raw ids pass through the encode step
    unchanged, and id families sharing a residue class (all-even user
    ids, snowflake ids with fixed low bits) would otherwise pile every
    row onto one device. The native counting scatter does it in O(n);
    where its library cannot load, or the mesh has more than 256
    devices, numpy's radix grouping gives the same arrays. The ledger
    counts each call as ``reshard.native`` or ``reshard.fallback``."""
    from pipelinedp_tpu import native, obs
    pad_rows = jax_engine._pad_rows
    try:
        out = native.shard_rows(pid, columns, n_dev, pad_rows)
    except native.NativeUnavailableError as e:
        obs.inc("reshard.fallback")
        obs.event("reshard.fallback", reason=str(e), n_dev=n_dev)
    else:
        obs.inc("reshard.native")
        return out

    from pipelinedp_tpu.ingest.assign import group_rows_by_cell
    from pipelinedp_tpu.ops.segment import fmix32
    shard_of_row = fmix32(pid.astype(np.uint32)) % np.uint32(n_dev)
    order, counts = group_rows_by_cell(shard_of_row, n_dev)
    per_shard = pad_rows(int(counts.max()) if len(pid) else 1)
    ends = np.cumsum(counts)
    sharded = []
    for col in columns:
        out = np.empty((n_dev * per_shard,) + col.shape[1:], col.dtype)
        for d in range(n_dev):
            lo = d * per_shard
            out[lo:lo + counts[d]] = col[order[ends[d] - counts[d]:ends[d]]]
            out[lo + counts[d]:lo + per_shard] = 0
        sharded.append(out)
    return sharded, counts, per_shard


def sharded_fused_aggregate(mesh: Mesh, config, num_partitions: int,
                            pid: np.ndarray, pk: np.ndarray,
                            values: Optional[np.ndarray],
                            noise_scales, keep_table, sel_threshold,
                            sel_scale, sel_min_count, sel_rows_per_uid,
                            key, fx_bits: int = 7,
                            kernel_backend: str = "xla", tracer=None):
    """Host entry: re-shards rows by hash(pid), pads each shard to a
    common length, places arrays over the mesh and runs the sharded
    kernel. Returns (keep_pk[P], accumulator dict) with the partition
    axis sharded over the mesh (device d owns block d); the scalar
    release happens downstream on host. Every input row is valid; the
    pad rows are not. ``tracer`` (an ``obs`` tracer) times the host
    re-shard as ``engine.reshard`` and the placement on the mesh, until
    the arrays have landed, as ``engine.h2d``."""
    from pipelinedp_tpu import obs
    tracer = tracer if tracer is not None else obs.NOOP_TRACER
    n_dev = mesh.devices.size
    # Owner blocks must tile the pk axis evenly. When this rounding is a
    # no-op (any power-of-two mesh: the padded axis is a power of two),
    # the mesh's selection draws are bit-identical to single-chip; a mesh
    # size that does NOT divide the padded axis widens it, so the draws
    # differ from single-chip (still valid DP, just not replay-identical).
    num_partitions = -(-num_partitions // n_dev) * n_dev
    with tracer.span("engine.reshard", cat="engine"):
        # Config never reads values (COUNT-style / select_partitions):
        # the value column is zeros, with nothing to re-shard.
        sharded, counts, per_shard = reshard_rows(
            pid, [c for c in (pid, pk, values) if c is not None], n_dev)
        pid_s, pk_s = sharded[:2]
        values_s = sharded[2] if values is not None else None
        # Each shard's rows are valid, its pad rows are not.
        valid_s = (np.arange(per_shard) < counts[:, None]).ravel()

    with tracer.span("engine.h2d", cat="engine"):
        sharding = NamedSharding(mesh, PSpec(mesh.axis_names[0]))
        placed = put_global(
            (pid_s, pk_s,
             np.zeros(n_dev * per_shard, np.float32) if values_s is None
             else values_s, valid_s), sharding)
        scalars = (jnp.asarray(noise_scales), jnp.asarray(keep_table),
                   jnp.float32(sel_threshold), jnp.float32(sel_scale),
                   jnp.float32(sel_min_count), jnp.float32(sel_rows_per_uid))
        # The span ends when the arrays have landed, not when the
        # transfer is enqueued; the kernel waits for them anyway.
        jax.block_until_ready((placed, scalars))
    return _sharded_kernel(config, num_partitions, mesh, *placed, *scalars,
                           key, fx_bits=fx_bits,
                           kernel_backend=kernel_backend)
