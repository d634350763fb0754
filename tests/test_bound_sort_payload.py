"""The bounding sort carries the columns it permutes.

``_partials`` sorts by (pid, hash(pid, pk), tie bits, row index) with pk
and rank-1 values riding the same ``lax.sort`` as payload operands, so
no gather applies its order:

* its outputs (``part``, ``part_nseg``, ``qrows``) are bit-identical to
  the former computation, kept here as ``former_partials``:
  ``jnp.lexsort`` of (pid, hash, tie bits), then one gather per column,
  in every bounding mode and value shape, with padded rows, under
  ``jax.vmap`` (the serve batch kernel) and per shard of the CPU mesh;
* the compiled SUM/MEAN program has one sort under ``bound_sort`` and
  nothing under ``bound_permute``; VECTOR_SUM's [N, D] values still
  gather there;
* the counters ``bound.payload_sort`` and ``bound.gathers`` say, per
  trace, whether the sort carried the columns and whether any work is
  left under ``bound_permute``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as PSpec

import pipelinedp_tpu as pdp
from pipelinedp_tpu import jax_engine, obs, parallel
from pipelinedp_tpu.obs import costs
from pipelinedp_tpu.ops import counter_rng
from pipelinedp_tpu.ops import segment as seg_ops

P = 64
D = 3

_BOUNDS = dict(max_partitions_contributed=3,
               max_contributions_per_partition=2)

CONFIGS = {
    "count": dict(metrics=[pdp.Metrics.COUNT], **_BOUNDS),
    "sum_mean": dict(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN],
        min_value=0.0, max_value=5.0, **_BOUNDS),
    "variance_percentile": dict(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.VARIANCE,
                 pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90)],
        min_value=0.0, max_value=5.0, **_BOUNDS),
    "per_partition_sum": dict(
        metrics=[pdp.Metrics.SUM], min_sum_per_partition=0.0,
        max_sum_per_partition=6.0, **_BOUNDS),
    "total_cap": dict(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        min_value=0.0, max_value=5.0, max_contributions=4),
    "vector_sum": dict(
        metrics=[pdp.Metrics.VECTOR_SUM], vector_size=D,
        vector_max_norm=4.0, vector_norm_kind=pdp.NormKind.L2, **_BOUNDS),
}


def _config(name):
    return jax_engine.FusedConfig.from_params(
        pdp.AggregateParams(**CONFIGS[name]), public=False)


def _columns(seed, n, n_valid, vector=False):
    """Padded columns on which every cap binds: 16 rows a pid over six
    of its partitions (segments of about three rows against a linf of
    2, six segments against an l0 of 3, 16 rows against a total cap of
    4); the padded rows carry values of their own, which no output may
    read."""
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n // 16, n).astype(np.int32)
    pk = ((pid * 7 + rng.integers(0, 6, n)) % P).astype(np.int32)
    shape = (n, D) if vector else (n,)
    values = rng.uniform(-1.0, 6.0, shape).astype(np.float32)
    valid = np.arange(n) < n_valid
    return pid, pk, values, valid


def former_partials(config, pid, pk, values, valid, key, fx_bits):
    """``_partials`` as it was before the sort carried its columns: a
    stable ``jnp.lexsort`` by (pid, hash(pid, pk), tie bits), then a
    gather of every column by its order, and in the total-cap branch a
    second lexsort by (pid, tie bits) with a gather of pid."""
    n = pid.shape[0]
    k_tie, k_salt, k_m = jax.random.split(key, 3)
    salt = jax.random.bits(k_salt, (), dtype=jnp.uint32)
    tiebreak = counter_rng.row_bits(k_tie, n)
    big_pid = jnp.where(valid, pid, seg_ops.PAD_ID)
    big_pk = jnp.where(valid, pk, seg_ops.PAD_ID)
    hpk = seg_ops.fmix32(
        seg_ops.fmix32(big_pid.astype(jnp.uint32) ^ salt) ^
        big_pk.astype(jnp.uint32))
    sort_idx = jnp.lexsort((tiebreak, hpk, big_pid))
    spid = big_pid[sort_idx]
    spk = big_pk[sort_idx]
    svalues = values[sort_idx] if config.needs_values else values

    idx = jnp.arange(n)
    svalid = idx < jnp.sum(valid.astype(jnp.int32))
    new_pid = (idx == 0) | (spid != jnp.roll(spid, 1))
    new_seg = new_pid | (spk != jnp.roll(spk, 1))
    if config.max_contributions is not None:
        tie_m = counter_rng.row_bits(k_m, n)
        order_m = jnp.lexsort((tie_m, big_pid))
        mpid = big_pid[order_m]
        new_pid_m = (idx == 0) | (mpid != jnp.roll(mpid, 1))
        keep_sorted = (seg_ops.rank_in_run(new_pid_m) <
                       config.max_contributions)
        keep_m = jnp.zeros(n, bool).at[order_m].set(keep_sorted)
        keep_row = svalid & keep_m[sort_idx]
        wk = jnp.cumsum(keep_row.astype(jnp.int32))
        seg_start = seg_ops.run_starts(new_seg)
        kept_before_seg = wk[seg_start] - keep_row[seg_start]
        seg_marker = keep_row & (wk == kept_before_seg + 1)
    else:
        linf_cap = config.linf if config.linf is not None else n
        row_keep = svalid & (seg_ops.rank_in_run(new_seg) < linf_cap)
        keep_l0 = seg_ops.run_ordinal_in_group(new_seg,
                                               new_pid) < config.l0
        keep_row = row_keep & keep_l0
        seg_marker = new_seg & svalid & keep_l0

    clipped = jax_engine._clip_values(config, svalues)
    masked = jnp.where(jax_engine._expand(keep_row, clipped), clipped, 0.0)
    pk_safe = jnp.where(svalid, spk, 0)
    contrib = None
    if config.per_partition_bounds:
        seg_ord = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
        seg_total = jax.ops.segment_sum(masked, seg_ord, num_segments=n)
        contrib = jnp.where(
            seg_marker,
            jnp.clip(seg_total[seg_ord], config.min_sum_per_partition,
                     config.max_sum_per_partition), 0.0)
    part, part_nseg = jax_engine._reduce_per_pk(
        config, pk_safe, masked, keep_row, contrib, P,
        seg_marker=seg_marker, fx_bits=fx_bits)
    qrows = (jax_engine._qrows(config, spk, svalues, keep_row)
             if config.percentiles else None)
    return part, part_nseg, qrows


def _partials_fn(config, n, partials=None):
    """``_partials`` (or ``former_partials``) of ``config`` at ``n``
    padded rows, as the kernels call it."""
    fx_bits = jax_engine.fused_fx_bits(config, n)

    def run(pid, pk, values, valid, key):
        if partials is None:
            return jax_engine._partials(config, P, pid, pk, values, valid,
                                        key, fx_bits)
        return partials(config, pid, pk, values, valid, key, fx_bits)
    return run


def _assert_bit_identical(got, want):
    got_leaves, got_tree = jax.tree.flatten(got)
    want_leaves, want_tree = jax.tree.flatten(want)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _both(wrap, config, n, args):
    """The program ``wrap`` builds around ``_partials``, and the same
    program around ``former_partials``, each jitted and run on
    ``args``."""
    got = jax.jit(wrap(_partials_fn(config, n)))(*args)
    want = jax.jit(wrap(_partials_fn(config, n, former_partials)))(*args)
    return got, want


CASES = [("count", 2048), ("sum_mean", 2048),
         ("variance_percentile", 2048), ("per_partition_sum", 2048),
         ("total_cap", 2048), ("vector_sum", 2048), ("sum_mean", 1500),
         ("total_cap", 1500), ("variance_percentile", 1500)]


@pytest.mark.parametrize("name,n_valid", CASES,
                         ids=[f"{c}-{v}" for c, v in CASES])
def test_partials_match_lexsort_then_gather(name, n_valid):
    config = _config(name)
    n = 2048
    cols = _columns(11, n, n_valid, vector=name == "vector_sum")
    got, want = _both(lambda f: f, config, n,
                      (*cols, jax.random.PRNGKey(5)))
    part, part_nseg, qrows = got
    assert int(np.asarray(part_nseg).sum()) > 0
    assert (qrows is not None) == bool(config.percentiles)
    _assert_bit_identical(got, want)


@pytest.mark.parametrize("name", ["sum_mean", "total_cap", "vector_sum"])
def test_partials_under_vmap_match(name):
    """The serve batch kernel's shape: one request axis, each request with
    its own rows, padding and key."""
    config = _config(name)
    n = 1024
    cols = [_columns(s, n, v, vector=name == "vector_sum")
            for s, v in ((1, 1024), (2, 700), (3, 333))]
    batch = [jnp.stack([c[i] for c in cols]) for i in range(4)]
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    got, want = _both(jax.vmap, config, n, (*batch, keys))
    _assert_bit_identical(got, want)


@pytest.mark.parametrize("name", ["sum_mean", "variance_percentile",
                                  "total_cap"])
def test_partials_per_shard_of_the_mesh_match(name):
    """``_partials`` as the sharded kernel calls it: inside ``shard_map``
    on the CPU mesh, with the bounding key folded per shard."""
    mesh = parallel.make_mesh(4)
    axis = mesh.axis_names[0]
    config = _config(name)
    n = 4096
    cols = _columns(13, n, 3500)

    def on_the_mesh(partials):
        def local(pid, pk, values, valid, key):
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            part, nseg, qrows = partials(pid, pk, values, valid, key)
            return jax.tree.map(lambda x: x[None], (part, nseg, qrows))

        shard = PSpec(axis)
        return shard_map(local, mesh=mesh,
                         in_specs=(shard,) * 4 + (PSpec(),),
                         out_specs=shard, check_vma=False)

    got, want = _both(on_the_mesh, config, n // 4,
                      (*cols, jax.random.PRNGKey(3)))
    _assert_bit_identical(got, want)


def fused_program_text(name, n=4096, sharding=None):
    """The compiled single-chip program of config ``name`` at ``n``
    padded rows, for the CPU or, given a described chip's
    ``sharding``, for that chip."""
    config = _config(name)
    f32 = jnp.float32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    values = (n, D) if name == "vector_sum" else (n,)
    return jax_engine.fused_aggregate_kernel.lower(
        config, P, spec((n,), jnp.int32), spec((n,), jnp.int32),
        spec(values, f32), spec((n,), jnp.bool_), spec((0,), f32),
        spec((32,), f32), spec((), f32), spec((), f32), spec((), f32),
        spec((), f32), spec((2,), jnp.uint32),
        fx_bits=jax_engine.fused_fx_bits(config, n)).compile().as_text()


def bounding_ops(hlo_text):
    """(sort instructions mapped to ``bound_sort``, instructions mapped to
    ``bound_permute``) of one compiled program."""
    _, table = costs.hlo_scope_table(hlo_text)
    sorts = [i.group(2) for i in map(costs._HLO_INSTRUCTION.match,
                                     hlo_text.splitlines())
             if i and table.get(i.group(2)) == "bound_sort"
             and " sort(" in i.string]
    return sorts, [i for i, s in table.items() if s == "bound_permute"]


def assert_bounding_structure(name, hlo_text):
    """One sort under ``bound_sort``. A SUM/MEAN program has nothing
    under ``bound_permute`` and gathers nothing by the sort's order;
    VECTOR_SUM's [N, D] values keep their gather under
    ``bound_permute``. Returns the instructions mapped to it."""
    sorts, permute = bounding_ops(hlo_text)
    assert len(sorts) == 1
    gathers = [ln for ln in hlo_text.splitlines()
               if " gather(" in ln and "/bound_permute/" in ln]
    if name == "vector_sum":
        assert gathers
    else:
        assert permute == [] and gathers == []
    return permute


@pytest.mark.parametrize("name", ["sum_mean", "vector_sum"])
def test_program_structure(name):
    """On the CPU; ``tests/test_tpu_compile.py`` checks the chip's."""
    assert_bounding_structure(name, fused_program_text(name))


@pytest.mark.parametrize("name,gathers", [
    ("count", 0), ("sum_mean", 0), ("variance_percentile", 0),
    ("total_cap", 1), ("vector_sum", 1)])
def test_each_trace_counts_its_path(name, gathers):
    """Every trace's sort carries pk; the total-cap keep bits and
    VECTOR_SUM's values still leave work under ``bound_permute``."""
    config = _config(name)
    n = 512
    cols = _columns(4, n, n, vector=name == "vector_sum")
    before = dict(obs.ledger().counters)
    jax.jit(_partials_fn(config, n))(*cols, jax.random.PRNGKey(0))
    after = obs.ledger().counters
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("bound.payload_sort", "bound.gathers")}
    assert delta == {"bound.payload_sort": 1, "bound.gathers": gathers}
