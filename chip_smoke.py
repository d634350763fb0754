#!/usr/bin/env python
"""One-process smoke of the DP aggregation path on a TPU.

``python chip_smoke.py`` drives the system's main path once, through the
entry points a user calls, at the repo's BASELINE sizes (``BASELINE.md``)
on one chip, and checks every output against an exact numpy answer on
the same data. The data is synthesized from ``--seed``.

Phases (one chip):

* ``flagship`` — COUNT+SUM+MEAN over a MovieLens-25M-shaped dataset
  (25M rows, 162k privacy ids, 59k Zipf-skewed partitions) at eps=1e5;
* ``quantiles`` — PERCENTILE(50, 90, 99)+VARIANCE, 10M rows over 100k
  partitions (BASELINE config 4);
* ``streamed`` — the quantile workload again, forced through at least
  three ingest chunks (pass A and pass B); its release must equal the
  ``quantiles`` release bit for bit;
* ``sweep`` — ``analysis.perform_utility_analysis``: 1,000
  configurations over 500k rows;
* ``pallas`` — ``kernel_backend=pallas`` on the streamed quantile
  workload (against the ``streamed`` phase's XLA release) and on
  VECTOR_SUM at D=256 (against an XLA run): at least one Pallas
  dispatch, no fallback, bit-identical releases;
* ``serve`` — a warm ``serve.Service`` answers 4 requests from 2
  tenants and debits each tenant's budget.

``--chips 4`` runs only the sharded path instead: ``flagship`` and
``quantiles`` on ``JaxBackend(mesh=parallel.make_mesh(4))``.

The first step checks ``jax.devices()``: anything but a TPU exits
nonzero before any work. Every phase runs even when an earlier one
failed, so one run reports every fault; any failure exits nonzero. The
last stdout line is ``{"ok": true, "device": {...}}`` and is printed
only when every phase passed.
"""

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: eps of the COUNT/SUM/MEAN and VECTOR_SUM releases: their noise is
#: then far below the tolerances of the checks.
BIG_EPS = 1e5
#: eps of the quantile workloads. The tree's node noise scales with
#: max_partitions_contributed * height / eps; at 1e5 the clamped noise
#: of a small partition's empty nodes outweighs its top 1% of rows and
#: carries p99 past the data, and its VARIANCE noise reaches ~3 (both
#: seen on the CPU at 400k rows, PR 21). At 1e9 every released
#: partition is held to one rank and one leaf.
Q_EPS = 1e9
DELTA = 1e-6
VALUE_HI = 10.0
#: Quantile-tree leaf width on [0, VALUE_HI]: branching 16, height 4.
LEAF = VALUE_HI / 16**4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The BASELINE sizes (``BASELINE.md``): MovieLens-25M's 25M ratings
    by 162k users over 59k movies, config 4's 10M rows over 100k
    partitions, config 5's sweep at 1,000 configurations."""
    flagship_rows: int = 25_000_000
    flagship_users: int = 162_000
    flagship_parts: int = 59_000
    flagship_l0: int = 256
    q_rows: int = 10_000_000
    q_users: int = 200_000
    q_parts: int = 100_000
    q_l0: int = 128
    sweep_rows: int = 500_000
    sweep_configs: int = 1_000
    vector_rows: int = 250_000
    vector_parts: int = 4_096
    vector_d: int = 256


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# Start-up: the chip, the repo, the compile cache
# --------------------------------------------------------------------------


def require_tpu(n_chips: int):
    """The first step on the device: a TPU with at least ``n_chips``
    devices, or exit nonzero. No probe child (it could not get the chip
    this process holds) and no CPU fallback."""
    from pipelinedp_tpu.resilience.health import hold_backend
    devices = hold_backend()
    if devices[0].platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU: jax found {len(devices)} "
            f"{devices[0].platform} device(s)\n")
        sys.exit(2)
    if len(devices) < n_chips:
        sys.stderr.write(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, jax found {len(devices)}\n")
        sys.exit(2)
    return devices


def import_repo():
    """The package of THIS checkout, never an installed copy: run alone,
    without the repo beside it, the smoke fails."""
    sys.path.insert(0, REPO)
    import pipelinedp_tpu
    where = os.path.dirname(os.path.abspath(pipelinedp_tpu.__file__))
    if os.path.dirname(where) != REPO:
        raise SystemExit(f"chip_smoke: pipelinedp_tpu imported from "
                         f"{where}, not from this checkout ({REPO})")
    return pipelinedp_tpu


# --------------------------------------------------------------------------
# Data and exact answers
# --------------------------------------------------------------------------


def bounded_dataset(seed: int, n_rows: int, n_users: int, n_parts: int,
                    max_parts: int, dim: int = 0):
    """Rows in which every privacy id holds at most ``max_parts`` rows,
    each in a distinct partition: bounding at ``max_partitions_contributed
    = max_parts`` and ``max_contributions_per_partition = 1`` drops
    nothing, so the exact per-partition answer is the numpy one.

    Rows per id follow a heavy tail (a Pareto weight, capped), as the
    ratings per user of MovieLens do. Each id covers a run of partition
    ranks from a Zipf-distributed start, so low ranks (shuffled into
    partition ids) are the popular partitions."""
    import pipelinedp_tpu as pdp
    assert n_rows <= n_users * max_parts and max_parts <= n_parts
    rng = np.random.default_rng(seed)
    w = rng.pareto(1.5, n_users) + 1.0
    k = np.clip(np.round(w / w.mean() * (n_rows / n_users)), 1,
                max_parts).astype(np.int64)
    while k.sum() != n_rows:
        diff = int(n_rows - k.sum())
        if diff > 0:
            idx = np.flatnonzero(k < max_parts)[:diff]
            k[idx] += 1
        else:
            idx = np.flatnonzero(k > 1)[:-diff]
            k[idx] -= 1
    user = np.repeat(np.arange(n_users, dtype=np.int64), k)
    j = np.arange(n_rows, dtype=np.int64) - np.repeat(np.cumsum(k) - k, k)
    start = (rng.zipf(1.3, n_users) - 1) % n_parts
    pk = rng.permutation(n_parts)[(start[user] + j) % n_parts]
    shape = (n_rows, dim) if dim else (n_rows,)
    lo = -1.0 if dim else 0.0
    values = rng.uniform(lo, 1.0 if dim else VALUE_HI, shape)
    order = rng.permutation(n_rows)
    return pdp.ArrayDataset(privacy_ids=user[order],
                            partition_keys=pk[order].astype(np.int64),
                            values=values[order])


def zipf_dataset(seed: int, n_rows: int, n_users: int, n_parts: int):
    import pipelinedp_tpu as pdp
    rng = np.random.default_rng(seed)
    return pdp.ArrayDataset(
        privacy_ids=rng.integers(0, n_users, n_rows),
        partition_keys=(rng.zipf(1.3, n_rows) % n_parts).astype(np.int64),
        values=rng.uniform(0.0, VALUE_HI, n_rows))


def released(result, fields):
    """(keys, {field: float64 column}) of a released aggregation."""
    rows = list(result)
    keys = np.fromiter((k for k, _ in rows), np.int64, len(rows))
    cols = {f: np.asarray([getattr(m, f) for _, m in rows], np.float64)
            for f in fields}
    return keys, cols


class Check:
    """Collects named checks; a phase passes when all of them hold."""

    def __init__(self):
        self.failed = []
        self.notes = []

    def that(self, ok, what: str) -> None:
        ok = bool(ok)
        self.notes.append(f"{what}: {'ok' if ok else 'FAILED'}")
        if not ok:
            self.failed.append(what)

    def err(self, name: str, got, want, tol: float) -> None:
        """max |got - want| <= tol (element-wise, over every entry)."""
        e = float(np.max(np.abs(np.asarray(got) - np.asarray(want)),
                         initial=0.0))
        self.that(e <= tol, f"{name} max err {e:.3g} <= {tol:g}")


def check_kept(chk: Check, keys, users_per_part) -> None:
    """Private selection at eps=1e5 keeps every partition with two or
    more privacy ids and never an empty one (a one-id partition may go
    either way: its keep probability is bounded by delta)."""
    populated = np.flatnonzero(users_per_part > 0)
    must = np.flatnonzero(users_per_part >= 2)
    chk.that(np.isin(keys, populated).all(), "kept partitions populated")
    chk.that(np.isin(must, keys).all(),
             f"all {len(must)} partitions with >= 2 ids kept "
             f"({len(keys)} kept)")


def check_sums(chk: Check, keys, cols, ds) -> None:
    """COUNT / SUM / MEAN against the exact numpy answer. The tolerances
    hold Laplace noise at eps=1e5 with up to 256 partitions per id,
    plus the 24-bit fixed-point quantization of the sums."""
    pk = ds.partition_keys
    n_parts = int(pk.max()) + 1
    count = np.bincount(pk, minlength=n_parts)[keys]
    total = np.bincount(pk, weights=ds.values, minlength=n_parts)[keys]
    tol_count, tol_sum = 0.5, 3.0
    chk.err("count", cols["count"], count, tol_count)
    chk.err("sum", cols["sum"], total, tol_sum)
    # The mean's error scales as 1/count: compare it times the count.
    chk.err("mean x count", (cols["mean"] - total / count) * count, 0.0,
            tol_sum + VALUE_HI * tol_count)


def check_quantiles(chk: Check, keys, cols, ds) -> None:
    """Every released partition. With ``n`` rows, percentile ``q`` walks
    the quantile tree to the leaf of the order statistic of rank
    ceil(q/100 * n); at ``Q_EPS`` the noisy node counts move that rank
    by less than one. So each percentile lies within one leaf of the
    order statistics one rank either side (one rank: a rank that lands
    exactly on a count boundary may go to either neighbour), and is
    monotone in q inside the clip range. VARIANCE against numpy: its
    noise at ``Q_EPS`` stays below 1e-3 on every partition."""
    pk, v = ds.partition_keys, ds.values
    n_parts = int(pk.max()) + 1
    order = np.lexsort((v, pk))
    sv = v[order]
    count = np.bincount(pk, minlength=n_parts)
    start = (np.cumsum(count) - count)[keys]
    n = count[keys]
    chk.that(len(keys) > 0 and (n > 0).all(),
             f"{len(keys)} of {len(keys)} released partitions checked "
             f"({n.min() if len(n) else 0} to {n.max() if len(n) else 0}"
             " rows)")
    qs = (50, 90, 99)
    got = np.stack([cols[f"percentile_{q}"] for q in qs])
    chk.that((np.diff(got, axis=0) >= 0).all() and (got >= 0).all() and
             (got <= VALUE_HI).all(), "percentiles monotone, in range")
    for q, g in zip(qs, got):
        k = np.ceil(q / 100.0 * n).astype(np.int64) - 1
        lo = sv[start + np.clip(k - 1, 0, n - 1)] - LEAF
        hi = sv[start + np.clip(k + 1, 0, n - 1)] + LEAF
        bad = int(np.sum((g < lo) | (g > hi)))
        chk.that(bad == 0, f"p{q} within one rank and one leaf "
                 f"({bad} of {len(keys)} outside)")
    s1 = np.bincount(pk, weights=v, minlength=n_parts)[keys]
    s2 = np.bincount(pk, weights=v * v, minlength=n_parts)[keys]
    var = s2 / n - (s1 / n)**2
    chk.err("variance", cols["variance"], var, 1e-3)


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------


def aggregate(ds, params, backend, eps=BIG_EPS):
    import pipelinedp_tpu as pdp
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=DELTA)
    result = pdp.DPEngine(acc, backend).aggregate(ds, params,
                                                  pdp.DataExtractors())
    acc.compute_budgets()
    list(result)  # run it
    return result


def on_device(chk: Check, result) -> None:
    """The fused device program ran: only it records ``device_s``; the
    host generator path (LocalBackend's) has no timings at all."""
    t = getattr(result, "timings", None) or {}
    chk.that(t.get("device_s", 0.0) > 0.0,
             f"device program ran (device_s={t.get('device_s')})")


@contextlib.contextmanager
def env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update({k: str(v) for k, v in kv.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def counters():
    from pipelinedp_tpu import obs
    return dict(obs.ledger().snapshot()["counters"])


def flagship_params(l0):
    import pipelinedp_tpu as pdp
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0, max_contributions_per_partition=1,
        min_value=0.0, max_value=VALUE_HI)


def quantile_params(l0):
    import pipelinedp_tpu as pdp
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90),
                 pdp.Metrics.PERCENTILE(99), pdp.Metrics.VARIANCE],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0, max_contributions_per_partition=1,
        min_value=0.0, max_value=VALUE_HI)


Q_FIELDS = ("percentile_50", "percentile_90", "percentile_99", "variance")


def phase_flagship(ctx, chk):
    s = ctx.sizes
    ds = ctx.flagship_dataset()
    res = aggregate(ds, flagship_params(s.flagship_l0), ctx.backend())
    on_device(chk, res)
    keys, cols = released(res, ("count", "sum", "mean"))
    check_kept(chk, keys, np.bincount(ds.partition_keys,
                                      minlength=s.flagship_parts))
    check_sums(chk, keys, cols, ds)
    return f"{s.flagship_rows} rows, {len(keys)} partitions released"


def phase_quantiles(ctx, chk):
    s = ctx.sizes
    ds = ctx.quantile_dataset()
    res = aggregate(ds, quantile_params(s.q_l0), ctx.backend(), Q_EPS)
    on_device(chk, res)
    keys, cols = released(res, Q_FIELDS)
    check_kept(chk, keys, np.bincount(ds.partition_keys,
                                      minlength=s.q_parts))
    check_quantiles(chk, keys, cols, ds)
    ctx.unstreamed = (keys, cols)
    return f"{s.q_rows} rows, {len(keys)} partitions released"


def streamed_quantiles(ctx, chk):
    """The quantile workload through at least three ingest chunks (pass
    A and pass B): (keys, cols) of its release, after the checks that
    it streamed and ran on the device."""
    s = ctx.sizes
    ds = ctx.quantile_dataset()
    ds.invalidate_cache()
    with env(PIPELINEDP_TPU_STREAM_CHUNK=-(-s.q_rows // 4)):
        res = aggregate(ds, quantile_params(s.q_l0), ctx.backend(), Q_EPS)
    on_device(chk, res)
    batches = res.timings.get("stream_batches", 0)
    chk.that(batches >= 3, f"streamed in {batches} chunks (>= 3)")
    chk.that("stream_pass_b" in res.timings,
             f"percentile pass B ran ({res.timings.get('stream_pass_b')})")
    return released(res, Q_FIELDS)


def same_release(chk: Check, got, want, what: str) -> None:
    """Bit-identical releases: the same kept keys, in the same order,
    and every field equal bit for bit."""
    (k1, c1), (k2, c2) = got, want
    diff = ([] if np.array_equal(k1, k2) else ["keys"]) + [
        f for f in Q_FIELDS
        if not (np.array_equal(k1, k2) and np.array_equal(c1[f], c2[f]))]
    chk.that(not diff, f"release bit-identical to {what} "
             f"({len(k1)} partitions; differ: {diff or 'none'})")


def phase_streamed(ctx, chk):
    keys, cols = ctx.streamed = streamed_quantiles(ctx, chk)
    check_quantiles(chk, keys, cols, ctx.quantile_dataset())
    if ctx.unstreamed is None:
        chk.that(False, "unstreamed quantile run to compare with")
    else:
        same_release(chk, (keys, cols), ctx.unstreamed, "the unstreamed run")
    return f"{ctx.sizes.q_rows} rows, {len(keys)} partitions released"


def phase_sweep(ctx, chk):
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import analysis
    from pipelinedp_tpu.analysis import jax_sweep
    s = ctx.sizes
    ds = zipf_dataset(ctx.seed + 5, s.sweep_rows,
                      max(1_000, s.sweep_rows // 25), 1_000)
    side = int(math.isqrt(s.sweep_configs))
    pairs = [(1 + i // side, 1 + i % side)
             for i in range(s.sweep_configs)]
    options = analysis.UtilityAnalysisOptions(
        epsilon=1.0, delta=1e-6,
        aggregate_params=pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=2),
        multi_param_configuration=analysis.MultiParameterConfiguration(
            max_partitions_contributed=[p[0] for p in pairs],
            max_contributions_per_partition=[p[1] for p in pairs]))
    compiles0 = ctx.stats.compiles
    res = analysis.perform_utility_analysis(ds, ctx.backend(), options,
                                            pdp.DataExtractors())
    chk.that(isinstance(res, jax_sweep.LazySweepResult),
             f"device sweep ({type(res).__name__})")
    out = list(res)
    chk.that(ctx.stats.compiles > compiles0,
             f"device programs compiled ({ctx.stats.compiles - compiles0})")
    chk.that(len(out) == 1 and len(out[0]) == s.sweep_configs,
             f"{len(out[0]) if out else 0} of {s.sweep_configs} "
             "configurations came back")
    errs = np.asarray([m.count_metrics.error_expected for m in out[0]])
    chk.that(np.isfinite(errs).all(), "every configuration finite")
    return f"{s.sweep_configs} configs x {s.sweep_rows} rows"


def phase_pallas(ctx, chk):
    """``kernel_backend=pallas`` on the ``streamed`` phase's workload
    (pass A's segment sum and pass B's histogram over every partition)
    and on VECTOR_SUM at D=256 (the wide segment sum), each against
    its XLA release."""
    import pipelinedp_tpu as pdp
    s = ctx.sizes
    # Two partitions per id: the Laplace noise of a D=256 vector scales
    # with L0 * D, and stays far below the check's tolerance.
    vds = bounded_dataset(ctx.seed + 4, s.vector_rows, s.vector_rows,
                          s.vector_parts, 2, dim=s.vector_d)
    vds.values *= 0.125  # most per-partition sums stay inside the clip
    vparams = pdp.AggregateParams(
        metrics=[pdp.Metrics.VECTOR_SUM], noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2, max_contributions_per_partition=1,
        vector_size=s.vector_d, vector_max_norm=1.0,
        vector_norm_kind=pdp.NormKind.Linf)
    kinds = ("kernel.pallas_dispatches", "kernel.fallbacks")

    def dispatched(run):
        c0 = counters()
        out = run()
        c1 = counters()
        return out, {k: c1.get(k, 0) - c0.get(k, 0) for k in kinds}

    vector = {}
    for backend in ("xla", "pallas"):
        vds.invalidate_cache()
        with env(PIPELINEDP_TPU_KERNEL_BACKEND=backend,
                 PIPELINEDP_TPU_VECTOR_ACCUMULATOR="fx"):
            vector[backend] = dispatched(
                lambda: aggregate(vds, vparams, ctx.backend()))
        on_device(chk, vector[backend][0])
    with env(PIPELINEDP_TPU_KERNEL_BACKEND="pallas"):
        (keys, cols), cq = dispatched(lambda: streamed_quantiles(ctx, chk))
    cv = vector["pallas"][1]
    chk.that(cq["kernel.pallas_dispatches"] >= 2,
             f"quantiles: kernel.pallas_dispatches="
             f"{cq['kernel.pallas_dispatches']} (pass A and pass B)")
    chk.that(cv["kernel.pallas_dispatches"] > 0,
             f"vector: kernel.pallas_dispatches="
             f"{cv['kernel.pallas_dispatches']}")
    chk.that(cq["kernel.fallbacks"] + cv["kernel.fallbacks"] == 0,
             f"kernel.fallbacks={cq['kernel.fallbacks']} (quantiles), "
             f"{cv['kernel.fallbacks']} (vector)")
    chk.that(vector["xla"][1]["kernel.pallas_dispatches"] == 0,
             "xla run dispatched xla")
    check_quantiles(chk, keys, cols, ctx.quantile_dataset())
    if ctx.streamed is None:
        chk.that(False, "streamed XLA quantile run to compare with")
    else:
        same_release(chk, (keys, cols), ctx.streamed, "the XLA run")
    vx, vp = dict(vector["xla"][0]), dict(vector["pallas"][0])
    chk.that(vx.keys() == vp.keys() and all(
        np.array_equal(np.asarray(vx[k].vector_sum),
                       np.asarray(vp[k].vector_sum)) for k in vx),
             f"VECTOR_SUM D={s.vector_d} release bit-identical to xla")
    vkeys = np.fromiter(vp.keys(), np.int64)
    got = np.stack([np.asarray(vp[k].vector_sum) for k in vkeys])
    exact = np.zeros((s.vector_parts, s.vector_d))
    np.add.at(exact, vds.partition_keys, vds.values)
    # The release norm-clips each partition's sum (Linf, max_norm 1).
    chk.err("vector_sum", got, np.clip(exact[vkeys], -1.0, 1.0), 0.25)
    return (f"dispatches={cq['kernel.pallas_dispatches']}+"
            f"{cv['kernel.pallas_dispatches']} "
            f"fallbacks={cq['kernel.fallbacks'] + cv['kernel.fallbacks']}")


def phase_serve(ctx, chk):
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import serve
    s = ctx.sizes
    # The flagship's data and params: the service runs the programs the
    # flagship phase compiled, warm.
    ds = ctx.flagship_dataset()
    params = flagship_params(s.flagship_l0)
    total, eps = 10 * BIG_EPS, BIG_EPS
    tenants = {"tenant-a": (total, 1e-3), "tenant-b": (total, 1e-3)}
    c0 = counters()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_serve_") as d:
        with serve.Service(d, tenants=tenants, workers=2) as svc:
            outs = []
            for i, tenant in enumerate(["tenant-a", "tenant-b"] * 2):
                outs.append(svc.submit(serve.ServeRequest(
                    tenant=tenant, params=params, dataset=ds,
                    epsilon=eps, delta=DELTA, rng_seed=ctx.seed + i)))
    c1 = counters()
    chk.that(all(o.ok for o in outs),
             f"4 requests served ({[type(o).__name__ for o in outs]})")
    if not all(o.ok for o in outs):
        return "refused"
    for o in outs[2:]:
        chk.that(o.remaining.epsilon == total - 2 * eps,
                 f"{o.tenant} debited to {o.remaining.epsilon:g} "
                 f"(of {total:g})")
    delta = {k: c1.get(k, 0) - c0.get(k, 0) for k in (
        "serve.requests_served", "serve.warm_hits", "serve.cold_builds",
        "selection.partitions_post")}
    chk.that(delta["serve.requests_served"] == 4, f"{delta}")
    chk.that(delta["serve.warm_hits"] >= 2, "warm programs reused")
    # Only the fused device program's selection step counts this.
    chk.that(delta["selection.partitions_post"] > 0,
             "device program selected partitions")
    counts = np.bincount(ds.partition_keys, minlength=s.flagship_parts)
    for o in outs:
        keys = np.fromiter((k for k, _ in o.results), np.int64)
        got = np.asarray([m.count for _, m in o.results])
        chk.err(f"{o.tenant} count", got, counts[keys], 0.5)
    return (f"{delta['serve.requests_served']} served, "
            f"{delta['serve.warm_hits']} warm")


class Context:

    def __init__(self, seed, sizes, stats, mesh=None):
        self.seed = seed
        self.sizes = sizes
        self.stats = stats
        self.mesh = mesh
        self.unstreamed = None
        self.streamed = None
        self._qds = None
        self._fds = None

    def backend(self):
        from pipelinedp_tpu.backends import JaxBackend
        return JaxBackend(mesh=self.mesh, rng_seed=self.seed)

    def flagship_dataset(self):
        if self._fds is None:
            s = self.sizes
            self._fds = bounded_dataset(self.seed, s.flagship_rows,
                                        s.flagship_users, s.flagship_parts,
                                        s.flagship_l0)
        return self._fds

    def quantile_dataset(self):
        if self._qds is None:
            s = self.sizes
            self._qds = bounded_dataset(self.seed + 1, s.q_rows, s.q_users,
                                        s.q_parts, s.q_l0)
        return self._qds


ONE_CHIP = (phase_flagship, phase_quantiles, phase_streamed, phase_sweep,
            phase_pallas, phase_serve)
MESH = (phase_flagship, phase_quantiles)


def run(seed: int, sizes: Sizes, mesh=None, phases=None) -> bool:
    """Run the phases, print one line each, return whether all passed.
    A phase's exception fails that phase (traceback on stderr) and the
    run goes on to the next one."""
    from pipelinedp_tpu.ingest import compile_stats
    stats = compile_stats()
    ctx = Context(seed, sizes, stats, mesh)
    ok = True
    for phase in phases or (MESH if mesh is not None else ONE_CHIP):
        name = phase.__name__[len("phase_"):]
        chk = Check()
        c0, n0, h0, m0 = stats.snapshot()
        t0 = time.perf_counter()
        try:
            summary = phase(ctx, chk)
        except Exception:
            traceback.print_exc()
            chk.that(False, "raised")
            summary = "exception"
        wall = time.perf_counter() - t0
        c1, n1, h1, m1 = stats.snapshot()
        passed = not chk.failed
        ok = ok and passed
        log(f"## phase {name}: {'ok' if passed else 'FAILED'} "
            f"wall_s={wall:.3f} compile_s={c1 - c0:.3f} "
            f"compiles={n1 - n0} cache_hits={h1 - h0} "
            f"cache_misses={m1 - m0} | {summary}")
        for note in chk.notes:
            log(f"##   {note}")
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the sharded path, on a 4-chip mesh")
    args = parser.parse_args(argv)

    import_repo()
    devices = require_tpu(args.chips)
    from pipelinedp_tpu.ingest import (compile_stats,
                                       maybe_enable_compile_cache)
    cache_dir = maybe_enable_compile_cache(
        default_dir=os.path.join(REPO, ".jax_cache"))
    log(f"## device: {devices[0].device_kind} x{len(devices)} "
        f"({devices[0].platform})")
    log(f"## compile cache: {cache_dir}")

    mesh = None
    if args.chips == 4:
        from pipelinedp_tpu import parallel
        mesh = parallel.make_mesh(4)
    t0 = time.perf_counter()
    ok = run(args.seed, Sizes(), mesh=mesh)
    compile_s, compiles, hits, misses = compile_stats().snapshot()
    log(f"## total wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={compile_s:.3f} compiles={compiles} "
        f"cache_dir={cache_dir} cache_hits={hits} cache_misses={misses}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:args.chips]]
    log(f"## peak_bytes_in_use per device: {peaks}")
    if not ok:
        log("## chip_smoke: FAILED")
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
