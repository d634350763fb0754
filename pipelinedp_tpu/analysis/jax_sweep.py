"""On-device utility-analysis sweep — the TPU-native form of the
reference's multi-configuration analysis (``analysis/combiners.py:313-381``
sparse/dense machinery + ``analysis/utility_analysis.py`` driver).

Reference semantics, redesigned with a **configuration axis** instead of
per-configuration Python combiner lists (SURVEY.md §7.6):

    stage A (once):   sort rows by (pid, pk) → per-(pid, pk) user stats
                      (count, sum) and per-pid partition fan-out, all in
                      row space (one lexsort, one monotone segment_sum).
    stage B (vmapped  broadcast user stats against [C] config vectors:
    over configs):    clip errors, L0 drop moments, per-user keep
                      probabilities → per-(partition, config) error
                      model via ONE widened segment_sum.
    stage C (fused    P(partition kept) from Poisson-binomial moments
    with B):          (refined-normal window with skewness for the
                      truncated-geometric table; Gauss-Hermite quadrature
                      for large-σ and thresholding strategies), error
                      quantiles (closed-form Gaussian / interpolated
                      Laplace+Gaussian table), then the cross-partition
                      reduction to per-config aggregate fields.
    host:             normalize and pack AggregateMetrics — O(C) tiny.

Approximation contract (documented divergences from the host oracle,
which itself approximates past 100 users — reference
``analysis/combiners.py:32``): the device path always uses the moment
approximation for P(keep) (the host uses exact PMF convolution below 100
users), and Laplace+Gaussian error quantiles come from a precomputed
400k-sample quantile table interpolated over the noise ratio instead of
a fresh 1k-sample Monte-Carlo per partition (the device table is the
*less* noisy of the two).

Configurations are processed in fixed-size chunks so arbitrarily large
sweeps stream through bounded HBM; each chunk is one compiled program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri as _ndtri
from jax.scipy.stats import norm as _jnorm

from pipelinedp_tpu import dp_computations
from pipelinedp_tpu.aggregate_params import (AggregateParams, MechanismType,
                                             Metrics, NoiseKind,
                                             PartitionSelectionStrategy)
from pipelinedp_tpu.analysis import data_structures
from pipelinedp_tpu.analysis import metrics as am
from pipelinedp_tpu.obs.costs import instrumented_jit
from pipelinedp_tpu.jax_engine import (_pad_pow2, _pad_rows, encode,
                                       pad_and_put)
from pipelinedp_tpu.ops import partition_selection as ps_ops
from pipelinedp_tpu.ops import segment as seg_ops

# Error quantile levels, as the reference driver fixes them
# (``analysis/utility_analysis.py:71``).
ERROR_QUANTILES = (0.1, 0.5, 0.9, 0.99)
# Integer window half-width for the refined-normal keep-probability sum
# (covers sigma up to _WINDOW/8 at the reference's ±8σ coverage).
_WINDOW = 64
# Gauss-Hermite order for the large-σ / thresholding quadrature.
_GH_ORDER = 32
# Truncated-geometric tables are clamped to this many entries per config
# (keep probability saturates to 1 long before for any sane budget).
_MAX_TABLE = 1 << 16
# Upper bound on configurations per compiled chunk (tests shrink this to
# exercise the chunk loop).
_CHUNK_CAP = 512
# Row-broadcast budget per chunk: bounds the [n, Cc] stage-B footprint
# (n_pad * chunk <= this).
_CHUNK_ROW_BUDGET = 1 << 26
# Knob seam (plan/knobs.py "sweep_config_batch"): nonzero pins the
# configuration-axis batch width; 0 = the auto sizing below. Kept as a
# module constant purely as the registry's test seam — consumers go
# through knobs.value().
_SWEEP_CONFIG_BATCH = 0


def _lane_align(chunk: int) -> int:
    """Round a config-axis width to the TPU lane grid: large chunks
    down to a 128 multiple, small ones to a power of two (a chunk of
    133 silently pads every broadcast to 256 lanes)."""
    if chunk >= 128:
        return (chunk // 128) * 128
    if chunk > 1:
        return 1 << (chunk.bit_length() - 1)
    return 1


def sweep_is_supported(options: data_structures.UtilityAnalysisOptions,
                       data_extractors, return_per_partition: bool) -> bool:
    """Gates for the fused path; anything else falls back to the host
    graph (which remains the oracle). Per-config ``noise_kind`` /
    ``partition_selection_strategy`` vectors, pre-aggregated input and
    ``return_per_partition`` all run fused (the per-partition fetch is
    byte-capped at runtime — past ``_PP_BYTE_CAP`` the sweep re-routes
    itself to the host graph)."""
    params = options.aggregate_params
    if (params.max_partitions_contributed is None or
            params.max_contributions_per_partition is None):
        # max_contributions-style params: let the host path raise its
        # NotImplementedError eagerly instead of failing in the kernel.
        return False
    multi = options.multi_param_configuration
    if Metrics.SUM in params.metrics:
        # SUM analysis clips per-partition sums: both bounds must come
        # from the params or the per-config vectors; anything else (the
        # host's one-sided clip, or its ValueError on missing bounds)
        # stays on the host path rather than silently diverging.
        has_base = (params.min_sum_per_partition is not None and
                    params.max_sum_per_partition is not None)
        has_multi = (multi is not None and
                     multi.min_sum_per_partition is not None)
        if not (has_base or has_multi):
            return False
    return True


# ---------------------------------------------------------------------------
# Host-side per-config parameter vectors
# ---------------------------------------------------------------------------


def _config_vectors(
        options) -> Tuple[Dict[str, np.ndarray], List[AggregateParams]]:
    """[C] vectors of the swept parameters. The sum bounds are guaranteed
    set when SUM is analyzed (``sweep_is_supported``); the 0.0 fallback
    only feeds configs whose metrics never read them."""
    all_params = list(data_structures.get_aggregate_params(options))
    return {
        "l0": np.asarray([p.max_partitions_contributed for p in all_params],
                         np.float32),
        "linf": np.asarray(
            [p.max_contributions_per_partition or 0 for p in all_params],
            np.float32),
        "min_sum": np.asarray(
            [p.min_sum_per_partition
             if p.min_sum_per_partition is not None else 0.0
             for p in all_params], np.float32),
        "max_sum": np.asarray(
            [p.max_sum_per_partition
             if p.max_sum_per_partition is not None else 0.0
             for p in all_params], np.float32),
    }, all_params


def _noise_stds(metric, all_params, budgets) -> np.ndarray:
    """Per-config noise std of the released metric — [C].

    Parity quirk preserved: every analysis combiner in the reference
    (SUM and PRIVACY_ID_COUNT included) predicts noise via
    ``compute_dp_count_noise_std`` with linf = the configuration's
    ``max_contributions_per_partition`` — even where the modeled
    mechanism clips per-partition sums or 0/1 indicators (reference
    ``analysis/combiners.py:265-270`` via the inherited
    ``SumCombiner.compute_metrics``). The host combiners here mirror
    that, so the device path must too."""
    spec = budgets[metric]
    out = []
    for p in all_params:
        params = dp_computations.ScalarNoiseParams(
            eps=spec.eps, delta=spec.delta,
            min_value=0.0,
            max_value=float(p.max_contributions_per_partition),
            min_sum_per_partition=None, max_sum_per_partition=None,
            max_partitions_contributed=p.max_partitions_contributed,
            max_contributions_per_partition=(
                p.max_contributions_per_partition),
            noise_kind=p.noise_kind)
        out.append(dp_computations.compute_dp_count_noise_std(params))
    return np.asarray(out, np.float32)


def _selection_tables(all_params, eps, delta) -> Tuple[np.ndarray, ...]:
    """Per-config keep-probability inputs, supporting a DIFFERENT
    selection strategy per configuration: a [C, T] truncated-geometric
    table (row-padded with its saturating tail value; all-ones dummy row
    for thresholding configs), threshold[C] and scale[C] (dummies for
    table configs)."""
    tables, thr, scale = [], [], []
    for p in all_params:
        strat = p.partition_selection_strategy
        s = ps_ops.create_partition_selection_strategy(
            strat, eps, delta, p.max_partitions_contributed)
        if strat == PartitionSelectionStrategy.TRUNCATED_GEOMETRIC:
            tables.append(s.keep_table[:_MAX_TABLE])
            thr.append(0.0)
            scale.append(1.0)
        else:
            tables.append(np.ones(1, np.float32))
            thr.append(s.threshold)
            scale.append(s.noise_scale if strat ==
                         PartitionSelectionStrategy.LAPLACE_THRESHOLDING
                         else s.noise_stddev)
    T = max(len(t) for t in tables)
    out = np.ones((len(tables), T), np.float32)
    for i, t in enumerate(tables):
        out[i, :len(t)] = t
        out[i, len(t):] = t[-1] if len(t) else 1.0
    return out, np.asarray(thr, np.float32), np.asarray(scale, np.float32)


@functools.lru_cache(maxsize=4)
def _laplace_gauss_table(quantiles: Tuple[float, ...],
                         n_r: int = 48) -> Tuple[np.ndarray, np.ndarray]:
    """Quantiles t(r, q) of Lap(1) + r·N(0,1) over a log grid of the noise
    ratio r — the device replacement for the host's per-partition
    Monte-Carlo (``analysis/probability_computations.py``)."""
    # lint: disable=rng-purity(fixed-seed Monte-Carlo table, not DP noise)
    rng = np.random.default_rng(0x5eed)
    lap = rng.laplace(size=400_000)
    gau = rng.normal(size=400_000)
    rs = np.geomspace(1e-3, 1e3, n_r)
    table = np.stack([
        np.quantile(lap + r * gau, quantiles) for r in rs
    ])  # [n_r, nq]
    return np.log(rs).astype(np.float32), table.astype(np.float32)


# ---------------------------------------------------------------------------
# Stage A: per-(pid, pk) user stats — one sort, row space
# ---------------------------------------------------------------------------


@instrumented_jit(phase="sweep")
def _preagg_kernel(pid, pk, values, valid):
    """Returns dense per-row arrays where ``marker`` rows carry one
    (pid, pk) user-contribution record: (pk, count, sum, n_partitions of
    the pid). Mirrors the analysis bounder
    (reference ``analysis/contribution_bounders.py:19-75``)."""
    n = pid.shape[0]
    idx = jnp.arange(n)
    big_pid = jnp.where(valid, pid, seg_ops.PAD_ID)
    big_pk = jnp.where(valid, pk, seg_ops.PAD_ID)
    sort_idx = jnp.lexsort((big_pk, big_pid))
    spid = big_pid[sort_idx]
    spk = big_pk[sort_idx]
    svalues = values[sort_idx]
    svalid = idx < jnp.sum(valid.astype(jnp.int32))

    new_pid = (idx == 0) | (spid != jnp.roll(spid, 1))
    new_seg = new_pid | (spk != jnp.roll(spk, 1))
    marker = new_seg & svalid

    seg_start = seg_ops.run_starts(new_seg)
    # Last row of each run via the same trick on the reversed arrays.
    last_of_seg = jnp.roll(new_seg, -1).at[-1].set(True)
    seg_end = n - 1 - jnp.flip(seg_ops.run_starts(jnp.flip(last_of_seg)))
    count_u = (seg_end - seg_start + 1).astype(jnp.float32)

    # Per-segment sum: monotone seg ordinal → one precision-safe scatter.
    seg_ord = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    masked = jnp.where(svalid, svalues, 0.0)
    sum_by_ord = jax.ops.segment_sum(masked, seg_ord, num_segments=n)
    sum_u = sum_by_ord[seg_ord]

    # Partition fan-out of the pid: ordinal of its last segment + 1.
    seg_in_pid = seg_ops.run_ordinal_in_group(new_seg, new_pid)
    last_of_pid = jnp.roll(new_pid, -1).at[-1].set(True)
    pid_end = n - 1 - jnp.flip(seg_ops.run_starts(jnp.flip(last_of_pid)))
    npart_u = (seg_in_pid[pid_end] + 1).astype(jnp.float32)

    pk_safe = jnp.where(svalid, spk, 0)
    return marker, pk_safe, count_u, sum_u, npart_u


# ---------------------------------------------------------------------------
# Stage B+C: per-config error model + cross-partition reduction
# ---------------------------------------------------------------------------


_MIXED = "mixed"  # static sentinel: per-config mechanisms in this chunk


def _keep_probability(strategy, mu, var, m3, table, thr, scale, is_tg,
                      is_lap):
    """E[keep(N)] for N ~ Poisson-binomial with the given moments, batched
    over [P, Cc].

    Small σ: refined-normal pmf with skewness correction over an integer
    window (the device twin of ``poisson_binomial.compute_pmf_approximation``).
    Large σ (window can't span ±8σ) and degenerate σ=0 are handled by
    Gauss-Hermite quadrature / direct lookup.

    ``strategy`` may be the static ``_MIXED`` sentinel: each config then
    picks its own strategy via the ``is_tg``/``is_lap`` [Cc] masks (all
    three keep curves are evaluated and selected per config — the masks
    are runtime inputs so mixed sweeps still compile once).
    """
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    skew = jnp.where(sigma > 0, m3 / jnp.maximum(sigma, 1e-30)**3, 0.0)
    T = table.shape[-1]

    def tg_at(i):  # i: [P, Cc, K] float counts
        ii = jnp.clip(jnp.round(i), 0, T - 1).astype(jnp.int32)
        return _table_lookup(table, ii)

    def lap_at(i):
        z = (i - thr[None, :, None]) / scale[None, :, None]
        # P(i + Lap(b) >= T) with b = scale.
        return jnp.where(z < 0, 0.5 * jnp.exp(z),
                         1.0 - 0.5 * jnp.exp(-z))

    def gauss_at(i):
        z = (i - thr[None, :, None]) / scale[None, :, None]
        return _jnorm.cdf(z)

    if strategy == PartitionSelectionStrategy.TRUNCATED_GEOMETRIC:
        keep_at = tg_at
    elif strategy == PartitionSelectionStrategy.LAPLACE_THRESHOLDING:
        keep_at = lap_at
    elif strategy == _MIXED:
        def keep_at(i):
            return jnp.where(
                is_tg[None, :, None], tg_at(i),
                jnp.where(is_lap[None, :, None], lap_at(i), gauss_at(i)))
    else:
        keep_at = gauss_at

    # --- windowed refined normal (small sigma) ---
    offsets = jnp.arange(-_WINDOW, _WINDOW + 1, dtype=jnp.float32)
    centers = jnp.round(mu)[..., None] + offsets  # [P, Cc, W]

    def refined_cdf(z):
        return jnp.clip(
            _jnorm.cdf(z) + skew[..., None] * (1 - z * z) *
            _jnorm.pdf(z) / 6.0, 0.0, 1.0)

    # Consecutive bins share an edge (z_lo[i] == z_hi[i-1]), so evaluate
    # the refined CDF once on the W+1 edges and difference — the
    # erf/pdf transcendentals are this window's dominant cost.
    edge_offsets = jnp.arange(-_WINDOW - 1, _WINDOW + 1,
                              dtype=jnp.float32)  # [W+1] left+right edges
    z_edges = (jnp.round(mu)[..., None] + edge_offsets + 0.5 -
               mu[..., None]) / jnp.maximum(sigma[..., None], 1e-30)
    cdf_edges = refined_cdf(z_edges)
    cdf_hi = cdf_edges[..., 1:]
    cdf_lo = cdf_edges[..., :-1]
    # Edge bins absorb the tails so the pmf always sums to 1.
    pmf = cdf_hi - cdf_lo
    pmf = pmf.at[..., 0].set(cdf_hi[..., 0])
    pmf = pmf.at[..., -1].set(1.0 - cdf_lo[..., -1])
    valid_center = centers >= 0
    pmf = jnp.where(valid_center, pmf, 0.0)
    win = _fold_last(pmf * keep_at(jnp.maximum(centers, 0.0)))

    # --- Gauss-Hermite (large sigma) ---
    nodes, weights = np.polynomial.hermite.hermgauss(_GH_ORDER)
    xs = mu[..., None] + math.sqrt(2.0) * sigma[..., None] * nodes
    gh = _fold_last(
        (weights / math.sqrt(math.pi)) *
        keep_at(jnp.maximum(xs.astype(jnp.float32), 0.0)))

    point = keep_at(jnp.maximum(jnp.round(mu), 0.0)[..., None])[..., 0]
    small = sigma * 8.0 <= _WINDOW
    return jnp.clip(
        jnp.where(sigma < 1e-9, point, jnp.where(small, win, gh)), 0.0,
        1.0)


def _table_lookup(table, ii):
    """table: [Cc, T]; ii: int32 [P, Cc, K] → [P, Cc, K]."""
    return jax.vmap(lambda t, ix: t[ix], in_axes=(0, 1),
                    out_axes=1)(table, ii)


def _fold_partitions(a):
    """Sum over the leading (partition) axis with a FIXED halving tree:
    each stage adds the upper half onto the lower half elementwise, so
    the floating-point combination order is a function of P alone —
    never of the config-axis width riding in the trailing dims. This
    is what makes walked (chunk=1) and batched (chunk=K) sweeps
    bit-identical per config (PARITY row 41): a plain
    ``jnp.sum(axis=0)`` lets XLA pick a width-dependent reduction
    strategy whose rounding differs in the last ulp. P is normally the
    pow2-padded partition count; an odd stage carries its last row
    into the next fold unchanged."""
    while a.shape[0] > 1:
        n = a.shape[0]
        half = n // 2
        front = a[:half] + a[half:2 * half]
        a = (front if n % 2 == 0 else
             jnp.concatenate([front, a[2 * half:]], axis=0))
    return a[0]


def _fold_last(a):
    """`_fold_partitions` over the trailing axis: a fixed halving tree
    replacing ``jnp.sum(axis=-1)`` on the window / Gauss-Hermite axes of
    `_keep_probability`. XLA lowers a plain last-axis ``reduce`` with a
    width- and layout-dependent accumulation order (it even splits long
    axes through ``reduce-window``), so the same moments summed under a
    different config-axis width can drift by an ulp — explicit slices and
    adds pin the combination order for every chunk width (PARITY row
    41)."""
    while a.shape[-1] > 1:
        n = a.shape[-1]
        half = n // 2
        front = a[..., :half] + a[..., half:2 * half]
        a = (front if n % 2 == 0 else
             jnp.concatenate([front, a[..., 2 * half:]], axis=-1))
    return a[..., 0]


def _error_quantiles(noise_kind, exp_l0, var_l0, noise_std, noise_sq,
                     log_rs, t_table, is_gauss=None):
    """Per-(partition, config, q) error quantiles of bounding + noise.
    Host twin: ``SumAggregateErrorMetricsCombiner._compute_error_quantiles``
    with the inverted quantile levels. ``noise_kind=None`` means a mixed
    sweep: both closed forms are evaluated and selected per config via
    the ``is_gauss`` [Cc] mask. ``noise_sq`` is the host-precomputed
    noise_std² (see `_metric_chunk`: squaring on device invites a
    width-dependent fma contraction of ``var_l0 + noise²``)."""
    inv_q = np.asarray([1.0 - q for q in ERROR_QUANTILES], np.float32)

    def gaussian():
        std = jnp.sqrt(var_l0 + noise_sq)
        return (exp_l0[..., None] +
                std[..., None] * _ndtri(inv_q)[None, None, :])

    def laplace():
        # Laplace noise + Gaussian L0 error: interpolated quantile table
        # over the noise ratio r = sigma_l0 / b. One vectorized
        # computation over the quantile axis (jnp.interp interpolates
        # each table column at every logr point; elementwise math is
        # identical to interpolating the columns one at a time).
        b = noise_std / math.sqrt(2.0)
        r = jnp.sqrt(jnp.maximum(var_l0, 0.0)) / jnp.maximum(b, 1e-30)
        logr = jnp.log(jnp.maximum(r, 1e-6))
        t = jax.vmap(lambda col: jnp.interp(logr, log_rs, col),
                     in_axes=1, out_axes=-1)(t_table)  # [..., Q]
        # Beyond the grid the Gaussian term dominates: t ≈ r·Φ⁻¹(q).
        ppf = jnp.asarray(_scipy_ppf(inv_q), t.dtype)
        t = jnp.where((r > 900.0)[..., None], r[..., None] * ppf, t)
        return exp_l0[..., None] + b[..., None] * t

    if noise_kind == NoiseKind.GAUSSIAN:
        return gaussian()
    if noise_kind == NoiseKind.LAPLACE:
        return laplace()
    return jnp.where(is_gauss[None, :, None], gaussian(), laplace())


def _scipy_ppf(q):
    import scipy.stats
    return scipy.stats.norm.ppf(q)


def _metric_chunk(metric_name, x_u, marker, pk_safe, p_u, bounds_lo,
                  bounds_hi, noise_std, noise_sq_row, noise_kind,
                  p_keep_pk, mask_pk, pseudo_mask_pk, P, log_rs, t_table,
                  is_gauss=None, per_partition=False):
    """Stage B+C for one metric over one config chunk. Returns the [Cc]
    aggregate accumulator fields (reference
    ``SumAggregateErrorMetricsCombiner.create_accumulator`` summed over
    partitions, with ``compute_metrics`` normalization done on host).
    With ``per_partition`` the UNREDUCED [P, Cc] accumulator fields are
    returned too (five separate rank-2 arrays — a single [P, Cc, 5]
    stack would tile-pad the trailing dim), feeding the per-partition
    ``SumMetrics`` rows (reference ``analysis/utility_analysis.py:60-77``
    returns the same rows from its host pass)."""
    Cc = bounds_lo.shape[0]
    x = x_u[:, None]  # [n, 1]
    lo = bounds_lo[None, :]
    hi = bounds_hi[None, :]
    contribution = jnp.clip(x, lo, hi)
    err = (contribution - x) * marker[:, None]
    err_min = jnp.where(x < lo, err, 0.0)
    err_max = jnp.where(x > hi, err, 0.0)
    exp_l0_u = -contribution * (1.0 - p_u) * marker[:, None]
    var_l0_u = contribution**2 * p_u * (1.0 - p_u) * marker[:, None]

    cols = jnp.stack(
        [jnp.broadcast_to(x * marker[:, None], err.shape), err_min,
         err_max, exp_l0_u, var_l0_u], axis=-1)  # [n, Cc, 5]
    per_pk = jax.ops.segment_sum(cols, pk_safe, num_segments=P)
    psum = per_pk[..., 0]        # [P, Cc] partition true aggregate
    e_min = per_pk[..., 1]
    e_max = per_pk[..., 2]
    exp_l0 = per_pk[..., 3]
    var_l0 = per_pk[..., 4]

    if pseudo_mask_pk is not None:
        # Empty public partitions: one (0, 0, 0) pseudo-user (reference
        # CompoundCombiner.create_accumulator on empty input). Its clip
        # error is clip(0, lo, hi) with keep probability 0.
        zc = jnp.clip(0.0, lo, hi)  # [1, Cc]
        pm = pseudo_mask_pk[:, None]
        e_min = e_min + jnp.where(0.0 < lo, zc, 0.0) * pm
        e_max = e_max + jnp.where(0.0 > hi, zc, 0.0) * pm
        exp_l0 = exp_l0 + (-zc) * pm
        # var term is zero: p(1-p) = 0.

    noise = noise_std[None, :]      # [1, Cc]
    # noise² is HOST-precomputed (same f32 rounding as an on-device
    # multiply) and shipped as data: written as ``noise * noise`` LLVM
    # may contract ``var_l0 + noise*noise`` into fma(noise, noise,
    # var_l0), and whether it does depends on the config-axis
    # vectorization width — breaking walked-vs-batched bit parity
    # (PARITY row 41) in the last ulp. A parameter operand cannot be
    # contracted, so the sum rounds identically at every chunk width.
    noise_sq = noise_sq_row[None, :]  # [1, Cc]
    p_keep = p_keep_pk          # [P, Cc]
    m = mask_pk[:, None]

    err_l0_expected = p_keep * exp_l0
    err_linf_min = p_keep * e_min
    err_linf_max = p_keep * e_max
    err_l0_var = p_keep * var_l0
    err_var = p_keep * (var_l0 + noise_sq)
    qs = _error_quantiles(noise_kind, exp_l0, var_l0,
                          jnp.broadcast_to(noise, exp_l0.shape),
                          jnp.broadcast_to(noise_sq, exp_l0.shape),
                          log_rs, t_table, is_gauss)  # [P, Cc, Q]
    err_quant = p_keep[..., None] * (qs + (e_min + e_max)[..., None])
    err_w_dropped = (p_keep * (exp_l0 + e_min + e_max) +
                     (1 - p_keep) * -psum)

    abs_sum = jnp.abs(psum)
    nz = abs_sum > 0
    safe = jnp.where(nz, abs_sum, 1.0)
    safe_sq = jnp.where(nz, psum * psum, 1.0)
    rel = lambda a: jnp.where(nz, a / safe, 0.0)
    relv = lambda a: jnp.where(nz, a / safe_sq, 0.0)

    if metric_name == "sum":
        dropped_l0 = jnp.zeros_like(exp_l0)
        dropped_linf = jnp.zeros_like(e_max)
        dropped_sel = jnp.zeros_like(psum)
    else:
        dropped_l0 = -exp_l0
        dropped_linf = -e_max
        dropped_sel = (1 - p_keep) * (psum + exp_l0 + e_max)

    def S(a):  # sum over (masked) partitions → [Cc]
        return _fold_partitions(a * m)

    def Sq(a):  # [P, Cc, Q] → [Cc, Q]
        return _fold_partitions(a * m[..., None])

    pp = {}
    if per_partition:
        # Field names must stay in sync with _PP_FIELDS (the split
        # helper keys the per-partition extraction on that list).
        pp = {"pp_sum": psum, "pp_err_min": e_min, "pp_err_max": e_max,
              "pp_exp_l0": exp_l0, "pp_var_l0": var_l0}

    return {
        **pp,
        "num_partitions": _fold_partitions(m)[0] * jnp.ones(Cc),
        "kept_partitions_expected": S(p_keep),
        "total_aggregate": S(psum),
        "data_dropped_l0": S(dropped_l0),
        "data_dropped_linf": S(dropped_linf),
        "data_dropped_partition_selection": S(dropped_sel),
        "error_l0_expected": S(err_l0_expected),
        "error_linf_min_expected": S(err_linf_min),
        "error_linf_max_expected": S(err_linf_max),
        "error_l0_variance": S(err_l0_var),
        "error_variance": S(err_var),
        "error_quantiles": Sq(err_quant),
        "rel_error_l0_expected": S(rel(err_l0_expected)),
        "rel_error_linf_min_expected": S(rel(err_linf_min)),
        "rel_error_linf_max_expected": S(rel(err_linf_max)),
        "rel_error_l0_variance": S(relv(err_l0_var)),
        "rel_error_variance": S(relv(err_var)),
        "rel_error_quantiles": Sq(
            jnp.where(nz[..., None], err_quant / safe[..., None], 0.0)),
        "error_expected_w_dropped_partitions": S(err_w_dropped),
        "rel_error_expected_w_dropped_partitions": S(rel(err_w_dropped)),
    }


def _sweep_chunk_body(metric_names, strategy, noise_kind, P, public,
                      chunk, start, marker, pk_safe, count_u, sum_u,
                      npart_u, users_pk, l0, linf, min_sum, max_sum,
                      noise_std_rows, table, thr, scale, is_tg, is_lap,
                      is_gauss, log_rs, t_table, per_partition=False):
    """Stages B+C for one chunk of configurations (pure function; jitted
    directly for one device, or shard_mapped over the mesh with the
    configuration axis sharded and rows replicated).

    The FULL (padded) config vectors live on device; each chunk call
    slices its ``chunk`` configs at ``start`` on device — the host never
    re-ships parameter vectors per chunk, so a 10k-config sweep costs
    one parameter transfer, not one per chunk of the high-latency link."""
    def sl(a, axis=0):
        return jax.lax.dynamic_slice_in_dim(a, start, chunk, axis=axis)

    l0, linf, min_sum, max_sum = (sl(l0), sl(linf), sl(min_sum),
                                  sl(max_sum))
    noise_std_rows = sl(noise_std_rows, axis=1)
    table = sl(table)
    thr, scale = sl(thr), sl(scale)
    is_tg, is_lap, is_gauss = sl(is_tg), sl(is_lap), sl(is_gauss)
    markerf = marker.astype(jnp.float32)
    p_u = jnp.where(npart_u[:, None] > 0,
                    jnp.minimum(1.0, l0[None, :] /
                                jnp.maximum(npart_u[:, None], 1.0)),
                    0.0) * markerf[:, None]  # [n, Cc]

    # users_pk carries -1 on padding partitions beyond the real vocab, so
    # "== 0" identifies genuinely empty (public) partitions only.
    mask_pk = (users_pk > 0) | (public & (users_pk == 0))
    pseudo_mask = ((users_pk == 0).astype(jnp.float32) if public
                   else None)

    if strategy is None:
        p_keep_pk = jnp.ones((P, l0.shape[0]))
        sel_stats = None
    else:
        mom = jnp.stack(
            [p_u, p_u * (1 - p_u), p_u * (1 - p_u) * (1 - 2 * p_u)],
            axis=-1)
        mom_pk = jax.ops.segment_sum(mom, pk_safe, num_segments=P)
        p_keep_pk = _keep_probability(strategy, mom_pk[..., 0],
                                      mom_pk[..., 1], mom_pk[..., 2],
                                      table, thr, scale, is_tg, is_lap)
        p_keep_pk = jnp.where(mask_pk[:, None], p_keep_pk, 0.0)
        mf = mask_pk.astype(jnp.float32)[:, None]
        # Partition-axis sums via the fixed fold: the combination order
        # must not depend on the config-axis width (see
        # _fold_partitions).
        sel_stats = {
            "num_partitions": (_fold_partitions(mf)[0] *
                               jnp.ones(l0.shape[0])),
            "keep_sum": _fold_partitions(p_keep_pk * mf),
            "keep_var": _fold_partitions(p_keep_pk * (1 - p_keep_pk) *
                                         mf),
        }

    out = {}
    idx = 0
    for name in metric_names:
        if name == "sum":
            x_u = sum_u
            lo_b, hi_b = min_sum, max_sum
        elif name == "count":
            x_u = count_u
            lo_b, hi_b = jnp.zeros_like(linf), linf
        else:  # privacy_id_count
            x_u = jnp.minimum(count_u, 1.0)
            lo_b, hi_b = jnp.zeros_like(linf), jnp.ones_like(linf)
        # Rows [M:] of noise_std_rows carry the host-precomputed squares
        # (see _metric_chunk on why noise² must arrive as data).
        out[name] = _metric_chunk(
            name, x_u, markerf, pk_safe, p_u, lo_b, hi_b,
            noise_std_rows[idx], noise_std_rows[len(metric_names) + idx],
            noise_kind, p_keep_pk,
            mask_pk.astype(jnp.float32), pseudo_mask, P, log_rs, t_table,
            is_gauss, per_partition=per_partition)
        idx += 1
    if per_partition:
        out["_pp_keep"] = p_keep_pk
    return out, sel_stats


_sweep_chunk_kernel = instrumented_jit(
    phase="sweep",
    static_argnames=("metric_names", "strategy", "noise_kind", "P",
                     "public", "chunk", "per_partition"))(_sweep_chunk_body)


@instrumented_jit(phase="sweep", static_argnames=("axis",))
def _concat_flat(chunks, axis):
    """Every field of every chunk (``chunks[c][f]``), concatenated over
    the chunks along ``axis`` and raveled into ONE flat vector: one
    program, so one d2h transfer and, on a mesh (config axis sharded),
    one collective launch. Separate eager concatenates each launch a
    collective program; with several in flight, a device thread can
    miss a rendezvous until XLA's CPU runtime aborts the process at
    its termination timeout."""
    return jnp.concatenate([jnp.concatenate(field, axis).ravel()
                            for field in zip(*chunks)])


def _fetch_concat(chunks, axis):
    """Host twin of :func:`_concat_flat`: the per-field arrays
    (concatenated over ``chunks`` along ``axis``), fetched in one
    transfer."""
    flat = np.asarray(_concat_flat(chunks, axis))
    out, off = [], 0
    for field in zip(*chunks):
        shape = list(field[0].shape)
        shape[axis] = sum(int(x.shape[axis]) for x in field)
        size = int(np.prod(shape))
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return out


#: The [P, Cc] per-partition blocks _metric_chunk emits (plus the
#: metric-independent "_pp_keep") — ONE list for the emission, the
#: single-device extraction and the mesh extraction.
_PP_FIELDS = ("pp_sum", "pp_err_min", "pp_err_max", "pp_exp_l0",
              "pp_var_l0")


def _split_pp(out, metric_names):
    """Pops the per-partition blocks out of a chunk's output dict into
    the flat-keyed dict (``_pp_keep`` / ``<metric>.<field>``) the
    driver accumulates."""
    pp = {"_pp_keep": out.pop("_pp_keep")}
    for nm in metric_names:
        for f in _PP_FIELDS:
            pp[f"{nm}.{f}"] = out[nm].pop(f)
    return pp


@instrumented_jit(
    phase="sweep",
    static_argnames=("metric_names", "strategy", "noise_kind", "P",
                     "public", "chunk", "mesh", "per_partition"))
def _sweep_chunk_sharded(metric_names, strategy, noise_kind, P, public,
                         chunk, mesh, start, marker, pk_safe, count_u,
                         sum_u, npart_u, users_pk, l0, linf, min_sum,
                         max_sum, noise_std_rows, table, thr, scale,
                         is_tg, is_lap, is_gauss, log_rs, t_table,
                         per_partition=False):
    """The chunk body over a device mesh: rows and the (padded) config
    vectors replicated, the chunk's configuration axis SPLIT — device d
    slices its chunk/n_dev configs at ``start + d*(chunk/n_dev)`` on
    device; outputs come back sharded along the config axis (no
    collectives needed). With ``per_partition`` the [P, Cc] blocks come
    back as a third pytree sharded along their CONFIG axis (dim 1) —
    ``return_per_partition`` stays fused on the mesh; the keys match
    the single-device driver's (``_pp_keep`` / ``<metric>.pp_*``)."""
    from jax.sharding import PartitionSpec as PSpec

    from jax import shard_map

    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    local = chunk // n_dev
    shard = PSpec(axis)
    repl = PSpec()
    # Multi-process meshes replicate the (small, [Cc]-sized) outputs
    # with one all_gather so every process fetches its own copy —
    # config-axis shards on another process are not host-addressable
    # (same tradeoff as the streaming kernels' psum switch).
    multiproc = mesh.is_multi_process

    def body(start, *args):
        my_start = start + jax.lax.axis_index(axis) * local
        out, sel = _sweep_chunk_body(metric_names, strategy, noise_kind,
                                     P, public, local, my_start, *args,
                                     per_partition=per_partition)
        pp = _split_pp(out, metric_names) if per_partition else {}
        if multiproc:
            from pipelinedp_tpu.parallel import sharded as psh
            topo = psh.topology_of(mesh)

            def ag(x, dim):
                return psh.gather_blocks(x, axis, dim=dim, topo=topo)
            out = jax.tree.map(lambda x: ag(x, 0), out)
            sel = jax.tree.map(lambda x: ag(x, 0), sel)
            pp = jax.tree.map(lambda x: ag(x, 1), pp)
        return out, sel, pp

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(repl,) * 20,
        out_specs=((repl, repl, repl) if multiproc else
                   (shard, shard, PSpec(None, axis))),
        check_vma=False)
    return mapped(start, marker, pk_safe, count_u, sum_u, npart_u,
                  users_pk, l0, linf, min_sum, max_sum, noise_std_rows,
                  table, thr, scale, is_tg, is_lap, is_gauss, log_rs,
                  t_table)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Dataset histograms on device (tuning input)
# ---------------------------------------------------------------------------

# Bin-id space of the 3-leading-digit binning: values <= 1000 are their
# own bin; each later decade d contributes 900 bins for n//10^(d+1) in
# [100, 1000). 7 decades cover int32.
_HIST_DECADES = 7
_HIST_BINS = 1001 + _HIST_DECADES * 900


def _bin_ids(v):
    """Exact integer 3-leading-digit binning (host twin
    ``histograms._to_bin_lower``): returns dense bin ids [same shape].
    The >= folds v == 10^k into decade k-2's first bin, whose decoded
    lower edge (10^k) matches the host's _to_bin_lower — in particular
    1000 shares the lower-1000 bin with 1001..1009."""
    thresholds = jnp.asarray(
        [10**(3 + j) for j in range(_HIST_DECADES)], jnp.int32)
    e = jnp.sum(v[..., None] >= thresholds[None, :], axis=-1)
    rb = jnp.asarray([10**j for j in range(_HIST_DECADES + 1)],
                     jnp.int32)[e]
    lead = v // rb  # in [100, 1000) for e >= 1
    return jnp.where(e == 0, v, 1001 + (e - 1) * 900 + lead - 100)


def _bin_lower_of_id(ids: np.ndarray) -> np.ndarray:
    """Host inverse of _bin_ids: dense bin id -> bin lower edge."""
    ids = np.asarray(ids, np.int64)
    d = np.maximum((ids - 1001) // 900, 0)  # clamp: small ids unused below
    m = (ids - 1001) % 900 + 100
    return np.where(ids <= 1000, ids, m * 10**(d + 1))


def _bin_stats(v, mask, P):
    """(count, sum, max) per dense bin over masked values — [BINS, 3].
    int32 accumulators are exact here: every histogram's total sum is
    bounded by the dataset's row count (< 2^31)."""
    ids = jnp.where(mask, _bin_ids(v), _HIST_BINS)  # masked -> dropped
    cnt = jax.ops.segment_sum(mask.astype(jnp.int32), ids,
                              num_segments=_HIST_BINS + 1)
    tot = jax.ops.segment_sum(jnp.where(mask, v, 0), ids,
                              num_segments=_HIST_BINS + 1)
    mx = jax.ops.segment_max(jnp.where(mask, v, -1), ids,
                             num_segments=_HIST_BINS + 1)
    return jnp.stack([cnt, tot, mx], axis=-1)[:_HIST_BINS]


@instrumented_jit(phase="sweep", static_argnames=("P",))
def _histogram_kernel(P, pid, pk, valid):
    """All four tuning histograms in one program (host graph twin:
    ``histograms.compute_dataset_histograms``). Returns [4, BINS, 3]."""
    n = pid.shape[0]
    idx = jnp.arange(n)
    big_pid = jnp.where(valid, pid, seg_ops.PAD_ID)
    big_pk = jnp.where(valid, pk, seg_ops.PAD_ID)
    sort_idx = jnp.lexsort((big_pk, big_pid))
    spid = big_pid[sort_idx]
    spk = big_pk[sort_idx]
    svalid = idx < jnp.sum(valid.astype(jnp.int32))

    new_pid = (idx == 0) | (spid != jnp.roll(spid, 1))
    new_seg = new_pid | (spk != jnp.roll(spk, 1))
    marker = new_seg & svalid
    pid_marker = new_pid & svalid
    pk_safe = jnp.where(svalid, spk, 0)

    seg_start = seg_ops.run_starts(new_seg)
    last_of_seg = jnp.roll(new_seg, -1).at[-1].set(True)
    seg_end = n - 1 - jnp.flip(seg_ops.run_starts(jnp.flip(last_of_seg)))
    count_u = (seg_end - seg_start + 1).astype(jnp.int32)  # Linf values

    seg_in_pid = seg_ops.run_ordinal_in_group(new_seg, new_pid)
    last_of_pid = jnp.roll(new_pid, -1).at[-1].set(True)
    pid_end = n - 1 - jnp.flip(seg_ops.run_starts(jnp.flip(last_of_pid)))
    npart_u = (seg_in_pid[pid_end] + 1).astype(jnp.int32)  # L0 values

    rows_pk = jax.ops.segment_sum(svalid.astype(jnp.int32), pk_safe,
                                  num_segments=P)
    pids_pk = jax.ops.segment_sum(marker.astype(jnp.int32), pk_safe,
                                  num_segments=P)
    pk_mask = pids_pk > 0

    return jnp.stack([
        _bin_stats(npart_u, pid_marker, P),          # L0
        _bin_stats(count_u, marker, P),              # Linf
        _bin_stats(rows_pk, pk_mask, P),             # count / partition
        _bin_stats(pids_pk, pk_mask, P),             # pids / partition
    ])


def fused_dataset_histograms(col, data_extractors):
    """Device twin of ``compute_dataset_histograms``: one sort + four
    binned reductions; only ~90KB of per-bin stats return to host."""
    from pipelinedp_tpu.analysis import histograms as hs
    from pipelinedp_tpu.jax_engine import pad_and_put

    encoded = encode(col, data_extractors, None, None)
    if encoded.n_rows == 0:
        empty = [hs.Histogram(t, []) for t in (
            hs.HistogramType.L0_CONTRIBUTIONS,
            hs.HistogramType.LINF_CONTRIBUTIONS,
            hs.HistogramType.COUNT_PER_PARTITION,
            hs.HistogramType.COUNT_PRIVACY_ID_PER_PARTITION)]
        return [hs.DatasetHistograms(*empty)]
    P = _pad_pow2(len(encoded.pk_vocab))
    pid, pk, _, valid = pad_and_put(encoded, None, with_values=False)
    stats = np.asarray(_histogram_kernel(P, pid, pk, valid))

    def to_histogram(name, table):
        nz = np.flatnonzero(table[:, 0] > 0)
        lowers = _bin_lower_of_id(nz)
        bins = [
            hs.FrequencyBin(lower=int(lo), count=int(table[i, 0]),
                            sum=int(table[i, 1]), max=int(table[i, 2]))
            for lo, i in zip(lowers, nz)
        ]
        return hs.Histogram(name, bins)

    return [hs.DatasetHistograms(
        to_histogram(hs.HistogramType.L0_CONTRIBUTIONS, stats[0]),
        to_histogram(hs.HistogramType.LINF_CONTRIBUTIONS, stats[1]),
        to_histogram(hs.HistogramType.COUNT_PER_PARTITION, stats[2]),
        to_histogram(hs.HistogramType.COUNT_PRIVACY_ID_PER_PARTITION,
                     stats[3]),
    )]


_METRIC_ORDER = [(Metrics.SUM, "sum", am.AggregateMetricType.SUM),
                 (Metrics.COUNT, "count", am.AggregateMetricType.COUNT),
                 (Metrics.PRIVACY_ID_COUNT, "privacy_id_count",
                  am.AggregateMetricType.PRIVACY_ID_COUNT)]


#: Byte budget for the fetched per-partition [P, C] blocks; sweeps whose
#: (partitions x configurations) footprint exceeds it fall back to the
#: host analysis graph (which materializes the same rows in Python).
_PP_BYTE_CAP = 256 << 20


class _PerPartitionRows:
    """Lazy view of the per-partition utility rows; forces the parent
    sweep on first iteration (same shape as the host path's
    ``per_partition_result``: (pk, flat per-config tuple))."""

    def __init__(self, parent: "LazySweepResult"):
        self._parent = parent

    def __iter__(self):
        for _ in self._parent:  # force execution
            pass
        yield from self._parent._pp_rows


class LazySweepResult:
    """1-element iterable (List[AggregateMetrics]) running the device
    sweep on first iteration — after ``compute_budgets()``."""

    def __init__(self, col, options, data_extractors, public_partitions,
                 budgets, selection_budget, mesh=None,
                 return_per_partition=False, backend=None,
                 checkpoint=None):
        self._col = col
        self._options = options
        self._extractors = data_extractors
        self._public = public_partitions
        self._budgets = budgets
        self._selection_budget = selection_budget
        self._mesh = mesh
        self._return_per_partition = return_per_partition
        self._backend = backend  # host-graph fallback past _PP_BYTE_CAP
        self._checkpoint = checkpoint  # budget-safe chunk-prefix resume
        #: chunk index the last _execute resumed from (observability).
        self._resumed_from_chunk: Optional[int] = None
        self._cache = None
        self._pp_rows: Optional[list] = None

    def per_partition_rows(self) -> "_PerPartitionRows":
        return _PerPartitionRows(self)

    def __iter__(self):
        if self._cache is None:
            self._cache = [self._execute()]
        yield from self._cache

    def _execute(self) -> List[am.AggregateMetrics]:
        options = self._options
        params = options.aggregate_params
        public = self._public is not None
        vectors, all_params = _config_vectors(options)
        C = len(all_params)

        if options.pre_aggregated_data:
            # Pre-aggregated input: each row IS one (pid, pk) user record
            # carrying (count, sum, n_partitions) — stage A is skipped
            # entirely (host twin: NoOpContributionBounder, which also
            # never samples partitions).
            from pipelinedp_tpu.dp_engine import DataExtractors
            ex = self._extractors
            wrap = DataExtractors(
                privacy_id_extractor=None,
                partition_extractor=ex.partition_extractor,
                value_extractor=lambda row: tuple(
                    ex.preaggregate_extractor(row)))
            encoded = encode(self._col, wrap, 3, self._public,
                             require_pid=False)
        else:
            encoded = encode(self._col, self._extractors, None,
                             self._public)
        n_pad = _pad_rows(encoded.n_rows)
        P = len(encoded.pk_vocab)
        P_pad = _pad_pow2(max(P, 1))

        per_partition = self._return_per_partition
        if per_partition:
            # Decide the host fallback BEFORE any device placement: the
            # fetched [P, C] blocks' budget only needs the encode. The
            # config axis is chunk-padded on device, so budget
            # C + _CHUNK_CAP columns. (A mesh changes nothing here: the
            # blocks come back config-axis-sharded and gather to the
            # same host footprint.)
            n_metrics = sum(1 for m, _, _ in _METRIC_ORDER
                            if m in params.metrics)
            pp_bytes = (P_pad * (C + _CHUNK_CAP) *
                        (5 * n_metrics + 1) * 4)
            if pp_bytes > _PP_BYTE_CAP:
                return self._host_fallback()

        if options.pre_aggregated_data:
            pid, pk, values, valid = pad_and_put(encoded, 3)
            marker = valid
            pk_safe = pk
            count_u = values[:, 0]
            sum_u = values[:, 1]
            npart_u = values[:, 2]
        else:
            pid, pk, values, valid = pad_and_put(
                encoded, None, with_values=Metrics.SUM in params.metrics)
            marker, pk_safe, count_u, sum_u, npart_u = _preagg_kernel(
                pid, pk, values, valid)
        if (options.partitions_sampling_prob < 1 and
                not options.pre_aggregated_data):
            # Deterministic partition sampling, identical to the host
            # bounder's ValueSampler (SHA1 of the ORIGINAL key): drop the
            # sampled-out partitions' user records after stage A, so
            # npart_u still reflects each privacy id's pre-sampling
            # spread (reference analysis/contribution_bounders.py:38-75).
            # A sampled-out partition then looks empty downstream: it is
            # excluded privately, or pseudo-filled like a missing public
            # partition — both matching the host graph.
            from pipelinedp_tpu.sampling_utils import ValueSampler
            sampler = ValueSampler(options.partitions_sampling_prob)
            sampled_np = np.zeros(P_pad, bool)
            for i, k in enumerate(encoded.pk_vocab):
                # The host sampler hashes the ROW-extracted key (a Python
                # scalar); a public_partitions list can put numpy scalars
                # in the vocab, whose repr differs — normalize so both
                # planes sample the same subset.
                if isinstance(k, np.generic):
                    k = k.item()
                sampled_np[i] = sampler.keep(k)
            marker = marker & jnp.asarray(sampled_np)[pk_safe]
        users_pk = jax.ops.segment_sum(marker.astype(jnp.int32), pk_safe,
                                       num_segments=P_pad)
        # Partitions beyond the real vocab must not count as public.
        real_pk = jnp.arange(P_pad) < P

        metric_names = tuple(nm for m, nm, _ in _METRIC_ORDER
                             if m in params.metrics)
        noise_rows = np.stack([
            _noise_stds(m, all_params, self._budgets)
            for m, nm, _ in _METRIC_ORDER if m in params.metrics
        ]) if metric_names else np.zeros((0, C), np.float32)

        tg = PartitionSelectionStrategy.TRUNCATED_GEOMETRIC
        lap_t = PartitionSelectionStrategy.LAPLACE_THRESHOLDING
        if public:
            strategy = None
            table = np.ones((C, 2), np.float32)
            thr = np.zeros(C, np.float32)
            scale = np.ones(C, np.float32)
            is_tg = is_lap = np.zeros(C, bool)
        else:
            strategies = [p.partition_selection_strategy
                          for p in all_params]
            strategy = (strategies[0] if len(set(strategies)) == 1 else
                        _MIXED)
            table, thr, scale = _selection_tables(
                all_params, self._selection_budget.eps,
                self._selection_budget.delta)
            is_tg = np.asarray([s == tg for s in strategies], bool)
            is_lap = np.asarray([s == lap_t for s in strategies], bool)
        kinds = [p.noise_kind for p in all_params]
        # None = mixed per-config noise kinds (static sentinel).
        noise_kind = kinds[0] if len(set(kinds)) == 1 else None
        is_gauss = np.asarray([k == NoiseKind.GAUSSIAN for k in kinds],
                              bool)

        log_rs, t_table = _laplace_gauss_table(
            tuple(1.0 - q for q in ERROR_QUANTILES))

        # Config chunking: bound both the [n, Cc] broadcast and the
        # [P, Cc, 2·WINDOW+1] selection-window footprints. The
        # sweep_config_batch knob (0 = this auto sizing) pins the width
        # explicitly — every width is bit-identical per config.
        from pipelinedp_tpu.plan import knobs as _knobs
        n_dev = self._mesh.devices.size if self._mesh is not None else 1
        pinned = int(_knobs.value("sweep_config_batch"))
        if pinned > 0:
            # A pin is respected exactly (clamped to the chunk cap):
            # chunk=1 IS the walked mode the parity bench measures
            # against, so no lane rounding here.
            chunk = int(np.clip(pinned, 1, _CHUNK_CAP))
        else:
            chunk = int(np.clip(
                min(_CHUNK_ROW_BUDGET // max(n_pad, 1),
                    (1 << 28) // max(P_pad * (2 * _WINDOW + 1), 1),
                    _pad_pow2(C, minimum=1)),  # don't pad tiny sweeps up
                1, _CHUNK_CAP))
            # Lane-align the config axis: every [n, Cc] / [P, Cc, w]
            # operand carries Cc in the TPU lane dimension, which tiles
            # in units of 128 (measured 6x on the 10k-config sweep).
            chunk = _lane_align(chunk)
            from pipelinedp_tpu import obs as _obs
            _obs.event("sweep.chunk_planned", chunk=int(chunk),
                       rows=int(n_pad), partitions=int(P_pad))
        if n_dev > 1:
            # Sharded over the mesh: every device takes an equal slice of
            # the chunk's configuration axis.
            chunk = max(chunk // n_dev, 1) * n_dev
        users_in = jnp.where(real_pk, users_pk, -1)

        # Pad every per-config vector to a chunk multiple (repeating the
        # last config) and place it on device ONCE; chunks then slice on
        # device, and all chunk outputs stay device-resident until one
        # final fetch — the high-latency link is paid twice total, not
        # twice per chunk.
        C_pad = -(-C // chunk) * chunk

        def cpad(a, axis=0):
            a = np.asarray(a)
            reps = C_pad - a.shape[axis]
            if reps:
                tail = np.repeat(np.take(a, [-1], axis=axis), reps, axis)
                a = np.concatenate([a, tail], axis)
            return a

        host_cfg = (cpad(vectors["l0"]), cpad(vectors["linf"]),
                    cpad(vectors["min_sum"]), cpad(vectors["max_sum"]),
                    # Rows [M:] are the host-precomputed squares the
                    # kernel adds to var_l0 (squaring on device invites
                    # a width-dependent fma contraction, see
                    # _metric_chunk).
                    cpad(np.concatenate([noise_rows,
                                         noise_rows * noise_rows]),
                         axis=1) if len(noise_rows) else
                    np.zeros((0, C_pad), np.float32),
                    cpad(table), cpad(thr), cpad(scale), cpad(is_tg),
                    cpad(is_lap), cpad(is_gauss))
        if self._mesh is not None and n_dev > 1:
            # Place the replicated row arrays, config vectors and tables
            # on the mesh ONCE: left committed to a single device they
            # would re-broadcast to every device on each chunk iteration.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec

            from pipelinedp_tpu.parallel import sharded as _psh
            repl_sharding = NamedSharding(self._mesh, PSpec())
            # put_global, NOT device_put: on a multi-process mesh a raw
            # device_put here would dispatch a hidden equality-check
            # collective per array that races with the sweep kernel's
            # all_gathers (see parallel/sharded.py:put_global).
            (marker, pk_safe, count_u, sum_u, npart_u, users_in, dlog_rs,
             dt_table) = _psh.put_global(
                 (marker, pk_safe, count_u, sum_u, npart_u, users_in,
                  log_rs, t_table), repl_sharding)
            cfg = _psh.put_global(host_cfg, repl_sharding)
        else:
            dlog_rs, dt_table = jax.device_put((log_rs, t_table))
            cfg = jax.device_put(host_cfg)

        # Budget-safe chunk-prefix resume (the streamed-aggregation
        # checkpoint pattern applied to the sweep): each chunk's
        # per-configuration outputs are a pure function of (data,
        # config), so persisting the completed-chunk prefix after every
        # chunk lets a killed sweep resume from its last chunk instead
        # of restarting the whole grid. The fingerprint covers the
        # chunking, the config vectors and the data content — a
        # checkpoint from a different sweep refuses to resume.
        # Per-partition sweeps skip checkpointing (their [P, C] blocks
        # dwarf the aggregate state; they fall back to a full rerun).
        import os as _os

        from pipelinedp_tpu import obs
        from pipelinedp_tpu.resilience import checkpoint as ckpt_mod
        from pipelinedp_tpu.resilience import faults
        ckpt_store = (ckpt_mod.as_store(self._checkpoint)
                      if not per_partition else None)
        if ckpt_store is not None:
            # The sweep checkpoints into a SIBLING file of the backend's
            # checkpoint path: the streamed aggregation owns the path
            # itself, and the two features must never collide — a
            # leftover stream checkpoint would raise CheckpointMismatch
            # here, and deleting it would destroy the stream's
            # budget-safe resume state.
            ckpt_store = ckpt_mod.CheckpointStore(
                ckpt_store.path + ".sweep")
        # Chunks between checkpoint writes (the streaming loop's knob):
        # every save fetches + rewrites the full accumulated prefix, so
        # large sweeps on slow disks can throttle it.
        ckpt_every = max(1, int(_os.environ.get(
            "PIPELINEDP_TPU_CKPT_EVERY", "1")))
        acc_flat = None  # host arrays, concatenated over done chunks
        done_chunks = 0
        ckpt_fp = None
        if ckpt_store is not None:
            ckpt_fp = ckpt_mod.sweep_fingerprint(
                repr((metric_names, str(strategy), str(noise_kind),
                      public, options.epsilon, options.delta,
                      options.partitions_sampling_prob,
                      bool(options.pre_aggregated_data))),
                C, chunk, P_pad, n_dev,
                data=ckpt_mod.data_digest(encoded), arrays=host_cfg)
            saved = ckpt_store.load_for(ckpt_fp)
            if saved is not None:
                done_chunks = saved.next_batch
                acc_flat = dict(saved.arrays)
        self._resumed_from_chunk = done_chunks

        def flatten_host(out, sel):
            """One chunk's outputs fetched to host, flat-keyed (the
            checkpoint array namespace)."""
            flat = {}
            for nm in metric_names:
                for f, v in out[nm].items():
                    flat[f"o:{nm}:{f}"] = np.asarray(v)
            if sel is not None:
                for f, v in sel.items():
                    flat[f"s:{f}"] = np.asarray(v)
            return flat

        import time as _time

        from pipelinedp_tpu.obs import monitor as _monitor

        chunk_outs = []
        pp_chunks = []
        n_chunks = -(-C // chunk)
        t_sweep0 = _time.monotonic()
        live_configs = 0  # configs dispatched THIS run (excl. resume)
        for ci, start in enumerate(range(0, C, chunk)):
            if ckpt_store is not None and ci < done_chunks:
                continue  # restored from the checkpoint prefix
            # Injectable kill points (the streaming loop's chunk-kill
            # twin, plus the megasweep's own seam): tests sever the
            # sweep at config chunk ci and assert the resumed grid is
            # bit-identical.
            faults.check_chunk(ci)
            faults.check_sweep_config_chunk(ci)
            # Megasweep heartbeat: the monitor's push registry carries
            # configs done vs planned + configs/s, so a stalled config
            # batch is nameable from the heartbeat alone.
            el = _time.monotonic() - t_sweep0
            _monitor.update_sweep({
                "configs_done": min(ci * chunk, C),
                "configs_planned": C,
                "chunk": ci,
                "chunks_planned": n_chunks,
                "config_batch": chunk,
                "configs_per_s": round(live_configs / el, 1) if el > 0
                else 0.0,
                "resumed_from_chunk": done_chunks,
            })
            # Ledger span per sweep chunk (a no-op unless
            # PIPELINEDP_TPU_TRACE is set); dispatch is async, so an
            # untraced chunk costs nothing and a traced one shows where
            # the checkpoint fetches serialize the grid.
            with obs.span("sweep.chunk", cat="sweep", chunk=ci,
                          start=int(start)):
                if self._mesh is not None and n_dev > 1:
                    out, sel, pp = _sweep_chunk_sharded(
                        metric_names, strategy, noise_kind, P_pad,
                        public, chunk, self._mesh, np.int32(start),
                        marker, pk_safe, count_u, sum_u, npart_u,
                        users_in, *cfg, dlog_rs, dt_table,
                        per_partition=per_partition)
                    if per_partition:
                        pp_chunks.append(pp)
                else:
                    out, sel = _sweep_chunk_kernel(
                        metric_names, strategy, noise_kind, P_pad,
                        public, chunk, np.int32(start), marker, pk_safe,
                        count_u, sum_u, npart_u, users_in, *cfg,
                        dlog_rs, dt_table,
                        per_partition=per_partition)
                    if per_partition:
                        pp_chunks.append(_split_pp(out, metric_names))
            if ckpt_store is not None:
                # Checkpointing fetches per chunk (the price of
                # resumability); the monoid append keeps the prefix
                # bit-identical to an uninterrupted accumulation. The
                # accumulated state is small ([C]-sized fields), so the
                # re-concatenate per chunk is noise next to the fetch.
                flat = flatten_host(out, sel)
                acc_flat = (flat if acc_flat is None else
                            {k: np.concatenate([acc_flat[k], flat[k]])
                             for k in flat})
                if (ci + 1) % ckpt_every == 0:  # same boundary rule
                    # as the streaming fold's checkpoint cadence.
                    ckpt_store.save(ckpt_mod.StreamCheckpoint(
                        ckpt_fp, ci + 1, acc_flat))
            else:
                chunk_outs.append((out, sel))
            live_configs += chunk

        # The grid completed: clear the heartbeat's sweep section. (A
        # KILLED sweep deliberately leaves its last snapshot installed,
        # so the stall watchdog names the blocked config batch.)
        _monitor.update_sweep(None)

        if ckpt_store is not None:
            # Reassemble the flat checkpoint namespace; the trailing
            # config padding (last chunk) slices off exactly as in the
            # device-concat path below.
            out_cat = {nm: {} for nm in metric_names}
            sel_cat = {}
            for k, v in acc_flat.items():
                if k.startswith("o:"):
                    _, nm, f = k.split(":", 2)
                    out_cat[nm][f] = v[:C]
                else:
                    sel_cat[k[2:]] = v[:C]
            sel_cat = sel_cat or None
        else:
            # ONE flat d2h transfer for every output field of every
            # chunk.
            treedef = jax.tree.structure(chunk_outs[0])
            fields_cat = _fetch_concat(
                [jax.tree.leaves(c) for c in chunk_outs], 0)
            out_cat, sel_cat = jax.tree.unflatten(
                treedef, [f[:C] for f in fields_cat])
        fields = {nm: out_cat[nm] for nm in metric_names}
        sel_fields = sel_cat

        if per_partition:
            keys = sorted(pp_chunks[0])
            # One flat d2h transfer for all [P_pad, C] blocks.
            cat = _fetch_concat([[c[k] for k in keys] for c in pp_chunks],
                                1)
            blocks = {k: b[:P, :C] for k, b in zip(keys, cat)}
            users_np = np.asarray(users_in)[:P]
            mask_np = (users_np > 0) | (public & (users_np == 0))
            self._pp_rows = self._assemble_pp(
                all_params, metric_names, blocks, mask_np, noise_rows,
                encoded.pk_vocab, public)

        result = self._pack(all_params, fields, sel_fields, noise_rows,
                            metric_names)
        if ckpt_store is not None:
            # The sweep released its outputs: a finished run must not be
            # resumable (mirrors the streaming-checkpoint contract).
            ckpt_store.clear()
        return result

    def _host_fallback(self):
        """Per-partition sweeps past the fetch budget run the host
        analysis graph instead (same rows, Python speed)."""
        from pipelinedp_tpu.analysis import utility_analysis as ua
        res, pp = ua._host_analysis(
            self._col, self._backend, self._options, self._extractors,
            self._public, return_per_partition=True)
        self._pp_rows = list(pp)
        return list(res)[0]

    def _assemble_pp(self, all_params, metric_names, blocks, mask_np,
                     noise_rows, vocab, public):
        """Fetched [P, C] blocks -> host rows in the host graph's
        per-partition format: (pk, flat tuple of per-config entries —
        [p_keep] + one SumMetrics per analyzed metric, configs
        sequential). Reference ``analysis/utility_analysis.py:60-77``."""
        import math as _math

        private = self._public is None
        rows = []
        pidx = np.flatnonzero(mask_np)
        C = len(all_params)
        keep = blocks["_pp_keep"]
        for p in pidx.tolist():
            entries = []
            for c in range(C):
                if private:
                    entries.append(float(keep[p, c]))
                for row_i, nm in enumerate(metric_names):
                    entries.append(am.SumMetrics(
                        sum=float(blocks[f"{nm}.pp_sum"][p, c]),
                        per_partition_error_min=float(
                            blocks[f"{nm}.pp_err_min"][p, c]),
                        per_partition_error_max=float(
                            blocks[f"{nm}.pp_err_max"][p, c]),
                        expected_cross_partition_error=float(
                            blocks[f"{nm}.pp_exp_l0"][p, c]),
                        std_cross_partition_error=_math.sqrt(max(
                            float(blocks[f"{nm}.pp_var_l0"][p, c]), 0.0)),
                        std_noise=float(noise_rows[row_i][c]),
                        noise_kind=all_params[c].noise_kind))
            rows.append((vocab[p], tuple(entries)))
        return rows

    def _pack(self, all_params, fields, sel_fields, noise_rows,
              metric_names) -> List[am.AggregateMetrics]:
        """Host normalization — the vectorized twin of
        ``SumAggregateErrorMetricsCombiner.compute_metrics``."""
        results = []
        type_of = {nm: t for _, nm, t in _METRIC_ORDER}
        for i, p in enumerate(all_params):
            packed = am.AggregateMetrics(input_aggregate_params=p)
            if sel_fields is not None:
                packed.partition_selection_metrics = am.PartitionSelectionMetrics(
                    num_partitions=float(sel_fields["num_partitions"][i]),
                    dropped_partitions_expected=float(
                        sel_fields["num_partitions"][i] -
                        sel_fields["keep_sum"][i]),
                    dropped_partitions_variance=float(
                        sel_fields["keep_var"][i]))
            for row, nm in enumerate(metric_names):
                f = fields[nm]
                kept = max(float(f["kept_partitions_expected"][i]), 1e-30)
                nparts = max(float(f["num_partitions"][i]), 1.0)
                total = max(1.0, float(f["total_aggregate"][i]))
                g = lambda k: float(f[k][i])
                gq = lambda k: [float(x) for x in f[k][i]]
                el0 = g("error_l0_expected") / kept
                emin = g("error_linf_min_expected") / kept
                emax = g("error_linf_max_expected") / kept
                rel0 = g("rel_error_l0_expected") / kept
                remin = g("rel_error_linf_min_expected") / kept
                remax = g("rel_error_linf_max_expected") / kept
                m = am.AggregateErrorMetrics(
                    metric_type=type_of[nm],
                    ratio_data_dropped_l0=g("data_dropped_l0") / total,
                    ratio_data_dropped_linf=g("data_dropped_linf") / total,
                    ratio_data_dropped_partition_selection=(
                        g("data_dropped_partition_selection") / total),
                    error_l0_expected=el0,
                    error_linf_expected=emin + emax,
                    error_linf_min_expected=emin,
                    error_linf_max_expected=emax,
                    error_expected=el0 + emin + emax,
                    error_l0_variance=g("error_l0_variance") / kept,
                    error_variance=g("error_variance") / kept,
                    error_quantiles=[q / kept for q in
                                     gq("error_quantiles")],
                    rel_error_l0_expected=rel0,
                    rel_error_linf_expected=remin + remax,
                    rel_error_linf_min_expected=remin,
                    rel_error_linf_max_expected=remax,
                    rel_error_expected=rel0 + remin + remax,
                    rel_error_l0_variance=g("rel_error_l0_variance") / kept,
                    rel_error_variance=g("rel_error_variance") / kept,
                    rel_error_quantiles=[
                        q / kept for q in gq("rel_error_quantiles")],
                    error_expected_w_dropped_partitions=(
                        g("error_expected_w_dropped_partitions") / nparts),
                    rel_error_expected_w_dropped_partitions=(
                        g("rel_error_expected_w_dropped_partitions") /
                        nparts),
                    noise_std=float(noise_rows[row][i]))
                if nm == "sum":
                    packed.sum_metrics = m
                elif nm == "count":
                    packed.count_metrics = m
                else:
                    packed.privacy_id_count_metrics = m
            results.append(packed)
        return results


def build_fused_sweep(col, options, data_extractors, public_partitions,
                      budget_accountant, mesh=None,
                      return_per_partition=False,
                      backend=None, checkpoint=None) -> LazySweepResult:
    """Requests the same budgets the host analysis engine would
    (``utility_analysis_engine.py:61-99``) and returns the lazy sweep.
    ``checkpoint`` (a path or ``resilience.checkpoint.CheckpointStore``)
    enables budget-safe chunk-prefix resume of the configuration grid —
    a killed sweep restarts from its last completed chunk instead of
    from scratch. The sweep writes a ``<path>.sweep`` SIBLING file so a
    backend shared with streamed aggregations never collides with (or
    destroys) a stream's own resume state; save cadence follows
    ``PIPELINEDP_TPU_CKPT_EVERY``."""
    params = options.aggregate_params
    mechanism_type = data_structures.analysis_mechanism_type(options)
    selection_budget = None
    if public_partitions is None:
        selection_budget = budget_accountant.request_budget(
            MechanismType.GENERIC, weight=params.budget_weight)
    budgets = {}
    for metric in params.metrics:
        budgets[metric] = budget_accountant.request_budget(
            mechanism_type, weight=params.budget_weight)
    if return_per_partition and backend is None:
        raise ValueError(
            "return_per_partition needs the pipeline backend (the "
            "byte-capped host-graph fallback runs on it); pass "
            "backend=... to build_fused_sweep")
    return LazySweepResult(col, options, data_extractors,
                           public_partitions, budgets, selection_budget,
                           mesh=mesh,
                           return_per_partition=return_per_partition,
                           backend=backend, checkpoint=checkpoint)
