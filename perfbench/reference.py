"""The plain reference, its control, and the comparison that decides
``correct``.

The reference is numpy over the same host columns the system was
handed, in float64, and imports nothing of the program. The generator
(``synth.py``) gives every privacy unit at most
``max_partitions_contributed`` rows, one per partition, so contribution
bounding drops nothing and the exact per-partition answer is the
release's answer up to noise. At the configuration's release epsilon
that noise lies below the limits of the answers.

The guarantee is held apart from the answers, from the configuration's
epsilon, delta and bounds alone: the noise, by the spread of the
residuals against the Laplace scale PipelineDP's budget split gives
(``noise_scales``), and the kept set, against the keep probability of
the stated selection strategy (``keep_probability``).

The control is the same reference computed in bfloat16, the precision
below the float32 the configurations state, as a TPU computes in it:
bfloat16 values, float32 accumulators, bfloat16 results. The
comparison must fail it.
"""

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def percentiles_of(query: dict):
    return [float(m[len("PERCENTILE("):-1]) for m in query["metrics"]
            if m.startswith("PERCENTILE(")]


def field_names(query: dict):
    """The MetricsTuple fields the comparison reads."""
    names = []
    for m in query["metrics"]:
        if m.startswith("PERCENTILE("):
            q = float(m[len("PERCENTILE("):-1])
            names.append(f"percentile_{q:g}")
        else:
            names.append(m.lower())
    return names


class Exact:
    """Per-partition exact answers over dense partition keys
    0..n_parts-1, computed once per run and shared by every release."""

    def __init__(self, pid, pk, values, n_parts: int, query: dict):
        del pid  # one row per (unit, partition): units == rows
        self.n_parts = n_parts
        self.count = np.bincount(pk, minlength=n_parts)
        self.units = self.count
        self.total = np.bincount(pk, weights=values, minlength=n_parts)
        self.percentiles = percentiles_of(query)
        if self.percentiles or "VARIANCE" in query["metrics"]:
            self.total2 = np.bincount(pk, weights=values * values,
                                      minlength=n_parts)
        if self.percentiles:
            order = np.lexsort((values, pk))
            self.sorted_values = values[order]
            self.start = np.cumsum(self.count) - self.count


def released_columns(rows, fields):
    """(keys int64, {field: float64}) of one release's
    [(partition_key, MetricsTuple)]."""
    keys = np.fromiter((k for k, _ in rows), np.int64, len(rows))
    cols = {f: np.fromiter((getattr(m, f) for _, m in rows), np.float64,
                           len(rows)) for f in fields}
    return keys, cols


def compare(keys, cols, ref: Exact, config: dict) -> dict:
    """The numbers compared for one release, by name. Each is a count
    of wrong answers or a widest gap, so larger is worse."""
    g = config["guarantees"]
    out = {}
    ok = (keys >= 0) & (keys < ref.n_parts)
    ok[ok] = ref.count[keys[ok]] > 0
    out["kept_extra"] = int(np.sum(~ok))
    keys = keys[ok]
    cols = {f: c[ok] for f, c in cols.items()}
    # The kept set: a partition the stated selection keeps with
    # probability above 1 - 1e-6 has to be released, and one it keeps
    # with probability under 1e-6 must not be.
    p_keep = keep_probability(ref.units, config["query"], g)
    must = np.flatnonzero(p_keep > 1 - SURE)
    out["kept_missing"] = int(np.sum(~np.isin(must, keys)))
    out["kept_unlikely"] = int(np.sum(p_keep[keys] < SURE))
    n = ref.count[keys].astype(np.float64)
    total = ref.total[keys]
    scales = noise_scales(config["query"], g)
    if scales:
        out["noise_scale_err"] = _noise_scale_err(cols, n, total, scales, g)
    if "count" in cols:
        out["count_err"] = _widest(cols["count"] - n)
    if "sum" in cols:
        out["sum_err"] = _widest(cols["sum"] - total)
    if "mean" in cols:
        # The mean's error scales as 1/count: compare it times the count.
        out["mean_err_x_n"] = _widest((cols["mean"] - total / n) * n)
    if "variance" in cols:
        var = ref.total2[keys] / n - (total / n)**2
        out["variance_err"] = _widest(cols["variance"] - var)
    if ref.percentiles:
        # With n rows, percentile q walks the quantile tree to the leaf
        # of the order statistic of rank ceil(q/100 * n); at the release
        # epsilon the noisy node counts move that rank by less than one.
        # So each percentile lies within one leaf of the order
        # statistics one rank either side (a rank on a count boundary
        # may go to either neighbour).
        q_cfg = config["query"]["quantile_tree"]
        lo_v, hi_v = g["min_value"], g["max_value"]
        leaf = (hi_v - lo_v) / q_cfg["branching"]**q_cfg["height"]
        got = np.stack([cols[f"percentile_{q:g}"] for q in ref.percentiles])
        bad = ~((got >= lo_v) & (got <= hi_v)).all(axis=0)
        bad |= ~(np.diff(got, axis=0) >= 0).all(axis=0)
        out["pct_unordered"] = int(np.sum(bad))
        ni = ref.count[keys]
        start = ref.start[keys]
        sv = ref.sorted_values
        outside = 0
        for q, gq in zip(ref.percentiles, got):
            k = np.ceil(q / 100.0 * ni).astype(np.int64) - 1
            lo = sv[start + np.clip(k - 1, 0, ni - 1)] - leaf
            hi = sv[start + np.clip(k + 1, 0, ni - 1)] + leaf
            outside += int(np.sum((gq < lo) | (gq > hi)))
        out["pct_outside"] = outside
    return out


def _widest(diff) -> float:
    return float(np.max(np.abs(diff), initial=0.0))


# A kept-set outcome this unlikely under the stated selection is a fault.
SURE = 1e-6


def mechanisms(query: dict, g: dict) -> dict:
    """PipelineDP's budget requests for the query, by name, each with
    the number of equal parts its mechanism splits its share into
    (``pipeline_dp/combiners.py`` compound combiner; private selection
    is one more, ``dp_engine.py``)."""
    m = set(query["metrics"])
    out = {}
    if "VARIANCE" in m:
        out["mean_var"] = 3
    elif "MEAN" in m:
        out["mean_var"] = 2
    else:
        out.update({k.lower(): 1 for k in ("COUNT", "SUM") if k in m})
    if "PRIVACY_ID_COUNT" in m:
        out["privacy_id_count"] = 1
    if any(x.startswith("PERCENTILE(") for x in m):
        out["percentile"] = 1
    if g["partition_selection"] == "private":
        out["selection"] = 1
    return out


def noise_scales(query: dict, g: dict) -> dict:
    """The Laplace scale of the count's noise and of the normalized
    sum's (values less the middle of [min, max]) that the guarantees
    give, for the released fields that carry them directly. Under
    NaiveBudgetAccountant every mechanism gets an equal share of
    epsilon and the Laplace ones no delta."""
    if g["noise_kind"] != "LAPLACE":
        raise ValueError("only Laplace noise is checked")
    mech = mechanisms(query, g)
    eps = g["release_epsilon"] / len(mech)
    l1 = g["max_partitions_contributed"] * g[
        "max_contributions_per_partition"]
    half = (g["max_value"] - g["min_value"]) / 2
    fields = set(field_names(query))
    out = {}
    if "mean_var" in mech:
        e = eps / mech["mean_var"]
        if "count" in fields:
            out["count"] = l1 / e
        if "sum" in fields and "count" in fields:
            out["nsum"] = l1 * half / e
    else:
        if "count" in fields:
            out["count"] = l1 / eps
        if "sum" in fields:
            out["sum"] = l1 * max(abs(g["min_value"]),
                                  abs(g["max_value"])) / eps
    return out


def _noise_scale_err(cols, n, total, scales, g) -> float:
    """The widest |ln(measured scale / stated scale)| over the noise
    components: the measured scale is the mean absolute residual, the
    Laplace scale's maximum-likelihood estimate. Under MEAN, PipelineDP
    releases sum = mean x count, so the sum's residual less the middle
    times the count's is the normalized sum's noise; partitions whose
    exact mean is min or max, where the mean's clamp can cut the noise,
    are left out."""
    r = {}
    if "count" in scales:
        r["count"] = cols["count"] - n
    if "nsum" in scales:
        middle = (g["min_value"] + g["max_value"]) / 2
        mean = total / n
        inside = (mean > g["min_value"]) & (mean < g["max_value"])
        r["nsum"] = (cols["sum"] - total - middle * r["count"])[inside]
    if "sum" in scales:
        r["sum"] = cols["sum"] - total
    worst = 0.0
    for k, b in scales.items():
        if len(r[k]) == 0:
            continue
        measured = max(float(np.mean(np.abs(r[k]))), 1e-300)
        worst = max(worst, abs(float(np.log(measured / b))))
    return worst


def keep_probability(units, query: dict, g: dict) -> np.ndarray:
    """The probability that private selection keeps a partition with
    ``units`` privacy units: the truncated geometric strategy
    (Desfontaines et al. 2022, "Differentially private partition
    selection") with its equal share of epsilon and all of delta, per
    partition over ``max_partitions_contributed``. Public partitions:
    every populated one is kept."""
    units = np.asarray(units)
    if g["partition_selection"] != "private":
        return (units > 0).astype(np.float64)
    if g.get("partition_selection_strategy",
             "TRUNCATED_GEOMETRIC") != "TRUNCATED_GEOMETRIC":
        raise ValueError("only TRUNCATED_GEOMETRIC selection is checked")
    l0 = g["max_partitions_contributed"]
    e = g["release_epsilon"] / len(mechanisms(query, g)) / l0
    d = g["delta"] / l0
    top = int(units.max(initial=0))
    pi = np.zeros(top + 1)
    with np.errstate(over="ignore"):
        grow = np.exp(e)
    for i in range(1, top + 1):
        p = pi[i - 1]
        pi[i] = min(p * grow + d if p > 0 else d,
                    1 - np.exp(-e) * (1 - p - d), 1.0)
        if pi[i] >= 1.0:
            pi[i:] = 1.0
            break
    return pi[units]


def merge(readings):
    """The worst of each number over several releases."""
    out = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out


def control_release(pid, pk, values, query: dict):
    """The reference in bfloat16, put in the program's place: every
    populated partition, values rounded to bfloat16, each field
    accumulated in float32 and released in bfloat16. (keys, cols) as
    ``released_columns`` gives them."""
    del pid
    pct = percentiles_of(query)
    order = (np.lexsort((values, pk)) if pct else
             np.argsort(pk, kind="stable"))
    spk = pk[order]
    sv = values[order].astype(BF16).astype(np.float32)
    starts = np.flatnonzero(np.r_[True, spk[1:] != spk[:-1]])
    keys = spk[starts]
    n = np.diff(np.r_[starts, len(sv)]).astype(np.float32)
    total = np.add.reduceat(sv, starts, dtype=np.float32)
    cols = {"count": n, "sum": total, "mean": total / n}
    if "VARIANCE" in query["metrics"]:
        total2 = np.add.reduceat(sv * sv, starts, dtype=np.float32)
        cols["variance"] = total2 / n - (total / n)**2
    for q in pct:
        rank = np.ceil(q / 100.0 * n).astype(np.int64) - 1
        cols[f"percentile_{q:g}"] = sv[starts + rank]
    return keys, {f: cols[f].astype(BF16).astype(np.float64)
                  for f in field_names(query)}
