"""The generator and the reference, on the CPU."""

import numpy as np
import pytest

import reference
import synth

SPEC = {
    "rows": 30_000, "privacy_units": 900, "partitions": 400,
    "values": {"kind": "grid", "lo": 0.5, "hi": 5.0, "step": 0.5},
    "rows_per_unit": {"min": 3, "median": 20, "max": 350},
    "partition_zipf_a": 1.3, "sizes_seed": 11,
}
CONFIG = {"query": {"metrics": ["COUNT", "SUM", "MEAN", "VARIANCE",
                                "PERCENTILE(50)", "PERCENTILE(99)"],
                    "quantile_tree": {"height": 4, "branching": 16}},
          "guarantees": {"min_value": 0.5, "max_value": 5.0,
                         "release_epsilon": 1e8, "delta": 1e-6,
                         "noise_kind": "LAPLACE",
                         "max_partitions_contributed": 350,
                         "max_contributions_per_partition": 1,
                         "partition_selection": "private"}}


def test_rows_per_unit_keeps_the_stated_tail():
    k = synth.rows_per_unit(SPEC)
    assert k.sum() == SPEC["rows"] and len(k) == SPEC["privacy_units"]
    assert k.min() >= 3 and k.max() == 350


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_columns_bound_nothing_and_keep_sizes_across_seeds(seed):
    pid, pk, v = synth.columns(SPEC, seed)
    assert len(pid) == SPEC["rows"]
    # One row per (unit, partition), at most max per unit: bounding at
    # L0 = max, Linf = 1 drops nothing.
    pairs = pid * SPEC["partitions"] + pk
    assert len(np.unique(pairs)) == len(pairs)
    np.testing.assert_array_equal(np.bincount(pid), synth.rows_per_unit(SPEC))
    assert set(np.unique(v)) <= set(0.5 * np.arange(1, 11))
    # Another seed: the same rows per unit and per partition (so the
    # same populated count), under other keys, values and row order.
    pid2, pk2, v2 = synth.columns(SPEC, seed + 1)
    np.testing.assert_array_equal(np.bincount(pid2), np.bincount(pid))
    per_part = np.sort(np.bincount(pk, minlength=SPEC["partitions"]))
    np.testing.assert_array_equal(
        np.sort(np.bincount(pk2, minlength=SPEC["partitions"])), per_part)
    assert not np.array_equal(pk, pk2) and not np.array_equal(v, v2)


def test_exact_release_reads_zero_and_control_does_not():
    pid, pk, v = synth.columns(SPEC, 3)
    ref = reference.Exact(pid, pk, v, SPEC["partitions"], CONFIG["query"])
    keys = np.flatnonzero(ref.count)
    n = ref.count[keys]
    order = np.lexsort((v, pk))
    sv = v[order]
    cols = {"count": n.astype(float), "sum": ref.total[keys],
            "mean": ref.total[keys] / n,
            "variance": ref.total2[keys] / n - (ref.total[keys] / n)**2}
    for q in (50, 99):
        rank = np.ceil(q / 100 * n).astype(int) - 1
        cols[f"percentile_{q}"] = sv[ref.start[keys] + rank]
    exact = reference.compare(keys, cols, ref, CONFIG)
    # No noise at all is a fault of its own; every answer is exact.
    assert exact.pop("noise_scale_err") > 5
    assert all(x == 0 for x in exact.values()), exact
    # A missing partition, one with no rows, and a shifted answer show.
    cols2 = {f: c[1:] for f, c in cols.items()}
    cols2["sum"] = cols2["sum"] + 0.5
    wrong = reference.compare(np.r_[keys[1:]], cols2, ref, CONFIG)
    assert wrong["sum_err"] == 0.5 and wrong["kept_missing"] >= (n[0] >= 2)
    extra = reference.compare(np.r_[keys, SPEC["partitions"] + 5],
                              {f: np.r_[c, 0.0] for f, c in cols.items()},
                              ref, CONFIG)
    assert extra["kept_extra"] == 1
    control = reference.compare(*reference.control_release(
        pid, pk, v, CONFIG["query"]), ref, CONFIG)
    assert control["count_err"] >= 1 and control["sum_err"] > 1
    assert control["variance_err"] > 1e-3


def test_noise_and_selection_are_held_to_the_guarantee():
    """Laplace noise at the stated scales reads near 0; half of it or a
    hundred times it does not. A partition with one unit that the
    selection keeps with probability delta / L0 reads as kept_unlikely."""
    g = CONFIG["guarantees"]
    query = {"metrics": ["COUNT", "SUM", "MEAN"]}
    pid, pk, v = synth.columns(SPEC, 4)
    ref = reference.Exact(pid, pk, v, SPEC["partitions"], query)
    config = {"query": query, "guarantees": g}
    scales = reference.noise_scales(query, g)
    # MEAN's half of eps/2 (selection takes the other half), L1 = L0.
    assert scales["count"] == pytest.approx(350 / (1e8 / 4))
    assert scales["nsum"] == pytest.approx(350 * 2.25 / (1e8 / 4))
    keys = np.flatnonzero(ref.count)
    n = ref.count[keys].astype(float)
    rng = np.random.default_rng(0)

    def release(factor):
        nc = rng.laplace(0, factor * scales["count"], len(keys))
        ns = rng.laplace(0, factor * scales["nsum"], len(keys))
        return {"count": n + nc, "sum": ref.total[keys] + ns + 2.75 * nc,
                "mean": ref.total[keys] / n}

    assert reference.compare(keys, release(1), ref,
                             config)["noise_scale_err"] < 0.15
    for factor in (0.5, 100):
        assert reference.compare(keys, release(factor), ref,
                                 config)["noise_scale_err"] > 0.6
    p = reference.keep_probability(np.array([0, 1, 2, 50]), query, g)
    assert p[0] == 0 and p[1] == pytest.approx(1e-6 / 350)
    assert p[2] == p[3] == 1.0
    # A stricter epsilon keeps a partition with a few units only sometimes.
    p = reference.keep_probability(np.array([1, 2, 3]), query,
                                   dict(g, release_epsilon=2000.0))
    assert 0 < p[0] < p[1] < p[2] < 1
