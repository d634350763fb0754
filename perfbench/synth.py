"""The benchmark's one data generator.

A configuration's ``dataset`` block and a run's seed give the numpy
columns a user hands the system (privacy id, partition key, value).
Copied from ``chip_smoke.py``'s ``bounded_dataset`` (PR 21), so that
the yardstick does not change when the program does.

Every privacy unit holds at most ``rows_per_unit.max`` rows, each in a
distinct partition: bounding at ``max_partitions_contributed = max``
and ``max_contributions_per_partition = 1`` drops nothing, so the exact
per-partition answer is the plain numpy one.

The sizes are drawn from the configuration's own ``sizes_seed``, not
from the run's seed: how many rows each unit holds and which partition
ranks it covers, so every run seed has the same rows per unit, rows per
partition and populated partitions (the engine compiles programs for
the populated count, and a mesh for its rows per device). The run's
seed draws which key each rank gets, the values and the row order.
"""

import numpy as np


def rows_per_unit(spec: dict) -> np.ndarray:
    """Rows of each privacy unit: a shifted log-normal tail with the
    source's least, median and greatest rows per unit. Its width is
    fitted so that the rows sum to exactly ``spec["rows"]``; one unit
    holds the greatest."""
    n_rows, n_units = spec["rows"], spec["privacy_units"]
    tail = spec["rows_per_unit"]
    lo, med, hi = tail["min"], tail["median"], tail["max"]
    if not n_units * lo <= n_rows <= n_units * hi or hi > spec["partitions"]:
        raise ValueError(f"{n_rows} rows cannot fit {n_units} units of "
                         f"{lo} to {hi} distinct partitions each")
    z = np.random.default_rng(spec["sizes_seed"]).standard_normal(n_units)

    def draw(sigma):
        return np.minimum(np.floor(lo + (med - lo) * np.exp(sigma * z)), hi)

    s_lo, s_hi = 0.0, 10.0  # the sum grows with the width
    for _ in range(100):
        mid = 0.5 * (s_lo + s_hi)
        s_lo, s_hi = (mid, s_hi) if draw(mid).sum() < n_rows else (s_lo, mid)
    k = draw(s_lo).astype(np.int64)
    k[np.argmax(k)] = hi
    while k.sum() != n_rows:
        diff = int(n_rows - k.sum())
        if diff > 0:
            k[np.flatnonzero(k < hi)[:diff]] += 1
        else:
            k[np.flatnonzero(k > lo)[:-diff]] -= 1
    return k


def values(spec: dict, rng, n: int) -> np.ndarray:
    """``grid``: a rating scale (lo, lo + step, ..., hi); ``uniform``:
    continuous on [lo, hi)."""
    v = spec["values"]
    if v["kind"] == "grid":
        steps = int(round((v["hi"] - v["lo"]) / v["step"])) + 1
        return v["lo"] + v["step"] * rng.integers(0, steps, n).astype(
            np.float64)
    if v["kind"] == "uniform":
        return rng.uniform(v["lo"], v["hi"], n)
    raise ValueError(f"unknown value kind {v['kind']!r}")


def columns(spec: dict, seed: int):
    """(privacy_ids int64, partition_keys int64, values float64), in a
    seeded random row order. Each unit covers a run of consecutive
    partition ranks from a Zipf-distributed start; the ranks are
    shuffled into partition keys, so low ranks are the popular
    partitions."""
    k = rows_per_unit(spec)
    n_rows, n_parts = spec["rows"], spec["partitions"]
    sizes = np.random.default_rng(spec["sizes_seed"] + 1)
    start = (sizes.zipf(spec["partition_zipf_a"], len(k)) - 1) % n_parts
    rng = np.random.default_rng(seed)
    unit = np.repeat(np.arange(len(k), dtype=np.int64), k)
    j = np.arange(n_rows, dtype=np.int64) - np.repeat(np.cumsum(k) - k, k)
    pk = rng.permutation(n_parts)[(start[unit] + j) % n_parts]
    del j
    vals = values(spec, rng, n_rows)
    order = rng.permutation(n_rows)
    return unit[order], pk[order], vals[order]
