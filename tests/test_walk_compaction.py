"""The quantile walk's bottom compaction, seen from a release.

``_build_sub_hist`` compacts the rows that fall in some chosen subtree
to a static prefix of an eighth of the rows, and falls back to full-row
scatters where more of them do. Continuous values put ~1% of the rows
there; values on a coarse grid (half-star ratings) put whole grades
there, and take the fallback. Each release records which it took: the
``obs`` counter ``walk.compacted`` or ``walk.full_scatter``, and
``timings["walk_full_scatter"]`` and ``timings["walk_relevant_rows"]``.

The releases below are checked against numpy (moments, and each
percentile within one leaf of the order statistics one rank either
side), and digest for digest against the same releases before the
walk's scope and its record existed.
"""

import hashlib
import math

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import obs
from pipelinedp_tpu.backends import JaxBackend

ROWS = 60_000
PARTITIONS = 40
LO, HI = 0.5, 5.0
PERCENTILES = (50, 90, 99)
#: A leaf of the default quantile tree (height 4, branching 16).
LEAF = (HI - LO) / 16**4

# sha256 of repr(rows) of ``_release``, computed before the walk's scope
# and its compaction record were added.
PARENT_DIGESTS = {
    ("half_star", "private"):
        "b423cf88054bdaf8f1bfb7feebba92a45fc85d00d9114aaa327ae4e00de067f0",
    ("half_star", "public"):
        "d96d5b5b84c9883f3ffa4d0dc00a19f7d91f5c570eca20ea41b7a05b216d59be",
    ("continuous", "private"):
        "53004ee864bb9fc82d94b8f305f9fed2416c0a7c888fd69b73cea11f66292396",
    ("continuous", "public"):
        "61bf9d59ab800979c63d7335ecfa5f90f54e7ede491b2e65c8ae439c8aa90ecd",
}


def _columns(values):
    """One row per privacy unit, so bounding drops nothing."""
    rng = np.random.default_rng(31)
    pk = rng.integers(0, PARTITIONS, ROWS)
    if values == "half_star":
        v = LO * rng.integers(1, 11, ROWS).astype(np.float64)
    else:
        v = rng.uniform(LO, HI, ROWS)
    return np.arange(ROWS), pk, v


def _release(values, partitions):
    """COUNT, SUM, MEAN, VARIANCE and PERCENTILE(50, 90, 99) at a huge
    epsilon, seeded: (sorted rows, timings, counter deltas)."""
    pid, pk, v = _columns(values)
    ds = pdp.ArrayDataset(privacy_ids=pid, partition_keys=pk, values=v)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN,
                 pdp.Metrics.VARIANCE] +
        [pdp.Metrics.PERCENTILE(q) for q in PERCENTILES],
        noise_kind=pdp.NoiseKind.LAPLACE, max_partitions_contributed=1,
        max_contributions_per_partition=1, min_value=LO, max_value=HI)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1e9, total_delta=1e-6)
    public = list(range(PARTITIONS)) if partitions == "public" else None
    before = dict(obs.ledger().counters)
    res = pdp.DPEngine(acc, JaxBackend(rng_seed=77)).aggregate(
        ds, params, pdp.DataExtractors(), public_partitions=public)
    acc.compute_budgets()
    rows = sorted(res)
    after = obs.ledger().counters
    delta = {k: after[k] - before.get(k, 0)
             for k in ("walk.compacted", "walk.full_scatter")
             if after.get(k, 0) != before.get(k, 0)}
    return rows, res.timings, delta


CASES = [(v, p) for v in ("half_star", "continuous")
         for p in ("private", "public")]


@pytest.fixture(scope="module", params=CASES, ids="-".join)
def release(request):
    return request.param, _release(*request.param)


def test_release_matches_numpy(release):
    (values, _), (rows, _, _) = release
    _, pk, v = _columns(values)
    assert [k for k, _ in rows] == list(range(PARTITIONS))
    for key, m in rows:
        x = np.sort(v[pk == key])
        n = len(x)
        assert m.count == pytest.approx(n, abs=0.5)
        assert m.sum == pytest.approx(x.sum(), abs=1e-2)
        assert m.mean == pytest.approx(x.mean(), abs=1e-5)
        assert m.variance == pytest.approx(x.var(), abs=1e-4)
        for q in PERCENTILES:
            k = math.ceil(q / 100 * n) - 1
            got = getattr(m, f"percentile_{q}")
            lo, hi = x[max(k - 1, 0)], x[min(k + 1, n - 1)]
            assert lo - LEAF <= got <= hi + LEAF, (key, q)


def test_compaction_outcome_is_recorded(release):
    (values, _), (_, timings, counters) = release
    full = values == "half_star"
    assert timings["walk_full_scatter"] == int(full)
    assert counters == {"walk.full_scatter" if full else "walk.compacted": 1}
    # Past the compaction's prefix (an eighth of the padded rows, at
    # least 8,192) exactly where the full-row scatters ran.
    relevant = timings["walk_relevant_rows"]
    assert 0 < relevant <= ROWS
    assert (relevant > 8_192) == full
    if full:
        # Every row of the grade under each percentile of each partition.
        assert relevant >= 0.2 * ROWS


def test_outputs_match_the_release_before_the_record(release):
    case, (rows, _, _) = release
    digest = hashlib.sha256(repr(rows).encode()).hexdigest()
    assert digest == PARENT_DIGESTS[case]
