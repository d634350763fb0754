"""The trace reduction, on small traces recorded on the chip by
``record_trace.py``: two releases of a tiny configuration inside a
``bench.window`` span."""

import os

import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps():
    merged = tracing._union([(5, 7, "c"), (0, 2, "a"), (1, 3, "b"),
                             (7, 8, "d")])
    assert merged == [[0, 3, "a", "b"], [5, 8, "c", "d"]]


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_recorded_trace(chips):
    from jax.profiler import ProfileData
    path = os.path.join(DATA, f"trace_{chips}chip.xplane.pb")
    r = tracing.reduce(ProfileData.from_file(path))
    assert [c["chip"] for c in r["chips"]] == list(range(chips))
    assert 0 < r["window_s"] < 5
    for c in r["chips"]:
        assert 0 < c["busy_s"] < r["window_s"]
        assert 0 <= c["collective_s"] <= c["busy_s"]
    # The cross-chip combine is there on four chips only.
    assert (r["collective_s"] > 0) == (chips > 1)
    times = [t for _, t in r["device_ops"]]
    assert 0 < len(times) <= tracing.TOP and times == sorted(times,
                                                            reverse=True)
    assert any(n.startswith("jit_fused_aggregate_kernel ") or
               n.startswith("jit__sharded_kernel ") or "fused" in n
               for n, _ in r["device_ops"])
    gaps = r["idle_gaps"]
    assert gaps and all(" bench." in n and t > 0 for n, t in gaps)
    idle = r["window_s"] - r["busy_s"]
    assert sum(t for _, t in gaps) <= idle * chips + 1e-9


def test_trace_without_the_window_span_is_refused():
    class Empty:
        planes = []

    with pytest.raises(ValueError):
        tracing.reduce(Empty())
