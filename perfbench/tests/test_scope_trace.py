"""The reduction's numbers pinned on the committed chip traces, and the
trace read by the program's own names (``scope_trace``) on the traces
``record_scoped_trace.py`` recorded with the program's spans and scope
tables; the per-layer readers of the program's spans and scopes."""

import json
import os

import pytest

import scope_trace
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: What ``tracing.reduce`` returns on ``trace_<N>chip.xplane.pb``: a
#: change to how it computes these numbers fails here.
PINNED = {
    1: {"window_s": 0.051439118000000006, "busy_s": 0.012867643000000002,
        "collective_s": 0.0,
        "chips": [{"chip": 0, "busy_s": 0.012867643000000002,
                   "collective_s": 0.0}],
        "device_ops": [
            ["jit_fused_aggregate_kernel fusion", 0.002920966],
            ["jit_fused_aggregate_kernel fusion.2", 0.002920943],
            ["jit_fused_aggregate_kernel fusion.1", 0.002920942],
            ["jit_fused_aggregate_kernel fusion.3", 0.002799067],
            ["jit_fused_aggregate_kernel sort", 0.000759235],
            ["jit_fused_aggregate_kernel copy.13", 0.000155906],
            ["jit_scatter select_dynamic-update-slice_fusion",
             5.1491000000000006e-05],
            ["jit__compact_fetch_kernel fusion.2", 2.9427e-05],
            ["jit__compact_fetch_kernel fusion.1", 2.9425e-05],
            ["jit__compact_fetch_kernel fusion", 2.9423000000000003e-05]]},
    4: {"window_s": 0.051396587, "busy_s": 0.00375592225,
        "collective_s": 6.621e-05,
        "chips": [
            {"chip": 0, "busy_s": 0.003760872, "collective_s": 6.7864e-05},
            {"chip": 1, "busy_s": 0.003755145,
             "collective_s": 7.549600000000001e-05},
            {"chip": 2, "busy_s": 0.003755135,
             "collective_s": 5.1617000000000004e-05},
            {"chip": 3, "busy_s": 0.0037525370000000002,
             "collective_s": 6.9863e-05}],
        "device_ops": [
            ["jit__sharded_kernel fusion.2", 0.0008181785000000001],
            ["jit__sharded_kernel fusion.1", 0.0008181760000000001],
            ["jit__sharded_kernel fusion", 0.0008181657500000001],
            ["jit__sharded_kernel fusion.3", 0.000808392],
            ["jit__sharded_kernel sort.19", 0.00017730100000000002],
            ["jit__sharded_kernel copy.25", 4.3895750000000004e-05],
            ["jit__compact_fetch_kernel fusion", 3.380775e-05],
            ["jit__compact_fetch_kernel fusion.1", 3.3806e-05],
            ["jit__compact_fetch_kernel fusion.2", 3.3790750000000005e-05],
            ["jit__compact_fetch_kernel fusion.3", 3.3778e-05]]},
}

#: The fused program of each recorded scoped trace.
ENGINE = {1: "jit_fused_aggregate_kernel", 4: "jit__sharded_kernel"}


def _profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_file(os.path.join(DATA, name))


def _scoped(chips):
    base = f"trace_scoped_{chips}chip"
    with open(os.path.join(DATA, base + ".scopes.json")) as f:
        scopes = json.load(f)
    return _profile(base + ".xplane.pb"), scopes


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_is_pinned(chips):
    r = tracing.reduce(_profile(f"trace_{chips}chip.xplane.pb"))
    for key, value in PINNED[chips].items():
        assert r[key] == value, key


@pytest.mark.parametrize("chips", [1, 4])
def test_scopes_cover_the_engine_program(chips):
    profile, scopes = _scoped(chips)
    got = scope_trace.scope_times(profile, scopes)
    engine = got["programs"][ENGINE[chips]]
    assert engine["s"] > 0
    assert engine["mapped_s"] / engine["s"] >= 0.9
    assert got["coverage"] >= 0.9
    assert {"bound_sort", "bound_permute", "segsum"} <= set(got["scopes"])
    assert ("combine" in got["scopes"]) == (chips > 1)
    # Scope seconds are device seconds of those programs, no more.
    busy = tracing.reduce(profile)["busy_s"]
    assert sum(got["scopes"].values()) <= sum(
        p["s"] for p in got["programs"].values()) + 1e-12
    assert sum(got["scopes"].values()) <= busy * 1.0001


@pytest.mark.parametrize("chips", [1, 4])
def test_gaps_carry_the_program_spans(chips):
    profile, _ = _scoped(chips)
    plain = tracing.reduce(profile)
    named = scope_trace.reduce_named(profile)
    # Only the names change: every other number is reduce's.
    for key in ("window_s", "busy_s", "collective_s", "chips",
                "device_ops"):
        assert named[key] == plain[key], key
    assert ([t for _, t in named["idle_gaps"]] ==
            [t for _, t in plain["idle_gaps"]])
    assert any(" engine." in n for n, _ in named["idle_gaps"])
    # reduce itself is left as it was.
    assert tracing.host_spans is scope_trace._host_spans
    assert all(" bench." in n for n, _ in plain["idle_gaps"])


@pytest.mark.parametrize("chips", [1, 4])
def test_the_trace_holds_the_program_spans(chips):
    profile, _ = _scoped(chips)
    names = {s[2] for s in tracing.host_spans(profile, "engine.")}
    assert {"engine.encode", "engine.device", "engine.h2d",
            "engine.fetch", "engine.release"} <= names
    assert ("engine.reshard" in names) == (chips > 1)
    assert tracing.host_spans(profile, "pdp.")


def test_scope_of_merges_signatures_only_where_they_agree():
    scopes = {"jit_k(1)": {"a": "segsum", "b": "bound_sort"},
              "jit_k(2)": {"a": "segsum", "b": "bound_caps"},
              "jit_one": {"c": "compact"}}
    assert scope_trace.scope_of(scopes, "jit_k(1)", "b") == ("bound_sort",
                                                            True)
    assert scope_trace.scope_of(scopes, "jit_k", "a") == ("segsum", True)
    assert scope_trace.scope_of(scopes, "jit_k(9)", "b") == (None, True)
    assert scope_trace.scope_of(scopes, "jit_one(5)", "c") == ("compact",
                                                               True)
    assert scope_trace.scope_of(scopes, "jit_other", "c") == (None, False)


def _run(releases, window_s=10.0, trace=None):
    return {"releases": [{"timings": t} for t in releases],
            "window_s": window_s, "trace": trace}


@pytest.mark.parametrize("metric,key", [("reshard_share", "reshard_s"),
                                        ("h2d_share", "h2d_s"),
                                        ("fetch_share", "fetch_s")])
def test_span_shares(metric, key):
    import harness
    read = harness.load_module(os.path.join(
        os.path.dirname(DATA), "..", "metrics", metric + ".py")).read
    assert read(_run([{key: 1.5}, {key: 0.5}])) == 20.0
    # A program without the span (the parent of this reader) reads None.
    assert read(_run([{"device_s": 3.0}])) is None


@pytest.mark.parametrize("scope", ["bound_sort", "bound_permute",
                                   "segsum"])
def test_device_scope_shares(scope, monkeypatch):
    import harness
    from pipelinedp_tpu import obs
    read = harness.load_module(os.path.join(
        os.path.dirname(DATA), "..", "metrics",
        scope + "_device_share.py")).read
    tables = {"jit_fused_aggregate_kernel": {"sort": "bound_sort",
                                             "fusion": "bound_permute",
                                             "fusion.2": "segsum"}}
    monkeypatch.setattr(obs, "program_scopes", lambda: tables)
    trace = {"window_s": 4.0, "device_ops": [
        ["jit_fused_aggregate_kernel fusion", 2.0],
        ["jit_fused_aggregate_kernel sort", 1.0],
        ["jit_fused_aggregate_kernel fusion.2", 0.5],
        ["jit_scatter fusion", 0.25]]}
    want = {"bound_permute": 50.0, "bound_sort": 25.0, "segsum": 12.5}
    assert read(_run([], trace=trace)) == want[scope]
    assert read(_run([], trace=None)) is None
    # A program with no scope tables (the parent of this reader).
    monkeypatch.delattr(obs, "program_scopes")
    assert read(_run([], trace=trace)) is None
