"""Device cost observatory tests (``pipelinedp_tpu/obs/costs``,
``make costcheck``).

Coverage contract:

* roofline math — verdicts flip at the device ridge point exactly;
  unknown device kinds / missing analyses stay ``unknown`` (never a
  made-up ceiling);
* ``instrumented_jit`` — off it dispatches through plain ``jax.jit``
  and records nothing; on it captures exactly ONE compile per
  (function, abstract-shape signature) — the wrapped Python body
  traces once across repeat calls (the compile-count assertion: cost
  capture never pays a second XLA compile) — with flops/bytes,
  memory stats, compile wall time and a persistent-cache verdict in
  the cost table, a ``compile.program`` span under tracing, and new
  signatures creating new entries;
* analysis tolerance — every known shape of ``cost_analysis()`` /
  ``memory_analysis()`` across jax versions (dict, one-element list,
  None, raise, missing fields) degrades to a ``cost.unavailable``
  event, never an error;
* HBM watermark sampling — gated by ``PIPELINEDP_TPU_COSTS``, fills
  the ``hbm.live_bytes`` gauge / ``hbm.watermark`` running max / the
  ledger series behind the Chrome-trace counter track;
* store schema tolerance v1→v2→v3 — a synthetic mixed-schema ledger
  round-trips through ``last_known_good``, ``--summarize`` (text,
  ``--json`` and ``--csv``) and ``bench.py --compare`` without error;
* Chrome-trace counter tracks — sampled series export as ``ph: "C"``
  events; cumulative progress counters differentiate into rows/s;
* the e2e acceptance shape — a traced streamed run on the CPU backend
  lands a ``device_costs`` section with >= 1 program carrying flops,
  compile wall time and cache verdict, plus a roofline verdict per
  recorded phase (``unknown`` only where witnessed by a
  ``cost.unavailable`` event);
* lint twin — AST-precise ban on ``cost_analysis(`` /
  ``memory_analysis(`` / ``live_arrays(`` calls outside
  ``pipelinedp_tpu/obs/`` (``make nocost`` runs the grep twin).

The DP-output bit-parity of costs on vs off (PARITY row 31) lives in
``tests/test_obs.py::TestParity``, extending the trace/audit pattern.
"""

import ast
import csv
import io
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import obs
from pipelinedp_tpu.backends import JaxBackend
from pipelinedp_tpu.obs import costs
from pipelinedp_tpu.obs import report as obs_report
from pipelinedp_tpu.obs import store as obs_store
from pipelinedp_tpu.obs.costs import instrumented_jit
from pipelinedp_tpu.obs.tracer import RunLedger
from pipelinedp_tpu.resilience.clock import FakeClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENV_A = {"jax_version": "0.4", "platform": "cpu", "device_kind": "cpu",
         "device_count": 1, "process_count": 1, "git_sha": "aaa"}


@pytest.fixture(autouse=True)
def fresh_state(monkeypatch, tmp_path):
    """Every test starts with capture OFF, a fresh ledger/cost table,
    and an isolated store dir."""
    monkeypatch.delenv(costs.ENV_VAR, raising=False)
    monkeypatch.delenv(obs.ENV_VAR, raising=False)
    monkeypatch.setenv(obs_store.ENV_VAR, str(tmp_path / "ledger"))
    monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "997")
    obs.reset()
    yield
    obs.reset()


class TestRoofline:
    """The static peak table and the verdict math."""

    def test_device_peaks_matching(self):
        assert costs.device_peaks("TPU v5 lite")["kind"] == "tpu_v5e"
        assert costs.device_peaks("TPU v4")["kind"] == "tpu_v4"
        cpu = costs.device_peaks("cpu")
        assert cpu["kind"] == "cpu_proxy" and cpu["proxy"] is True
        # A TPU kind the table lacks is an error, not "unknown".
        with pytest.raises(ValueError, match="TPU v9000"):
            costs.device_peaks("TPU v9000")
        assert costs.device_peaks(None) is None

    def test_verdict_flips_exactly_at_the_ridge(self):
        peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
        # ridge = 10 flop/byte: at it -> compute, just under -> bandwidth
        at = costs.roofline_verdict(1000.0, 100.0, peaks)
        assert at["verdict"] == "compute_bound"
        assert at["intensity"] == 10.0 and at["ridge"] == 10.0
        under = costs.roofline_verdict(999.0, 100.0, peaks)
        assert under["verdict"] == "bandwidth_bound"

    def test_unknown_when_analysis_or_peaks_missing(self):
        peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
        assert costs.roofline_verdict(None, 8.0, peaks)[
            "verdict"] == "unknown"
        assert costs.roofline_verdict(8.0, None, peaks)[
            "verdict"] == "unknown"
        assert costs.roofline_verdict(8.0, 0.0, peaks)[
            "verdict"] == "unknown"
        no_peaks = costs.roofline_verdict(8.0, 2.0, None)
        assert no_peaks["verdict"] == "unknown"
        assert no_peaks["ridge"] is None
        # Intensity is a property of the PROGRAM: it must survive a
        # missing peak row (only the verdict needs the ridge).
        assert no_peaks["intensity"] == 4.0

    def test_wide_d_matmul_classifies_compute_bound_on_v5e(self):
        """ISSUE-17 satellite: the wide-D segment-sum program's shape
        on the v5e row. HBM traffic is one pass over the [N, D] lanes
        + pk plus the [P, D] result (the [P, Dt] accumulator slab is
        VMEM-resident across row blocks — the kernel's whole point),
        while the one-hot contraction does 2*N*P*D flops: intensity
        ~P/2 flop/byte, so at P >= ~512 partitions the program clears
        the v5e ridge (~240) and classifies compute_bound — the one
        workload in the repo that saturates the MXU instead of the
        memory system."""
        peaks = costs.device_peaks("TPU v5e")
        assert peaks is not None and peaks["kind"] == "tpu_v5e"
        N, P, D = 200_000, 1024, 256
        flops = 2.0 * N * P * D
        bytes_accessed = 4.0 * (N * D + N + P * D)
        got = costs.roofline_verdict(flops, bytes_accessed, peaks)
        assert got["intensity"] > got["ridge"] > 100
        assert got["verdict"] == "compute_bound"
        # The scalar-lane shape (C ~ a handful of metric columns)
        # stays bandwidth_bound on the same row — wide D is what
        # changes the regime, exactly the ISSUE's motivation.
        C = 4
        scalar = costs.roofline_verdict(
            2.0 * N * 64 * C, 4.0 * (N * C + N + 64 * C), peaks)
        assert scalar["verdict"] == "bandwidth_bound"


class FakeCompiled:
    """Stand-in for a jax Compiled with configurable analyses."""

    def __init__(self, cost=None, memory=None, cost_raises=False,
                 memory_raises=False):
        self._cost, self._memory = cost, memory
        self._cr, self._mr = cost_raises, memory_raises

    def cost_analysis(self):
        if self._cr:
            raise NotImplementedError("no analysis on this backend")
        return self._cost

    def memory_analysis(self):
        if self._mr:
            raise NotImplementedError("no analysis on this backend")
        return self._memory


class FakeMemStats:
    argument_size_in_bytes = 100
    output_size_in_bytes = 40
    temp_size_in_bytes = 60
    alias_size_in_bytes = 20
    generated_code_size_in_bytes = 8


class TestAnalysisTolerance:
    """Every known backend shape degrades gracefully, never raises."""

    def test_cost_analysis_shapes(self):
        d = {"flops": 7.0, "bytes accessed": 3.0}
        got, err = costs._cost_analysis(FakeCompiled(cost=d))
        assert err is None and got == {"flops": 7.0,
                                       "bytes_accessed": 3.0}
        # Older jax wraps the dict in a one-element list.
        got, err = costs._cost_analysis(FakeCompiled(cost=[d]))
        assert err is None and got["flops"] == 7.0
        got, err = costs._cost_analysis(FakeCompiled(cost=None))
        assert got is None and "cost_analysis" in err
        got, err = costs._cost_analysis(FakeCompiled(cost={}))
        assert got is None and "no fields" in err
        got, err = costs._cost_analysis(FakeCompiled(cost_raises=True))
        assert got is None and "NotImplementedError" in err

    def test_memory_analysis_shapes(self):
        got, err = costs._memory_analysis(
            FakeCompiled(memory=FakeMemStats()))
        assert err is None
        # peak = args + outputs + temps + code - aliased
        assert got["peak_bytes"] == 100 + 40 + 60 + 8 - 20
        got, err = costs._memory_analysis(FakeCompiled(memory=None))
        assert got is None and "memory_analysis" in err
        got, err = costs._memory_analysis(
            FakeCompiled(memory_raises=True))
        assert got is None and "NotImplementedError" in err


class TestInstrumentedJit:
    """The seam itself: off = jax.jit; on = capture-once dispatch."""

    def test_off_records_nothing(self):
        traces = {"n": 0}

        @instrumented_jit(phase="t", static_argnames=("k",))
        def f(x, k):
            traces["n"] += 1
            return x * k

        assert float(f(jnp.float32(3.0), k=2)) == 6.0
        assert float(f(jnp.float32(4.0), k=2)) == 8.0
        assert costs.TABLE.snapshot()["programs"] == {}
        assert traces["n"] == 1  # plain jit cache still deduplicates

    def test_on_captures_once_per_signature(self, monkeypatch):
        """THE compile-count assertion: with capture on, two calls at
        the same signature trace (= compile) the wrapped body exactly
        once — dispatch goes through the captured executable, never a
        second XLA compile."""
        monkeypatch.setenv(costs.ENV_VAR, "1")
        monkeypatch.setenv(obs.ENV_VAR, "1")
        traces = {"n": 0}

        @instrumented_jit(phase="walk", static_argnames=("k",))
        def g(x, k):
            traces["n"] += 1
            return x * jnp.float32(k)

        r1 = g(jnp.arange(8, dtype=jnp.float32), k=3)
        r2 = g(jnp.arange(8, dtype=jnp.float32), k=3)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        assert traces["n"] == 1, "same signature recompiled"
        snap = costs.TABLE.snapshot()
        assert len(snap["programs"]) == 1
        (entry,) = snap["programs"].values()
        assert entry["program"] == "g" and entry["phase"] == "walk"
        assert entry["compile_s"] > 0.0
        assert entry["compile_cache"] in ("hit", "miss", "disabled",
                                          "unknown")
        assert entry["calls"] == 2
        # CPU exposes both analyses: flops/bytes and a verdict land.
        assert entry["flops"] is not None
        assert entry["bytes_accessed"] is not None
        assert entry["verdict"] in ("compute_bound", "bandwidth_bound")
        assert entry["memory"]["peak_bytes"] >= 0
        led = obs.ledger().snapshot()
        compile_spans = [s for s in led["spans"]
                         if s.name == "compile.program"]
        assert len(compile_spans) == 1
        assert led["counters"]["cost.programs_captured"] == 1
        # A NEW static value is a new program: second capture.
        g(jnp.arange(8, dtype=jnp.float32), k=4)
        assert traces["n"] == 2
        assert len(costs.TABLE.snapshot()["programs"]) == 2

    def test_phase_aggregates_roll_up(self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")

        @instrumented_jit(phase="pass_a")
        def h1(x):
            return x + 1

        @instrumented_jit(phase="pass_a")
        def h2(x):
            return x * 2

        h1(jnp.arange(4.0))
        h2(jnp.arange(4.0))
        snap = costs.TABLE.snapshot()
        ph = snap["phases"]["pass_a"]
        assert ph["programs"] == 2 and ph["calls"] == 2
        assert ph["verdict"] in ("compute_bound", "bandwidth_bound")
        assert snap["peaks"]["kind"] == "cpu_proxy"

    def test_unavailable_backend_records_event_not_error(
            self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")
        monkeypatch.setattr(
            costs, "_cost_analysis",
            lambda c: (None, "cost_analysis: NotImplementedError"))
        monkeypatch.setattr(
            costs, "_memory_analysis",
            lambda c: (None, "memory_analysis: NotImplementedError"))

        @instrumented_jit(phase="t")
        def f(x):
            return x - 1

        out = f(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.arange(4.0) - 1)
        (entry,) = costs.TABLE.snapshot()["programs"].values()
        assert entry["verdict"] == "unknown"
        assert entry["flops"] is None and entry["memory"] is None
        assert entry["unavailable"] and len(entry["unavailable"]) == 2
        led = obs.ledger().snapshot()
        assert led["counters"]["cost.unavailable"] == 1
        ev = next(e for e in led["events"]
                  if e["name"] == "cost.unavailable")
        assert ev["program"] == "f"

    def test_exotic_signature_falls_back_to_plain_jit(
            self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")

        @instrumented_jit(phase="t")
        def f(*xs):
            return sum(xs)

        assert float(f(jnp.float32(1.0), jnp.float32(2.0))) == 3.0
        assert costs.TABLE.snapshot()["programs"] == {}

    def test_dispatch_fallback_on_executable_rejection(
            self, monkeypatch):
        """The signature key sees abstract shapes, not sharding or
        placement — when the AOT executable rejects a call jax.jit
        would have accepted, dispatch falls back to the traced path
        (capture must never take an aggregation down) and records a
        ``cost.dispatch_fallback`` event."""
        monkeypatch.setenv(costs.ENV_VAR, "1")

        @instrumented_jit(phase="t")
        def f(x):
            return x + 2

        x = jnp.arange(4.0)
        expected = np.arange(4.0) + 2
        np.testing.assert_array_equal(np.asarray(f(x)), expected)
        ((key, (_, table_key)),) = f._compiled.items()

        def rejecting_executable(*a, **k):
            raise ValueError("sharding mismatch")

        f._compiled[key] = (rejecting_executable, table_key)
        np.testing.assert_array_equal(np.asarray(f(x)), expected)
        led = obs.ledger().snapshot()
        assert led["counters"]["cost.dispatch_fallbacks"] == 1
        ev = next(e for e in led["events"]
                  if e["name"] == "cost.dispatch_fallback")
        assert ev["program"] == "f" and "ValueError" in ev["error"]

    def test_jit_attributes_pass_through(self):
        @instrumented_jit(phase="t")
        def f(x):
            return x + 1

        lowered = f.lower(jnp.arange(4.0))
        assert lowered is not None
        assert f.__name__ == "f"


class TestHbmSampling:
    """The monitor-beat hook: live-array bytes -> gauges + series."""

    def test_off_is_noop(self):
        assert costs.sample_live_bytes() is None
        assert costs.hbm_snapshot() is None

    def test_on_fills_gauges_watermark_and_series(self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")
        keep = jnp.arange(1024, dtype=jnp.float32)  # noqa: F841
        n = costs.sample_live_bytes()
        assert n is not None and n >= 1024 * 4
        snap = costs.hbm_snapshot()
        assert snap["live_bytes"] == n
        assert snap["watermark"] >= n
        led = obs.ledger().snapshot()
        assert led["counters"]["hbm.live_bytes"] == n
        assert led["counters"]["hbm.watermark"] >= n
        # The time series feeds only the Chrome-trace counter track:
        # it accumulates under tracing, not on the bare heartbeat.
        assert "hbm.live_bytes" not in led["series"]
        monkeypatch.setenv(obs.ENV_VAR, "1")
        costs.sample_live_bytes()
        led = obs.ledger().snapshot()
        assert led["series"]["hbm.live_bytes"], "no series sample"
        # The watermark never comes back down when live bytes do.
        del keep
        costs.sample_live_bytes()
        snap2 = costs.hbm_snapshot()
        assert snap2["watermark"] >= snap["watermark"] or (
            snap2["watermark"] >= snap2["live_bytes"])

    def test_reset_clears_table_and_watermark(self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")
        costs.sample_live_bytes()
        assert costs.hbm_snapshot() is not None
        obs.reset()
        assert costs.hbm_snapshot() is None
        assert costs.TABLE.snapshot()["programs"] == {}


def _mixed_schema_store(tmp_path, fp_env=ENV_A):
    """A synthetic ledger holding one v1, one v2 and one v3 entry for
    the same fingerprint — the store file a long-lived install accretes
    across upgrades."""
    s = obs_store.LedgerStore(str(tmp_path / "mixed"))
    fp = obs_store.fingerprint_key(fp_env)
    v1 = {"schema_version": 1, "name": "run_report", "fingerprint": fp,
          "payload": {"run_report": {
              "schema_version": 1,
              "spans": {"pass_a": {"count": 1, "total_s": 1.0}}}}}
    v2 = {"schema_version": 2, "name": "run_report", "fingerprint": fp,
          "payload": {"run_report": {
              "schema_version": 2,
              "privacy": {"enabled": False},
              "spans": {"pass_a": {"count": 1, "total_s": 0.9}}}}}
    with open(s.path, "w", encoding="utf-8") as f:
        f.write(json.dumps(v1) + "\n")
        f.write(json.dumps(v2) + "\n")
    # The v3 entry goes through the real writer.
    s.append("run_report", {"run_report": {
        "schema_version": 3,
        "spans": {"pass_a": {"count": 1, "total_s": 0.8}},
        "device_costs": {
            "platform": "cpu", "device_kind": "cpu",
            "peaks": {"kind": "cpu_proxy", "flops_per_s": 1e11,
                      "hbm_bytes_per_s": 5e10, "proxy": True},
            "programs": {"_partials_kernel#0001": {
                "program": "_partials_kernel", "phase": "pass_a",
                "compile_s": 0.25, "compile_cache": "miss",
                "flops": 1e6, "bytes_accessed": 1e7,
                "intensity": 0.1, "verdict": "bandwidth_bound",
                "memory": {"peak_bytes": 4096}, "calls": 3}},
            "phases": {"pass_a": {"programs": 1, "calls": 3,
                                  "compile_s": 0.25, "flops": 1e6,
                                  "bytes_accessed": 1e7, "analyzed": 1,
                                  "verdict": "bandwidth_bound",
                                  "intensity": 0.1, "ridge": 2.0}}}}},
        env=fp_env)
    return s, fp


class TestSchemaToleranceV1V2V3:
    """Satellite: a mixed-schema ledger round-trips through every
    reader — ``last_known_good``, ``--summarize`` (all three output
    modes) and ``bench.py --compare`` — without error."""

    def test_entries_and_last_known_good(self, tmp_path):
        s, fp = _mixed_schema_store(tmp_path)
        entries = s.entries()
        assert [e["schema_version"] for e in entries] == [1, 2, 6]
        lkg = s.last_known_good("run_report", fp)
        assert lkg["schema_version"] == 6

    def test_summarize_mixes_all_schemas(self, tmp_path):
        s, fp = _mixed_schema_store(tmp_path)
        summary = obs_store.summarize_entries(s.entries())
        agg = summary[fp]
        assert agg["runs"] == 3
        # All three reports' pass_a spans feed the phase table...
        assert agg["phases"]["pass_a"]["reports"] == 3
        # ...but only the v3 entry contributes cost/roofline columns.
        prog = agg["programs"]["_partials_kernel"]
        assert prog["samples"] == 1
        assert prog["compile_s_latest"] == 0.25
        assert prog["compile_cache"] == "miss"
        assert prog["verdict"] == "bandwidth_bound"
        assert prog["hbm_peak_bytes"] == 4096

    def test_summarize_cli_text_json_csv(self, tmp_path, capsys,
                                         monkeypatch):
        s, fp = _mixed_schema_store(tmp_path)
        base = ["--summarize", "--dir", os.path.dirname(s.path)]
        assert obs_store.main(base) == 0
        text = capsys.readouterr().out
        assert "_partials_kernel" in text
        assert "bandwidth_bound" in text
        assert obs_store.main(base + ["--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["fingerprints"][fp]["programs"][
            "_partials_kernel"]["flops"] == 1e6
        assert obs_store.main(base + ["--csv"]) == 0
        rows = list(csv.DictReader(io.StringIO(
            capsys.readouterr().out)))
        kinds = {r["kind"] for r in rows}
        assert kinds == {"phase", "program"}
        prog_row = next(r for r in rows if r["kind"] == "program")
        assert prog_row["name"] == "_partials_kernel"
        assert prog_row["verdict"] == "bandwidth_bound"
        assert float(prog_row["flops"]) == 1e6

    def test_program_rows_key_per_signature(self):
        """Two shape signatures of one kernel aggregate as separate
        rows (distinct XLA programs must not share a compile-trend
        series); signature-less entries keep the bare name."""
        p1 = {"program": "k", "signature": "P=16, f32[16]"}
        p2 = {"program": "k", "signature": "P=32, f32[32]"}
        k1, k2 = (obs_store._program_row_key(p1),
                  obs_store._program_row_key(p2))
        assert k1 != k2
        assert k1.startswith("k@") and k2.startswith("k@")
        assert obs_store._program_row_key(p1) == k1  # stable
        assert obs_store._program_row_key({"program": "k"}) == "k"

    def test_json_and_csv_are_mutually_exclusive(self, tmp_path):
        with pytest.raises(SystemExit):
            obs_store.main(["--summarize", "--dir", str(tmp_path),
                            "--json", "--csv"])

    def test_bench_compare_tolerates_mixed_schemas(self, monkeypatch,
                                                   tmp_path):
        """``bench.py --compare`` against a store whose baseline
        entries span v1..v3: no error, and the span comparison still
        works off whichever schemas carry spans."""
        monkeypatch.setenv(obs_store.ENV_VAR, str(tmp_path / "mixed"))
        monkeypatch.syspath_prepend(REPO)
        import bench
        bench.reset_run_state()
        _mixed_schema_store(tmp_path, fp_env=bench.env_fingerprint())
        bench.reset_run_state()  # re-reads baselines incl. the mix
        report = {"schema_version": 3,
                  "spans": {"pass_a": {"count": 1, "total_s": 0.7}}}
        reg = bench.compare_to_baseline(records=[], run_report=report)
        span = next((p for p in reg["spans"]
                     if p["span"] == "pass_a"), None)
        assert span is not None and span["baseline_total_s"] == 0.8
        assert reg["regressed"] == []


class TestChromeCounterTracks:
    """Satellite: sampled series export as ``ph: "C"`` counter events —
    rows/s differentiated from the cumulative progress counter, raw
    values for live-HBM bytes."""

    def test_counter_track_export(self):
        clock = FakeClock(100.0)
        led = RunLedger(clock=clock)
        led.sample("hbm.live_bytes", 1000.0)
        clock.sleep(1.0)
        led.sample("hbm.live_bytes", 3000.0)
        events = obs_report.chrome_trace_events(led.snapshot())
        cs = [e for e in events if e["ph"] == "C"]
        assert [e["args"]["value"] for e in cs] == [1000.0, 3000.0]
        assert cs[0]["name"] == "hbm.live_bytes"
        assert cs[1]["ts"] - cs[0]["ts"] == pytest.approx(1e6)

    def test_progress_counter_differentiates_to_rate(self):
        clock = FakeClock(10.0)
        led = RunLedger(clock=clock)
        # Cumulative rows-staged samples: 0 -> 997 over 1s -> 997 rows/s
        led.sample("progress.rows_staged", 0.0)
        clock.sleep(1.0)
        led.sample("progress.rows_staged", 997.0)
        clock.sleep(2.0)
        led.sample("progress.rows_staged", 997.0 + 4000.0)
        events = obs_report.chrome_trace_events(led.snapshot())
        cs = [e for e in events if e["ph"] == "C"]
        assert all(e["name"] == "rows/s" for e in cs)
        assert [e["args"]["value"] for e in cs] == [997.0, 2000.0]

    def test_traced_inc_feeds_the_series(self, monkeypatch):
        monkeypatch.setenv(obs.ENV_VAR, "1")
        obs.inc("progress.rows_staged", 997)
        obs.inc("progress.rows_staged", 997)
        snap = obs.ledger().snapshot()
        assert [v for _, v in snap["series"][
            "progress.rows_staged"]] == [997.0, 1994.0]


def run_streamed(seed=31, chunk_env="PIPELINEDP_TPU_STREAM_CHUNK"):
    rng = np.random.default_rng(seed)
    n, users, parts = 9_000, 2_000, 12
    ds = pdp.ArrayDataset(privacy_ids=rng.integers(0, users, n),
                          partition_keys=rng.integers(0, parts, n),
                          values=rng.uniform(0.0, 10.0, n))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PERCENTILE(50)],
        max_partitions_contributed=parts,
        max_contributions_per_partition=50,
        min_value=0.0, max_value=10.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1e12,
                                    total_delta=1e-2)
    engine = pdp.DPEngine(acc, JaxBackend(rng_seed=17))
    res = engine.aggregate(ds, params, pdp.DataExtractors())
    acc.compute_budgets()
    return dict(res)


class TestAcceptanceEndToEnd:
    """The ISSUE acceptance shape on the CPU backend: a traced run with
    ``PIPELINEDP_TPU_COSTS=1`` produces a run report whose
    ``device_costs`` section carries >= 1 program with flops, compile
    wall time and cache verdict, plus a roofline verdict for every
    recorded phase — ``unknown`` only when witnessed by a
    ``cost.unavailable`` event."""

    def test_traced_streamed_run_lands_device_costs(self, monkeypatch):
        monkeypatch.setenv(costs.ENV_VAR, "1")
        monkeypatch.setenv(obs.ENV_VAR, "1")
        # A chunk size unique to this test: the kernels' abstract
        # shapes must be fresh so capture fires even after other tests
        # compiled the default-chunk programs.
        monkeypatch.setenv("PIPELINEDP_TPU_STREAM_CHUNK", "983")
        run_streamed()
        report = obs.build_run_report()
        assert report["schema_version"] == 6
        dc = report["device_costs"]
        assert len(dc["programs"]) >= 1
        assert dc["device_kind"], "device kind not captured"
        for entry in dc["programs"].values():
            assert entry["compile_s"] > 0.0
            assert entry["compile_cache"] in ("hit", "miss",
                                              "disabled", "unknown")
        events = obs.ledger().snapshot()["events"]
        unavailable = {e["program"] for e in events
                      if e["name"] == "cost.unavailable"}
        for key, entry in dc["programs"].items():
            if entry["program"] not in unavailable:
                assert entry["flops"] is not None, key
        for name, ph in dc["phases"].items():
            if ph["verdict"] == "unknown":
                assert ph["analyzed"] == 0
                assert unavailable, (
                    f"phase {name} unknown without a cost.unavailable "
                    "witness")
            else:
                assert ph["verdict"] in ("compute_bound",
                                         "bandwidth_bound")
        # The streamed phases all surfaced.
        assert {"pass_a", "pass_b", "walk", "select"} <= set(
            dc["phases"])


class TestNoDirectAnalysisCalls:
    """AST-precise twin of ``make nocost``: ``cost_analysis(`` /
    ``memory_analysis(`` / ``live_arrays(`` calls are banned outside
    ``pipelinedp_tpu/obs/`` — device-cost capture must flow through the
    observatory so every measurement lands in the versioned report."""

    def test_analysis_calls_only_under_obs(self):
        # Delegates to the shared AST engine; `make nocost` is the
        # same rule.
        from pipelinedp_tpu import lint
        assert lint.check_tree("nocost") == []
