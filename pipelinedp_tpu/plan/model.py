"""Ledger-fit cost model: predict device seconds / HBM peak per shape.

The device cost observatory (PR 8) left a feature matrix in the
durable run ledger — per (program, abstract-shape signature) compile
seconds, flops, bytes accessed, HBM peak, keyed by env fingerprint —
and every run report carries per-phase span seconds plus the row/
partition counters that describe the request. This module closes the
measure half of the measure→decide loop: a **stdlib-only** model fit
from those accumulated entries that, per (device kind, phase,
shape-signature bucket), predicts device seconds and HBM peak from
(rows, partitions, quantiles).

The model is deliberately small:

* samples bucket by log2(rows) / log2(partitions) / exact quantile
  count — the same granularity the abstract-shape signatures vary on;
* per bucket, seconds fit a one-feature least-squares line
  ``t = a + b * units`` (units = rows for the streamed passes,
  partitions x quantiles for the walk) — two parameters per cell is
  all the trial counts here can support honestly;
* prediction falls back bucket → phase-wide ratio → the **static
  roofline peak table** (``obs.costs.DEVICE_PEAKS``): with recorded
  bytes-per-row for the phase, seconds >= bytes / peak HBM bandwidth.
  A fingerprint with no history at all predicts None — the planner
  then keeps today's defaults (cold start must be byte-identical).

Degraded entries never contribute samples (a CPU fallback must not
calibrate the device model), and fitting windows by
fingerprint so mixed-environment ledgers cannot cross-pollute.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: Phases whose natural work unit is the request's row count; the walk
#: scales with the (partition x quantile) grid instead.
_ROW_PHASES = ("pass_a", "pass_b", "select", "engine", "fetch", "sweep")


def bucket_key(rows: int, partitions: int, quantiles: int) -> str:
    """Shape-signature bucket: log2-quantized rows/partitions + exact
    quantile count — coarse enough to accumulate samples, fine enough
    that a 2^17-partition walk never calibrates a 2^10 one."""
    lr = max(0, (max(int(rows), 1) - 1).bit_length())
    lp = max(0, (max(int(partitions), 1) - 1).bit_length())
    return f"r{lr}_p{lp}_q{int(quantiles)}"


def phase_units(phase: str, rows: int, partitions: int,
                quantiles: int) -> int:
    if phase.startswith("walk"):
        return max(1, int(partitions) * max(1, int(quantiles)))
    return max(1, int(rows))


def _least_squares(points: List[Tuple[float, float]]
                   ) -> Tuple[float, float]:
    """(a, b) for t = a + b*u; degenerate inputs collapse to the
    through-origin ratio (a=0, b=mean(t/u))."""
    n = len(points)
    su = sum(u for u, _ in points)
    st = sum(t for _, t in points)
    suu = sum(u * u for u, _ in points)
    sut = sum(u * t for u, t in points)
    denom = n * suu - su * su
    if n >= 2 and abs(denom) > 1e-12:
        b = (n * sut - su * st) / denom
        a = (st - b * su) / n
        if b >= 0 and a >= 0:
            return a, b
    # Fallback: ratio estimator (always sane for positive samples).
    return 0.0, sum(t / u for u, t in points) / n if n else 0.0


@dataclasses.dataclass
class _Cell:
    points: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    hbm_peaks: List[int] = dataclasses.field(default_factory=list)


class CostModel:
    """Per-(device kind, phase, bucket) seconds/HBM predictor. Build
    with :func:`fit`; round-trips through :meth:`to_dict` /
    :meth:`from_dict` so a plan file can embed the fitted model."""

    def __init__(self):
        #: {(device_kind, phase, bucket): {"n", "a", "b", "hbm_peak"}}
        self.cells: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
        #: {(device_kind, phase): bytes accessed per work unit} — the
        #: observatory-derived feature behind the roofline fallback.
        self.bytes_per_unit: Dict[Tuple[str, str], float] = {}
        self.samples = 0

    # --- fitting ---

    def _add(self, device_kind: str, phase: str, bucket: str,
             units: float, seconds: float,
             hbm_peak: Optional[int] = None) -> None:
        if seconds <= 0 or units <= 0:
            return
        cell = self._raw.setdefault((device_kind, phase, bucket),
                                    _Cell())
        cell.points.append((float(units), float(seconds)))
        if hbm_peak:
            cell.hbm_peaks.append(int(hbm_peak))
        self.samples += 1

    def _finalize(self) -> None:
        for key, cell in self._raw.items():
            a, b = _least_squares(cell.points)
            self.cells[key] = {
                "n": len(cell.points), "a": a, "b": b,
                "hbm_peak": (max(cell.hbm_peaks) if cell.hbm_peaks
                             else None)}
        del self._raw

    # --- prediction ---

    def predict_seconds(self, device_kind: Optional[str], phase: str,
                        rows: int, partitions: int = 1,
                        quantiles: int = 0) -> Optional[float]:
        """Predicted device seconds for one phase of a request, or
        None when neither history nor the static peak table can say
        anything (the planner then keeps the defaults)."""
        units = phase_units(phase, rows, partitions, quantiles)
        bucket = bucket_key(rows, partitions, quantiles)
        cell = self.cells.get((device_kind, phase, bucket))
        if cell is None:
            # Phase-wide fallback: pool every bucket of the phase into
            # one ratio (a cross-shape extrapolation, but an informed
            # one — same device, same program family).
            pooled = [c for (dk, ph, _), c in self.cells.items()
                      if dk == device_kind and ph == phase]
            if pooled:
                b = (sum(c["b"] * c["n"] for c in pooled) /
                     max(1, sum(c["n"] for c in pooled)))
                if b > 0:
                    return b * units
            return self.roofline_floor(device_kind, phase, units)
        return cell["a"] + cell["b"] * units

    def predict_hbm_peak(self, device_kind: Optional[str], phase: str,
                         rows: int, partitions: int = 1,
                         quantiles: int = 0) -> Optional[int]:
        bucket = bucket_key(rows, partitions, quantiles)
        cell = self.cells.get((device_kind, phase, bucket))
        return cell["hbm_peak"] if cell else None

    def roofline_floor(self, device_kind: Optional[str], phase: str,
                       units: float) -> Optional[float]:
        """The static-peak-table fallback: seconds >= phase bytes over
        the device's peak HBM bandwidth — a lower bound, not a fit,
        used only when the fingerprint has no usable history."""
        per_unit = self.bytes_per_unit.get((device_kind, phase))
        if not per_unit:
            return None
        from pipelinedp_tpu.obs import costs as obs_costs
        peaks = obs_costs.device_peaks(device_kind)
        if peaks is None:
            return None
        return (per_unit * units) / peaks["hbm_bytes_per_s"]

    # --- serialization ---

    def to_dict(self) -> Dict[str, Any]:
        return {
            "samples": self.samples,
            "cells": [{"device_kind": dk, "phase": ph, "bucket": bk,
                       **cell}
                      for (dk, ph, bk), cell in sorted(
                          self.cells.items())],
            "bytes_per_unit": [
                {"device_kind": dk, "phase": ph, "value": v}
                for (dk, ph), v in sorted(self.bytes_per_unit.items())],
        }

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "CostModel":
        m = cls()
        m.samples = int(data.get("samples", 0))
        for row in data.get("cells", ()):
            m.cells[(row["device_kind"], row["phase"],
                     row["bucket"])] = {
                "n": row.get("n", 0), "a": row.get("a", 0.0),
                "b": row.get("b", 0.0),
                "hbm_peak": row.get("hbm_peak")}
        for row in data.get("bytes_per_unit", ()):
            m.bytes_per_unit[(row["device_kind"], row["phase"])] = (
                float(row["value"]))
        return m


def fit(entries: Iterable[Dict[str, Any]],
        fingerprint: Optional[str] = None) -> CostModel:
    """Fit a :class:`CostModel` from accumulated ledger entries (the
    shape ``LedgerStore.entries()`` returns). Uses:

    * ``autotune.trial`` entries — per-phase seconds at a known
      (rows, partitions, quantiles) shape under a known knob vector;
    * ``run_report`` entries — span seconds for the streamed phases
      against the ``ingest.rows_ingested`` counter, plus the
      ``device_costs`` bytes-accessed feature behind the roofline
      fallback.

    Degraded entries are skipped, and with ``fingerprint`` given only
    matching entries contribute — a poisoned (degraded-only or
    foreign-fingerprint) ledger fits an EMPTY model, which predicts
    None and leaves the planner on defaults."""
    model = CostModel()
    model._raw = {}
    bytes_samples: Dict[Tuple[str, str], List[float]] = {}
    for e in entries:
        if not isinstance(e, dict) or e.get("degraded"):
            continue
        if fingerprint is not None and (
                e.get("fingerprint") != fingerprint):
            continue
        payload = e.get("payload") or {}
        if e.get("name") == "autotune.trial":
            t = payload.get("trial") or {}
            shape = t.get("shape") or {}
            rows = int(shape.get("rows", 0))
            parts = int(shape.get("partitions", 1))
            q = int(shape.get("quantiles", 0))
            dk = t.get("device_kind")
            bucket = bucket_key(rows, parts, q)
            for phase, secs in (t.get("phases") or {}).items():
                if isinstance(secs, (int, float)) and secs > 0:
                    model._add(dk, phase, bucket,
                               phase_units(phase, rows, parts, q),
                               float(secs))
            continue
        rr = payload.get("run_report")
        if not isinstance(rr, dict):
            continue
        env = rr.get("env") or payload.get("env") or {}
        dk = env.get("device_kind")
        counters = rr.get("counters") or {}
        rows = int(counters.get("ingest.rows_ingested", 0) or 0)
        spans = rr.get("spans") or {}
        dc = rr.get("device_costs") or {}
        # Per-phase HBM peak from the observatory's program memory
        # stats — the sample behind predict_hbm_peak.
        hbm_by_phase: Dict[str, int] = {}
        for prog in (dc.get("programs") or {}).values():
            pk = (prog.get("memory") or {}).get("peak_bytes")
            if isinstance(pk, (int, float)) and pk > 0:
                ph = prog.get("phase") or "device"
                hbm_by_phase[ph] = max(hbm_by_phase.get(ph, 0),
                                       int(pk))
        # Bucket at the REQUEST's shape when the report carries it
        # (the schema-v4 plan section) — prediction queries the real
        # (rows, partitions, quantiles), so degenerate (rows, 1, 0)
        # buckets from older reports can only serve the pooled
        # fallback, never a direct hit.
        pshape = (rr.get("plan") or {}).get("shape") or {}
        parts = int(pshape.get("partitions", 1) or 1)
        q = int(pshape.get("quantiles", 0) or 0)
        if rows > 0:
            span_to_phase = {"ingest.pass_a": "pass_a",
                             "ingest.pass_b_sweep": "pass_b",
                             "ingest.select": "select"}
            for span_name, phase in span_to_phase.items():
                sp = spans.get(span_name)
                if sp and isinstance(sp.get("total_s"), (int, float)):
                    model._add(dk, phase, bucket_key(rows, parts, q),
                               rows, float(sp["total_s"]),
                               hbm_peak=hbm_by_phase.get(phase))
        for phase, agg in (dc.get("phases") or {}).items():
            ba = agg.get("bytes_accessed")
            if rows > 0 and isinstance(ba, (int, float)) and ba > 0:
                bytes_samples.setdefault((dk, phase), []).append(
                    float(ba) / rows)
    for key, samples in bytes_samples.items():
        model.bytes_per_unit[key] = (sum(samples) / len(samples))
    model._finalize()
    return model


def choose_best_trial(entries: Iterable[Dict[str, Any]],
                      fingerprint: Optional[str] = None
                      ) -> Optional[Dict[str, Any]]:
    """The measured-argmin decision over ``autotune.trial`` entries:
    lowest total seconds per shape bucket wins. Returns
    ``{bucket: {"knobs": ..., "total_s": ..., "shape": ...}}`` — the
    plan file's knob tables — or None when no eligible trial exists."""
    best: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        if not isinstance(e, dict) or e.get("degraded"):
            continue
        if e.get("name") != "autotune.trial":
            continue
        if fingerprint is not None and (
                e.get("fingerprint") != fingerprint):
            continue
        t = (e.get("payload") or {}).get("trial") or {}
        total = t.get("total_s")
        shape = t.get("shape") or {}
        if not isinstance(total, (int, float)) or not t.get("knobs"):
            continue
        bucket = bucket_key(int(shape.get("rows", 0)),
                            int(shape.get("partitions", 1)),
                            int(shape.get("quantiles", 0)))
        cur = best.get(bucket)
        if cur is None or total < cur["total_s"]:
            best[bucket] = {"knobs": dict(t["knobs"]),
                            "total_s": float(total),
                            "shape": dict(shape)}
    return best or None
