"""The knob registry: every execution knob, in ONE place.

The stack grew a forest of hand-set execution knobs — HBM byte caps,
stream batch sizing, cache budgets, the ingest-executor switch — each
living as a module constant or an env var at its point of use. This
module is the single registry over them: one :class:`KnobSpec` per
knob recording its unit, hardcoded default, env-override name, module
seam (the test-injectable constant) and whether it only chooses
between bit-identical execution paths, plus the one resolution
function every consumer goes through.

Resolution precedence (most explicit wins)::

    explicit env override  >  test-seam mutation  >  default

* **env** — the knob's ``PIPELINEDP_TPU_*`` variable is set (any
  value, including the default: setting it is the explicit act).
* **seam** — the module constant (``je._SUBHIST_BYTE_CAP``,
  ``streaming._SELECT_UNITS_CAP``, ...) differs from the registered
  default. Tests and bench inject caps by mutating these (via
  :func:`seam_override`).
* **default** — the hardcoded value.

Direct reads of the registered constants outside this package are
banned (``make noknobs`` + the AST twin in ``tests/test_plan.py``);
consumers call :func:`value` / :func:`resolve_all` instead, and the
module-level names survive purely as test seams.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class KnobSpec:
    """One registered execution knob."""
    name: str
    unit: str                       #: human unit ("bytes", "rows", ...)
    default: Any                    #: today's hardcoded default
    env_var: Optional[str]          #: explicit-override env name
    seam: Optional[Tuple[str, str]]  #: (module, attr) test seam
    #: True when every value selects among bit-identical execution
    #: paths (PARITY rows 3, 11, 33, 35, 36, 39, 41, 43): a pure
    #: performance choice, so a fork behind it may be deleted outright.
    dp_safe: bool
    kind: type                      #: int, bool or str (enumerated)
    doc: str
    choices: Tuple[str, ...] = ()   #: legal values for str knobs

    def parse(self, raw: Any) -> Any:
        if self.kind is bool:
            if isinstance(raw, str):
                return raw.lower() not in ("0", "false", "off")
            return bool(raw)
        if self.kind is str:
            # Enumerated string knobs (kernel_backend): an unknown
            # value — a typo'd env var — resolves to the default
            # rather than crashing a request over a performance choice.
            v = str(raw).strip().lower()
            return v if v in self.choices else self.default
        return int(raw)


_I32_MAX = int(np.iinfo(np.int32).max)

#: The registry. Units and defaults are the documentation of record
#: (mirrored in README "Execution knobs"); ``seam`` names the module
#: constant kept alive as the test seam.
REGISTRY: Tuple[KnobSpec, ...] = (
    KnobSpec(
        "subhist_byte_cap", "bytes", 600 << 20,
        "PIPELINEDP_TPU_SUBHIST_CAP",
        ("pipelinedp_tpu.jax_engine", "_SUBHIST_BYTE_CAP"), True, int,
        "HBM budget for the walk's [P, Q, span] subtree histogram AND "
        "the pass-B sweep planner's tile-packing budget: above it the "
        "walk partition-block-chunks and pass B tiles the (quantile x "
        "partition) grid. Any tiling is bit-identical to the unchunked "
        "walk (node noise is a pure function of the global (partition, "
        "node id))."),
    KnobSpec(
        "stream_chunk_rows", "rows per device batch", 1 << 26,
        "PIPELINEDP_TPU_STREAM_CHUNK", None, False, int,
        "Rows per streamed device batch (and the engine's streaming "
        "trigger). NOT dp-safe: batch membership decides which rows a "
        "privacy unit's bounding subsample sees."),
    KnobSpec(
        "stream_cache_bytes", "bytes", 4 << 30,
        "PIPELINEDP_TPU_STREAM_CACHE", None, True, int,
        "Per-device HBM budget for the pass-B prefix cache (0 "
        "disables). device_cache / hybrid / reship are bit-identical "
        "(PARITY row 3): a trade of HBM for link traffic only."),
    KnobSpec(
        "ingest_executor", "bool", True,
        "PIPELINEDP_TPU_INGEST_EXECUTOR", None, True, bool,
        "Overlapped staging/compute/fold executor for streamed runs; "
        "off = the serial bit-parity reference path (identical "
        "outputs, PARITY row 11)."),
    KnobSpec(
        "q_chunk", "quantiles per pass-B tile (0 = planner search)", 0,
        "PIPELINEDP_TPU_Q_CHUNK",
        ("pipelinedp_tpu.streaming", "_Q_CHUNK"), True, int,
        "Pins the sweep planner's quantiles-per-tile choice; 0 lets "
        "plan_pass_b_sweeps search the (q_chunk, p_blk) grid. Every "
        "tiling is bit-identical (PARITY row 3); an infeasible pin "
        "falls back to the search."),
    KnobSpec(
        "kernel_backend", "xla | pallas", "xla",
        "PIPELINEDP_TPU_KERNEL_BACKEND",
        ("pipelinedp_tpu.ops.kernels.dispatch", "_KERNEL_BACKEND"),
        True, str,
        "Device kernel path for the pass-B multi-tile histogram binner "
        "and the fused lane-packed segment_sum: 'xla' (generic "
        "sort/scatter lowering — the default; cold start is "
        "byte-identical to pre-knob behavior) or 'pallas' (the "
        "hand-tiled VMEM-resident kernels in ops/kernels/, interpret "
        "mode off-TPU). dp-safe: both paths are bit-identical (PARITY "
        "row 33); out-of-envelope shapes or a host without Pallas "
        "fall back to 'xla' with a kernel.fallback event.",
        choices=("xla", "pallas")),
    KnobSpec(
        "segsum_wide_d_block", "coordinates per wide-D tile (0 = auto)",
        0, "PIPELINEDP_TPU_SEGSUM_WIDE_D_BLOCK",
        ("pipelinedp_tpu.ops.kernels.dispatch", "_WIDE_D_BLOCK"),
        True, int,
        "Pins the D-tile width of the wide-D vector segment-sum kernel "
        "(ops/kernels/segsum.segment_sum_wide); 0 lets the envelope "
        "pick the widest in-envelope tile. dp-safe: every tile width "
        "is bit-identical integer arithmetic (PARITY row 39); an "
        "out-of-envelope pin falls back to the envelope's choice."),
    KnobSpec(
        "sweep_config_batch", "configs per compiled sweep chunk "
        "(0 = widest in-HBM-budget)", 0,
        "PIPELINEDP_TPU_SWEEP_CONFIG_BATCH",
        ("pipelinedp_tpu.analysis.jax_sweep", "_SWEEP_CONFIG_BATCH"),
        True, int,
        "Pins the configuration-axis batch width of the utility-analysis "
        "megasweep (analysis/jax_sweep.py): every sweep chunk dispatches "
        "this many configs through ONE warm compiled program whose "
        "bounds / eps-splits / selection tables / noise kinds are "
        "runtime inputs. 0 lets the driver pick the widest chunk inside "
        "the HBM row-broadcast and selection-window budgets. dp-safe: "
        "every batch width is bit-identical per config (PARITY row 41 — "
        "padding-invariant, walked == batched). Note the sweep "
        "checkpoint fingerprint covers the width: a "
        "resume must run the same batch width it was killed at."),
    KnobSpec(
        "vector_accumulator", "f32 | fx", "f32",
        "PIPELINEDP_TPU_VECTOR_ACCUMULATOR",
        ("pipelinedp_tpu.jax_engine", "_VECTOR_ACCUMULATOR"),
        False, str,
        "VECTOR_SUM per-coordinate accumulator: 'f32' (plain float32 "
        "segment_sum — the historical default, drift hazard past ~2^24 "
        "contributions per coordinate) or 'fx' (24-bit fixed-point "
        "coordinate lanes quantized against the norm clip bound, int32 "
        "lane sums, float64 host reassembly — exact, backend- and "
        "mesh-bit-identical, the wide-D Pallas kernel's operand). NOT "
        "dp-safe: the two accumulators release different floats (fx "
        "quantizes at the clip bound).",
        choices=("f32", "fx")),
    KnobSpec(
        "serve_fusion", "bool", False,
        "PIPELINEDP_TPU_SERVE_FUSION", None, True, bool,
        "Shape-bucketed request fusion in the resident service "
        "(serve/fusion.py): admitted compatible requests batch through "
        "ONE warm compiled program per pow2 shape bucket. dp-safe: "
        "fusion on/off is bit-identical per request (PARITY row 35) — "
        "per-request noise keys, row validity masks and "
        "padding-invariant tie-breaks keep every request's stream its "
        "own. Default off; the serve knobs carry no module seam so "
        "resolving them never imports serve/ into batch mode "
        "(Service constructor args are the injection point)."),
    KnobSpec(
        "serve_fuse_window_ms", "milliseconds", 8,
        "PIPELINEDP_TPU_SERVE_FUSE_WINDOW_MS", None, True, int,
        "Bounded wait window of an open fusion bucket: the first "
        "request in a bucket waits at most this long for companions "
        "before the batch flushes. A latency<->throughput trade only "
        "(dp-safe; outputs are window-invariant)."),
    KnobSpec(
        "serve_fuse_batch", "requests per fused batch", 8,
        "PIPELINEDP_TPU_SERVE_FUSE_BATCH", None, True, int,
        "Max requests one fused batch carries; a full bucket flushes "
        "immediately, before its window expires. dp-safe (batch "
        "membership never reaches the per-request noise streams)."),
    KnobSpec(
        "serve_fuse_rows_floor", "rows (pow2 bucket floor)", 8192,
        "PIPELINEDP_TPU_SERVE_FUSE_ROWS_FLOOR", None, True, int,
        "Smallest row-bucket edge: requests bucket at "
        "max(floor, solo row shape) — the 8192-row-tile edges the "
        "solo compile cache already uses, so a fused member's row "
        "plane is exactly its solo size. Raising the floor merges "
        "small-request buckets (fewer compiled shapes, more padded "
        "compute); clamped to >= 8192 (the solo row-padding floor). "
        "dp-safe: released values are padding-invariant."),
    KnobSpec(
        "sketch_width", "hash buckets (row-0 selection axis)", 1 << 16,
        "PIPELINEDP_TPU_SKETCH_WIDTH", None, False, int,
        "Buckets per counting-sketch row in the sketch-first path "
        "(sketch/). NOT dp-safe: the bucket grid decides which keys "
        "become candidates. Explicit SketchParams outrank the "
        "registry. Rounded up to a "
        "multiple of 256 on device (the matmul binner's radix width)."),
    KnobSpec(
        "sketch_depth", "sketch rows (hash remixes)", 2,
        "PIPELINEDP_TPU_SKETCH_DEPTH", None, False, int,
        "Counting-sketch depth: row 0 selects candidate buckets, rows "
        "1+ refine the count-min mass estimate in the run report. NOT "
        "dp-safe (part of the selection mechanism's shape)."),
    KnobSpec(
        "sketch_candidate_cap", "selected buckets (DP top-K cap)", 4096,
        "PIPELINEDP_TPU_SKETCH_CANDIDATE_CAP", None, False, int,
        "Max buckets phase-1 selection keeps (the DP top-K cap over "
        "noisy sketch mass — the cap lives INSIDE the DP mechanism, on "
        "buckets, never on data-derived key lists). NOT dp-safe: it "
        "changes the releasable candidate set."),
    KnobSpec(
        "sketch_backend", "matmul | xla", "matmul",
        "PIPELINEDP_TPU_SKETCH_BACKEND", None, True, str,
        "Device formulation of the sketch binner: 'matmul' (radix "
        "one-hot MXU contraction, sketch/device.py — the default) or "
        "'xla' (the scatter-add reference). dp-safe: both are exact "
        "integer arithmetic and bit-identical (PARITY row 36). Like the "
        "serve knobs, no "
        "module seam — SketchParams.backend is the injection point, so "
        "resolving the registry never imports sketch/ into non-sketch "
        "runs.", choices=("matmul", "xla")),
    KnobSpec(
        "mesh_topology", "flat | hier | auto", "flat",
        "PIPELINEDP_TPU_MESH_TOPOLOGY",
        ("pipelinedp_tpu.parallel.sharded", "_MESH_TOPOLOGY"),
        True, str,
        "Cross-shard exchange layout (parallel/sharded.py): 'flat' "
        "(one exchange over the whole device axis — the historical "
        "default; cold start is byte-identical to pre-knob behavior), "
        "'hier' (two-stage reduction: owner-block psum_scatter over "
        "each host's ici group, then one batch-boundary block "
        "exchange over the dcn groups — scatter traffic stays on ICI, "
        "only 1/per_host of the payload crosses DCN) or 'auto' (hier "
        "iff the mesh spans more than one host; CPU proxy: processes "
        "are hosts, PIPELINEDP_TPU_MESH_HOSTS simulates hosts in one "
        "process). dp-safe: both stages run fixed reduction trees over "
        "exact-integer payloads, so hier and flat release bit-identical "
        "values and kept sets (PARITY row 43); ragged host groups fall "
        "back to flat with a mesh.topology_fallback event.",
        choices=("flat", "hier", "auto")),
    KnobSpec(
        "select_units_cap", "privacy units per partition", _I32_MAX,
        None, ("pipelinedp_tpu.streaming", "_SELECT_UNITS_CAP"),
        False, int,
        "int32 guard cap: privacy units per partition at streamed "
        "selection time. A refusal threshold, not a performance "
        "choice; the seam exists so boundary tests "
        "can pin the exact cliff."),
    KnobSpec(
        "tree_rows_cap", "kept rows per partition", _I32_MAX,
        None, ("pipelinedp_tpu.streaming", "_TREE_ROWS_CAP"),
        False, int,
        "int32 guard cap: kept rows per partition in the streamed "
        "percentile tree histograms. A refusal threshold; seam for "
        "boundary tests."),
)

BY_NAME: Dict[str, KnobSpec] = {spec.name: spec for spec in REGISTRY}


def _seam_value(spec: KnobSpec) -> Any:
    mod = importlib.import_module(spec.seam[0])
    return getattr(mod, spec.seam[1])


def resolve_value(spec: KnobSpec) -> Tuple[Any, str]:
    """(value, source) for one knob under the registry precedence;
    source is one of ``env`` / ``seam`` / ``default``."""
    if spec.env_var is not None:
        raw = os.environ.get(spec.env_var)
        if raw is not None and raw != "":
            return spec.parse(raw), "env"
    if spec.seam is not None:
        current = _seam_value(spec)
        if current != spec.default:
            return current, "seam"
    return spec.default, "default"


def value(name: str) -> Any:
    """The resolved value of one knob (see :func:`resolve_value`)."""
    return resolve_value(BY_NAME[name])[0]


def defaults() -> Dict[str, Any]:
    """{name: hardcoded default} — the cold-start resolution vector."""
    return {spec.name: spec.default for spec in REGISTRY}


def resolve_all() -> Dict[str, Tuple[Any, str]]:
    """{name: (value, source)} for every registered knob."""
    return {spec.name: resolve_value(spec) for spec in REGISTRY}


@contextlib.contextmanager
def seam_override(name: str, value: Any):
    """Temporarily set a knob's module seam (the blessed injection
    idiom for tests and bench probe records)."""
    spec = BY_NAME[name]
    if spec.seam is None:
        raise ValueError(f"knob {name!r} has no module seam")
    mod = importlib.import_module(spec.seam[0])
    saved = getattr(mod, spec.seam[1])
    setattr(mod, spec.seam[1], spec.parse(value))
    try:
        yield
    finally:
        setattr(mod, spec.seam[1], saved)
