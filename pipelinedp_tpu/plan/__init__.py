"""The execution-knob registry.

Every execution choice that used to be a hand-set module constant or
env var — ``_SUBHIST_BYTE_CAP``, pass-B tile packing, stream cache
bytes, ``q_chunk``, the ingest-executor switch, ... — resolves through
one registry, :mod:`~pipelinedp_tpu.plan.knobs`: per knob its unit,
hardcoded default, env override, module seam and dp-safety, plus the
ONE resolution precedence (env > seam > default).
"""

from __future__ import annotations

from pipelinedp_tpu.plan import knobs
from pipelinedp_tpu.plan.knobs import (KnobSpec, REGISTRY, defaults,
                                       resolve_all, seam_override)
from pipelinedp_tpu.plan.knobs import value as knob_value

__all__ = [
    "knobs",
    "KnobSpec", "REGISTRY", "defaults", "resolve_all", "seam_override",
    "knob_value",
]
