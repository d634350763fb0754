"""``chip_smoke.py``'s phases, rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU (``test_faults.py``
checks that). Its phases are plain functions, so here they run through
``chip_smoke.run`` on the 8 virtual CPU devices, Pallas in interpret
mode: the same entry points, data generators and exact-answer checks
the chip run uses, at sizes a test can afford.
"""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    flagship_rows=40_000, flagship_users=2_000, flagship_parts=500,
    flagship_l0=64, q_rows=40_000, q_users=2_000, q_parts=200, q_l0=64,
    sweep_rows=5_000, sweep_configs=12, vector_rows=2_000,
    vector_parts=4_096, vector_d=256)


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as mod
    return mod


@pytest.mark.parametrize("phases", [
    ("flagship",), ("quantiles", "streamed", "pallas"), ("sweep",),
    ("serve",),
])
def test_phases_pass_on_cpu(chip_smoke, phases):
    fns = [getattr(chip_smoke, f"phase_{p}") for p in phases]
    assert chip_smoke.run(0, chip_smoke.Sizes(**SIZES), phases=fns)


def test_mesh_phases_pass_on_cpu(chip_smoke):
    from pipelinedp_tpu import parallel
    assert chip_smoke.run(1, chip_smoke.Sizes(**SIZES),
                          mesh=parallel.make_mesh(4))


def test_a_fault_fails_the_run(chip_smoke, monkeypatch):
    """A release that is off by one leaf fails its phase: the checks
    are not vacuous."""
    released = chip_smoke.released

    def shifted(result, fields):
        keys, cols = released(result, fields)
        cols["percentile_90"] = cols["percentile_90"] + 2 * chip_smoke.LEAF
        return keys, cols

    monkeypatch.setattr(chip_smoke, "released", shifted)
    assert not chip_smoke.run(0, chip_smoke.Sizes(**SIZES),
                              phases=[chip_smoke.phase_quantiles])



def test_bounded_dataset_respects_the_bounds(chip_smoke):
    """Bounding drops nothing: every id within its partition cap, one
    row per (id, partition), exactly the requested row count."""
    import numpy as np
    ds = chip_smoke.bounded_dataset(3, 30_000, 1_000, 300, 40)
    assert len(ds) == 30_000
    pid, pk = ds.privacy_ids, ds.partition_keys
    assert np.bincount(pid).max() <= 40
    pairs = pid * 300 + pk
    assert len(np.unique(pairs)) == len(pairs)
