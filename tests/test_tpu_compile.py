"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

Interpret mode runs the Pallas kernels everywhere but a TPU, so it
cannot show what the chip's compiler (Mosaic) refuses: block shapes off
the (8, 128) tiling, float iotas, grid semantics of the wrong length,
more VMEM than a kernel may use. These tests compile the three kernels
at their dispatch envelopes' widest shapes with ``interpret=False``,
and the fused aggregation program at 2^20 rows and 2^16 partitions,
for one chip of a described ``v5e:2x2`` topology; and, at 2,048 rows,
checks which columns the bounding sort carries in the chip's program.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every
xdist worker imports this file. The persistent compilation cache is off
around the compiles (an entry compiled for a described chip cannot be
read back without one).
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from pipelinedp_tpu.ops.kernels import dispatch, hist, segsum

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


ROWS = 1 << 16


@pytest.mark.parametrize("P,C", [
    (dispatch._SEGSUM_P_BLOCK, dispatch._SEGSUM_MAX_COLS),  # widest block
    (100, 3),  # unaligned P and C: blocks equal to the array's dims
    (100_000, 9),  # BASELINE config 4's partitions: 13 ragged blocks
])
def test_segment_sum_lanes_compiles(one_chip, no_persistent_cache, P, C):
    env = dispatch.segsum_envelope(P, C)
    assert env is not None
    compiled = _compile(
        lambda c, p: segsum.segment_sum_lanes(c, p, P, *env,
                                              interpret=False),
        _spec(one_chip, (ROWS, C)), _spec(one_chip, (ROWS,)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P,D", [
    (dispatch._SEGSUM_P_BLOCK * 2, 1024),  # full blocks: (128, 128) tiles
    (1024, 1024),  # small P: the widest (512, 512) tiles
])
def test_segment_sum_wide_compiles(one_chip, no_persistent_cache, P, D):
    env = dispatch.segsum_wide_envelope(P, D)
    compiled = _compile(
        lambda c, p: segsum.segment_sum_wide(c, p, P, *env,
                                             interpret=False),
        _spec(one_chip, (ROWS, D)), _spec(one_chip, (ROWS,)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T,Pb,Qc,span", [
    (4, 64, dispatch._HIST_UNROLL_CAP, 256),  # the quantile unroll cap
    (1, 4096, 1, 256),  # the widest one-hot: 128-row blocks
    (1, 131_072, 3, 256),  # config 4's pass B: 103 blocks of 1,280
])
def test_hist_bin_multi_compiles(one_chip, no_persistent_cache, T, Pb,
                                 Qc, span):
    env = dispatch.hist_envelope(T, Pb, Qc, span)
    assert env is not None
    compiled = _compile(
        lambda q, l, k, s, po: hist.hist_bin_multi(
            q, l, k, s, po, Pb, span, *env, interpret=False),
        _spec(one_chip, (ROWS,)), _spec(one_chip, (ROWS,)),
        _spec(one_chip, (ROWS,), jnp.bool_), _spec(one_chip, (T, Pb, Qc)),
        _spec(one_chip, (T,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_aggregation_fits_one_chip(one_chip, no_persistent_cache):
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import jax_engine
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.MEAN],
        max_partitions_contributed=4, max_contributions_per_partition=2,
        min_value=0.0, max_value=10.0)
    config = jax_engine.FusedConfig.from_params(params, public=False)
    n, P = 1 << 20, 1 << 16
    f32 = jnp.float32
    compiled = jax_engine.fused_aggregate_kernel.lower(
        config, P, _spec(one_chip, (n,)), _spec(one_chip, (n,)),
        _spec(one_chip, (n,), f32), _spec(one_chip, (n,), jnp.bool_),
        _spec(one_chip, (0,), f32), _spec(one_chip, (32,), f32),
        _spec(one_chip, (), f32), _spec(one_chip, (), f32),
        _spec(one_chip, (), f32), _spec(one_chip, (), f32),
        _spec(one_chip, (2,), jnp.uint32),
        fx_bits=jax_engine.fused_fx_bits(config, n)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
            mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("name", ["sum_mean", "vector_sum"])
def test_bounding_sort_carries_its_columns(one_chip, no_persistent_cache,
                                           name):
    """The chip's program of a SUM/MEAN release has one sort under
    ``bound_sort`` and nothing under ``bound_permute``: pk and the values
    ride the sort. VECTOR_SUM's [N, D] values cannot, and keep their
    gather there."""
    from test_bound_sort_payload import (assert_bounding_structure,
                                         fused_program_text)
    text = fused_program_text(name, n=2048, sharding=one_chip)
    permute = assert_bounding_structure(name, text)
    # On the chip the gather's index fusion maps to the scope, as a
    # trace reads it.
    assert bool(permute) == (name == "vector_sum")
