"""Row-space segment primitives — the array-plane building blocks of the
fused DP aggregation (SURVEY.md §7: ``group_by_key`` = sort + contiguous
runs, ``sample_fixed_per_key`` = random-priority sort + rank-in-run,
``combine_accumulators_per_key`` = ``segment_sum``).

Design note: on TPU a scatter (``segment_sum``/``segment_max`` over the
row axis) costs roughly an order of magnitude more than an elementwise op,
so after the single bounding sort every per-segment quantity is derived
*in row space* from cumulative ops over the contiguous runs —
``run_start`` is a cummax, ranks are index differences, group ordinals
are cumsum differences. The only scatters in the fused kernel are the
final per-pk reductions.

Everything here is jit-compatible: static shapes, no data-dependent
Python control flow. Padding rows carry ``PAD_ID`` keys so they sort
after all real rows.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# Sentinel for padding rows: sorts after all real ids.
PAD_ID = jnp.iinfo(jnp.int32).max


def fmix32(x):
    """murmur3 finalizer: a cheap elementwise bijection on uint32 with
    full avalanche. Works on jax and numpy arrays alike; used to derive
    per-(pid, pk) sampling priorities and shard assignments."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def run_starts(new_run):
    """Per-row index of the first row of its run, scatter-free.

    ``new_run`` is a bool[N] marking run boundaries over rows that are
    sorted so equal keys are contiguous; row 0 must be marked. The first
    index of each run is then a running maximum of the marked indices.
    """
    idx = jnp.arange(new_run.shape[0])
    return jax.lax.cummax(jnp.where(new_run, idx, 0))


def rank_in_run(new_run):
    """0-based rank of each row inside its contiguous run."""
    idx = jnp.arange(new_run.shape[0])
    return idx - run_starts(new_run)


def run_ordinal_in_group(new_run, new_group):
    """Per row: the ordinal (0-based) of the row's run within its group.

    Runs and groups are both contiguous after the sort and every group
    boundary is also a run boundary (``new_group`` implies ``new_run``).
    With the run order inside each group randomized by a hashed sort key,
    ``ordinal < k`` IS a uniform without-replacement sample of k runs per
    group — the L0 contribution bound.
    """
    run_ord = jnp.cumsum(new_run.astype(jnp.int32)) - 1
    group_first_run = jax.lax.cummax(jnp.where(new_group, run_ord, 0))
    return run_ord - group_first_run
