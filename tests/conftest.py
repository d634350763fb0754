"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic
(`jax.sharding.Mesh` + shard_map collectives) is exercised without TPU
hardware; the TPU path is covered by `python chip_smoke.py` (one chip)
and `python chip_smoke.py --chips 4`, run on the chip. This must run
before anything imports jax.
"""

import os

# Force, don't setdefault: the ambient environment may select a TPU, and
# tests must never depend on TPU hardware. jax may already be pre-imported
# at interpreter startup, so the env var alone is too late — backend
# selection is lazy, and jax.config.update still wins as long as no
# computation has run yet.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
