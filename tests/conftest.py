"""Test configuration: hermetic 8-device virtual CPU mesh.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding logic is
exercised without TPU hardware (the run on real chips is chip_smoke.py).
The platform is pinned here, before any backend initializes, so the suite
never takes a chip even when run on a chip host without JAX_PLATFORMS=cpu;
XLA_FLAGS is read at backend init, which hasn't happened yet when conftest
loads.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
