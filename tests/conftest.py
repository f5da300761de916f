"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so the multi-chip sharding path
(parallel/) is exercised without TPU hardware.  The chip is driven by
``python chip_smoke.py``; tests/test_tpu_smoke.py compiles the kernels
for a described v5e without one.  The persistent compilation cache stays
off here and in the children tests start (they inherit the environment).

This must run before anything imports jax, which pytest guarantees for a
root conftest.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
