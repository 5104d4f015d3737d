"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's fake-cluster strategy (reference tests/conftest.py
spawns 4 real node processes on localhost) — here multi-chip behavior is
tested by forcing XLA to expose 8 host devices, so shardings/collectives
compile and execute exactly as they would across a real TPU slice.

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

# Tests run on the virtual 8-device CPU mesh wherever they are started —
# the chip is reached only through chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (env must be set first)

jax.config.update("jax_platforms", "cpu")
