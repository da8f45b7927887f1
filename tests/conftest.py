"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests must be hermetic and multi-chip-shaped without TPU hardware:
``JAX_PLATFORMS`` and ``XLA_FLAGS`` are set here before jax is imported
(the config update covers a runner that imported jax first), and the
session start asserts the platform really is the CPU. The chip is
reached only through ``chip_smoke.py``; ``tests/test_tpu_compile.py``
compiles for a *described* chip without touching one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# run the whole suite race-checked — the `go test -race ./...` analog
# (utils/raceguard.py): store mutations assert thread affinity
os.environ.setdefault("KCP_RACE", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# the same persistent XLA compile cache the binaries use: recompiles of
# the fused step would otherwise dominate cold isolated test runs (and a
# compile landing inside a latency-bounded test is exactly the stall the
# cache exists to prevent in production)
from kcp_tpu.cli import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


def pytest_sessionstart(session):
    # fail fast if the platform override did not take: the suite must
    # never claim an accelerator another process may be holding
    assert jax.devices()[0].platform == "cpu", jax.devices()
