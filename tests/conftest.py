"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-run-compiles the
multi-chip path; benchmarks run on the real chip).

Must run before any ``import jax`` — pytest imports conftest first.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running oracle pins (deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: fault-injection coverage (crash–restart, lossy "
        "networks, corrupt checkpoints); select with -m faults. Fast "
        "configs run in tier-1 by default.")
    config.addinivalue_line(
        "markers", "obs: observability coverage (run-trace schema, "
        "trace-on/off parity, metrics registry, /.metrics); select "
        "with -m obs. Fast configs run in tier-1 by default.")
