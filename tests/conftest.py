"""Test harness configuration.

Tests always run on a virtual 8-device CPU mesh so multi-chip sharding
(`shard_map` + psum/pmax sketch merges) is exercised without TPU hardware,
mirroring how the reference tests its distributed paths with in-process
rings and local backends (SURVEY.md section 4). The platform is pinned
through JAX_PLATFORMS before jax is imported, which also covers every
subprocess a test spawns.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running multi-process e2e tests")
