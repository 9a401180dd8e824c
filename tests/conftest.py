"""Test configuration: an 8-device virtual CPU platform so multi-chip
sharding paths (mesh/pjit/shard_map) are exercised without TPU hardware —
mirroring the reference's "N processes on one host" distributed test
strategy (SURVEY.md §4).  The environment is set before jax is imported;
nothing here touches a device."""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if not re.search(r"--xla_force_host_platform_device_count=\d+", _flags):
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; tier-1 deselects with -m 'not slow'")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu
    paddle_tpu.seed(0)
    np.random.seed(0)
    yield
