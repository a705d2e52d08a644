"""Test harness: a deterministic 8-worker virtual mesh on CPU.

This replaces the reference's integration harness (one JVM per worker launched over
ssh by collective/Driver.java:93): every multi-worker behavior is tested in a single
process on an 8-device virtual CPU mesh, exactly how the driver validates the
multi-chip path.
"""

import os

# Must run before jax initializes a backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def session():
    from harp_tpu.session import HarpSession

    assert len(jax.devices()) == 8, "virtual device mesh not active"
    return HarpSession(num_workers=8)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "large: larger-scale behavior tests (~1 min total); "
        "deselect with -m 'not large'")
    config.addinivalue_line(
        "markers", "slow: multi-process gang relaunch tests (minutes); "
        "excluded from tier-1 (-m 'not slow')")
