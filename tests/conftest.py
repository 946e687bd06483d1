"""Test configuration: force an 8-device virtual CPU platform.

This is the standard JAX trick for testing pjit/shard_map/psum multi-device
code without TPU hardware (SURVEY.md §4): must run before jax initialises.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# JAX_PLATFORMS=cpu alone is honoured by this installation; the config
# update also wins over an environment that pins another platform, so the
# suite can never take the chip.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
