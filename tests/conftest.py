"""Test fixture: 8 virtual CPU devices stand in for an 8-chip TPU slice.

This mirrors the reference's test strategy (``mpirun -np 4`` localhost ranks,
SURVEY.md §4): the "fixture" is a real device mesh, not a mock — collectives
actually run, just on the host XLA backend.
"""
import os

# Must be set before jax initializes its backends.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Tests are hermetic on the host backend even when the caller's environment
# names another platform (setdefault above keeps an exported JAX_PLATFORMS).
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, "conftest must provide 8 virtual CPU devices"
    return devs[:8]
