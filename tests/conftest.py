"""Test harness config.

All tests run on a virtual 8-device CPU platform so sharding/collective
tests work without TPU hardware (reference test strategy: SURVEY.md §4 —
TestDistBase simulates the cluster on localhost; here the virtual mesh
plays that role). The platform is pinned to the CPU both ways — the
environment for child processes, jax.config for this one — before any
backend is initialized, so the suite never claims a chip.
"""
import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(2024)
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")
