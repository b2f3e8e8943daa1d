"""Test env: CPU backend with 8 virtual devices for mesh tests.

Mirrors the reference test strategy (SURVEY.md §4): CPU is the oracle
backend; mesh/distributed tests run on host-simulated devices
(`--xla_force_host_platform_device_count`), real-TPU tests are gated on
device availability.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

# The env var alone is not enough when something (a pytest plugin) imported
# jax before this file ran and it read another JAX_PLATFORMS — force it via
# config before any backend initializes.
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# CPU is the numerics oracle (reference pattern: CPU kernels are golden);
# default matmul precision emulates TPU bf16 passes, so force full f32.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from tier-1 (-m 'not slow')")


@pytest.fixture(autouse=True)
def fresh_state():
    """Each test gets fresh default programs / scope / name generator."""
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.core import scope as scope_mod

    framework.reset_default_programs()
    scope_mod._reset_global_scope_for_tests()
    old = unique_name.switch()
    yield
    unique_name.switch(old)


@pytest.fixture
def rng():
    return np.random.RandomState(42)
