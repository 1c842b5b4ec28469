"""Test config: run the whole suite on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (SURVEY §7 /
driver contract). Both variables are set before jax is imported; jax
honours JAX_PLATFORMS by itself."""

import os

os.environ.setdefault("XLA_FLAGS",
                      (os.environ.get("XLA_FLAGS", "") +
                       " --xla_force_host_platform_device_count=8").strip())
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    # MXNET_TEST_SEED lets tools/flakiness_checker.py vary the seed per
    # trial (reference tests/python/unittest/common.py with_seed); the
    # default 0 keeps ordinary runs deterministic
    seed = int(os.environ.get("MXNET_TEST_SEED", 0))
    np.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
