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


@pytest.fixture
def fresh_rows(monkeypatch):
    """Who made the one-lane rows of a test's admissions. `.eager` lists
    the batch of every tf.init_cache call that ran eagerly (a launch a
    leaf), `.traced` counts the times its one-lane body ran under a trace
    (a batcher's eval_shape, the row's program being built) and `.made`
    lists the config of every row ContinuousBatcher._fresh_row handed out.
    The row's programs of earlier tests are dropped, so the first row of
    a config is always the one that traces."""
    import types
    import jax
    from mxnet_tpu.models import serving, transformer as tf

    seen = types.SimpleNamespace(eager=[], traced=0, made=[])
    for key in [k for k in tf._PREFILL_JIT_CACHE if k[0] == "fresh_row"]:
        del tf._PREFILL_JIT_CACHE[key]
    init_cache = tf.init_cache
    fresh_row = serving.ContinuousBatcher._fresh_row

    def spy_init(cfg, batch):
        cache = init_cache(cfg, batch)
        if isinstance(jax.tree.leaves(cache)[0], jax.core.Tracer):
            seen.traced += batch == 1
        else:
            seen.eager.append(batch)
        return cache

    def spy_row(self, cfg=None):
        seen.made.append(self.cfg if cfg is None else cfg)
        return fresh_row(self, cfg)
    monkeypatch.setattr(tf, "init_cache", spy_init)
    monkeypatch.setattr(serving.ContinuousBatcher, "_fresh_row", spy_row)
    return seen
