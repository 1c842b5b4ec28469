"""A hybrid model (state-space layers beside attention) through
ContinuousBatcher: every stream equals its solo generate(), whatever
shares the pool with it, and every mechanism that cannot carry recurrent
state refuses by name instead of serving other tokens."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import core as obs

KINDS = ("mamba", "attention", "mamba")
CFG = tf.TransformerConfig(
    vocab_size=97, d_model=32, n_heads=4, n_kv_heads=1, n_layers=3,
    layer_kinds=KINDS, d_ff=64, ffn="gated_silu", positions="none",
    max_len=64, ssm_state=8, ssm_dt_rank=4)


@pytest.fixture(scope="module")
def params():
    return tf.init_params(CFG, seed=5)


def _jobs(rng, n):
    """Mixed lengths: prompts on both sides of the 8 and 16 buckets."""
    return [(list(rng.randint(1, 97, rng.randint(3, 20))),
             int(rng.randint(2, 12))) for _ in range(n)]


def _solo(params, prompt, n_new):
    out = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new, CFG)
    return [int(t) for t in np.asarray(out)[0]]


@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 2},
    {"chunk_size": 2, "pipeline_depth": 2}, {"pipeline_depth": 1},
    {"chunk_size": 4, "pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth2", "chunk2-depth2", "depth1",
         "chunk4-depth1"])
def test_streams_equal_solo_generate_with_lane_reuse(params, kw):
    """Seven requests through two lanes: every lane is reused, each
    admission writes a whole row of both kinds of state over the
    previous occupant's. The defaults keep two rounds in flight; a
    window of one (depth 1) serves the same streams."""
    jobs = _jobs(np.random.RandomState(3), 7)
    srv = ContinuousBatcher(params, CFG, max_batch=2, **kw)
    assert srv.pipeline_depth == kw.get("pipeline_depth", 2)
    got, order = srv.run(jobs)
    assert len(got) == len(order) == len(jobs)
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _solo(params, prompt, n_new)


def test_sampled_streams_equal_solo_generate(params):
    rng = np.random.RandomState(4)
    jobs = [(list(rng.randint(1, 97, n)), 7, seed)
            for n, seed in ((5, 11), (13, 12), (9, 13))]
    srv = ContinuousBatcher(params, CFG, max_batch=2, temperature=0.8,
                            top_k=5)
    got, order = srv.run(jobs)
    for (prompt, n_new, seed), rid in zip(jobs, order):
        solo = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new,
                           CFG, seed=seed, temperature=0.8, top_k=5)
        assert list(got[rid]) == [int(t) for t in np.asarray(solo)[0]]


def test_streams_equal_solo_generate_through_cache_prefix(params,
                                                          fresh_rows):
    rng = np.random.RandomState(8)
    prefix = list(rng.randint(1, 97, 11))
    srv = ContinuousBatcher(params, CFG, max_batch=2)
    assert srv.cache_prefix(prefix) == 11
    jobs = [(prefix + list(rng.randint(1, 97, n)), 6) for n in (1, 5, 9)]
    jobs.append((prefix, 5))                    # the prefix is the prompt
    jobs.append((list(rng.randint(1, 97, 9)), 4))        # a miss
    got, order = srv.run(jobs)
    # the prefix's own row and the miss's: both kinds of state, one
    # launch each
    assert fresh_rows.made == [CFG] * 2 and fresh_rows.eager == [2]
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _solo(params, prompt, n_new)


def test_a_cancelled_lane_and_a_continuation_leave_no_state_behind(
        params, fresh_rows):
    rng = np.random.RandomState(2)
    srv = ContinuousBatcher(params, CFG, max_batch=2)
    victim = srv.admit(list(rng.randint(1, 97, 14)), 30)
    for _ in range(3):
        srv.step()
    srv.cancel(victim)
    # the freed lane's next occupant, and a stream resumed mid-way
    prompt = list(rng.randint(1, 97, 7))
    want = _solo(params, prompt, 10)
    rid = srv.admit_continuation(want[:7 + 4], 6, emitted=4)
    other = srv.admit(list(rng.randint(1, 97, 5)), 3)
    done = {}
    while rid not in done or other not in done:
        done.update(srv.step())
    assert list(done[rid]) == want
    # (generate() above builds its own cache of one lane eagerly)
    assert fresh_rows.made == [CFG] * 3 and fresh_rows.eager == [2, 1]


def test_the_two_kinds_of_state_are_published(params, monkeypatch):
    monkeypatch.setenv("MXNET_OBS", "1")
    srv = ContinuousBatcher(params, CFG, max_batch=3)
    srv.admit([5, 6, 7, 8, 9], 4)
    srv.admit([1, 2, 3], 4)
    # a lane of this model: two Mamba layers of (3 x 64 conv + 8 x 64 ssm)
    # float32, and one attention layer of K and V, 1 head of 8
    lane = 2 * (3 * 64 * 4 + 8 * 64 * 4)
    snap = srv.health_snapshot()
    assert snap["serving.state_bytes"] == 2 * lane
    assert snap["serving.kv_bytes"] == (6 + 4) * (2 * 8 * 4)
    srv.step()
    assert obs.gauge("serving.state_bytes").value == 2 * lane
    assert obs.gauge("serving.kv_bytes").value == (7 + 5) * (2 * 8 * 4)
    # an attention-only model holds no recurrent state
    plain = dataclasses.replace(CFG, layer_kinds=None)
    srv = ContinuousBatcher(tf.init_params(plain, 0), plain, max_batch=2)
    srv.admit([1, 2, 3], 2)
    assert srv.health_snapshot()["serving.state_bytes"] == 0


@pytest.mark.parametrize("what,call", [
    ("paged", lambda p: ContinuousBatcher(p, CFG, max_batch=2, paged=True)),
    ("spec_k", lambda p: ContinuousBatcher(p, CFG, max_batch=2, spec_k=2)),
    ("kv_cache_int8", lambda p: ContinuousBatcher(
        p, dataclasses.replace(CFG, kv_cache_int8=True), max_batch=2)),
    ("kv_cache_int8", lambda p: tf.init_cache(
        dataclasses.replace(CFG, kv_cache_int8=True), 1)),
    ("paged", lambda p: tf.init_paged_cache(CFG, 4, 16)),
    ("decode_step_paged", lambda p: tf.decode_step_paged(
        p, None, None, None, None, CFG)),
    ("verify_chunk", lambda p: tf.verify_chunk(p, None, None, None, CFG)),
    ("verify_chunk_paged", lambda p: tf.verify_chunk_paged(
        p, None, None, None, None, CFG)),
    ("speculative decoding", lambda p: tf.speculative_generate(
        p, p, jnp.ones((1, 3), jnp.int32), 4, CFG, CFG)),
    ("quantize_weights_int8", tf.quantize_weights_int8),
    ("mesh-sharded forward", lambda p: tf.forward(
        p, jnp.ones((1, 4), jnp.int32), CFG, mesh=object())),
    ("shard_params", lambda p: tf.shard_params(p, CFG, None)),
    ("shard_cache", lambda p: tf.shard_cache(None, CFG, None)),
])
def test_what_cannot_carry_recurrent_state_refuses_by_name(params, what,
                                                           call):
    with pytest.raises(ValueError, match="state-space") as e:
        call(params)
    assert what in str(e.value)


@pytest.mark.parametrize("name,what", [("MXNET_KV_PAGED", "paged"),
                                       ("MXNET_SPEC_K", "spec_k")])
def test_the_environment_cannot_page_or_speculate_a_hybrid_model(
        params, monkeypatch, name, what):
    monkeypatch.setenv(name, "2")
    with pytest.raises(ValueError, match=what):
        ContinuousBatcher(params, CFG, max_batch=2)
