"""Replica router (models/router.py): SLO-aware routing, shedding, and
failure draining over N ContinuousBatcher replicas.

The per-stream oracle is still solo generate() — the router must never
perturb a stream, only place it; chaos-injected replica death must
re-route the drained requests bit-exactly (greedy decode is a pure
function of the token prefix).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.router import ReplicaRouter
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import chaos
from mxnet_tpu.observability import core as obs


def _cfg(**kw):
    base = dict(vocab_size=211, d_model=24, n_heads=4, n_layers=2,
                d_ff=48, max_len=64, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _jobs(rng, n):
    return [(list(rng.randint(1, 211, rng.randint(3, 12))),
             int(rng.randint(4, 12))) for _ in range(n)]


def _solo(params, prompt, n, cfg, **kw):
    return np.asarray(tf.generate(params, jnp.asarray([prompt],
                                                      jnp.int32),
                                  n, cfg, **kw)[0])


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
@pytest.mark.parametrize("paged", [False, True])
def test_router_streams_bit_exact(paged, loop):
    """Jobs spread over 2 replicas all emit exactly their solo greedy
    streams, and the fleet balances (both replicas served work):
    replicas as built by default (two rounds in flight) and replicas
    with a window of one."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(1)
    jobs = _jobs(rng, 8)
    kw = dict(loop, paged=True, block_size=8) if paged else dict(loop)
    r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2,
                            **kw)
    results, order = r.run(jobs)
    assert len(results) == len(jobs) and not r.shed_rids
    for rid, (p, n) in zip(order, jobs):
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      _solo(params, p, n, cfg),
                                      err_msg="rid %d" % rid)


def test_router_sampled_streams_bit_exact():
    cfg = _cfg()
    params = tf.init_params(cfg, seed=17)
    rng = np.random.RandomState(6)
    jobs = [(p, n, 100 + i)
            for i, (p, n) in enumerate(_jobs(rng, 6))]
    r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2,
                            paged=True, block_size=8,
                            temperature=0.8, top_k=20)
    results, order = r.run(jobs)
    for rid, (p, n, seed) in zip(order, jobs):
        np.testing.assert_array_equal(
            np.asarray(results[rid]),
            _solo(params, p, n, cfg, temperature=0.8, top_k=20,
                  seed=seed))


def test_router_routes_to_most_headroom():
    """Admission lands on the replica with the most free blocks."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    r0 = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                           block_size=8, num_blocks=5)
    r1 = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                           block_size=8, num_blocks=17)
    router = ReplicaRouter([r0, r1])
    router.submit([1, 2, 3], 4)
    router.step()
    assert r1.active_count == 1 and r0.active_count == 0


def test_router_chaos_kills_one_replica_drains_and_reroutes():
    """MXNET_CHAOS kills replica r1 mid-stream (every dispatch errors,
    so its internal requeue cap re-raises): the router drains its live
    requests back into the queue, the survivor serves them, greedy
    streams stay bit-exact vs solo generate(), and nothing hangs."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(2)
    jobs = _jobs(rng, 8)
    chaos.reset()
    try:
        # fire from the 3rd r1 dispatch on, forever: r1 gets some
        # streams genuinely mid-flight before its cap (3) re-raises
        chaos.install("serving.dispatch.r1:error:every=1:at=2;"
                      "serving.dispatch.r1:error:every=1:count=0")
        r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2,
                                paged=True, block_size=8)
        results, order = r.run(jobs)
    finally:
        chaos.reset()
    assert r.alive_count == 1 and r._alive[0]
    assert len(results) == len(jobs) and not r.shed_rids
    for rid, (p, n) in zip(order, jobs):
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      _solo(params, p, n, cfg),
                                      err_msg="post-chaos rid %d" % rid)


def test_router_all_replicas_dead_raises():
    """No survivor -> the failure surfaces instead of spinning."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    chaos.reset()
    try:
        chaos.install("serving.dispatch.r0:error:every=1:count=0;"
                      "serving.dispatch.r1:error:every=1:count=0")
        r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2)
        with pytest.raises(Exception):
            r.run([([1, 2, 3], 8)])
    finally:
        chaos.reset()


def test_router_sheds_over_queue_bound_and_counts():
    """With every lane and block busy and the backlog past shed_queue,
    the newest requests are shed: serving.slo_violation.shed counts
    them, the caller sees None, and run() terminates (no hang)."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(4)
    jobs = _jobs(rng, 8)
    obs.reset()
    obs.set_enabled(True)
    try:
        r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=1,
                                paged=True, block_size=8,
                                shed_queue=1)
        results, order = r.run(jobs)
        shed = [rid for rid in order if results[rid] is None]
        assert shed and set(shed) == set(r.shed_rids)
        c = obs.counters().get("serving.slo_violation.shed")
        assert c is not None and c.value == len(shed)
        for rid, (p, n) in zip(order, jobs):
            if results[rid] is None:
                continue
            np.testing.assert_array_equal(np.asarray(results[rid]),
                                          _solo(params, p, n, cfg))
    finally:
        obs.set_enabled(None)
        obs.reset()


def test_router_slo_floor_gates_admission():
    """A replica below the SLO attainment floor takes no NEW
    admissions (its snapshot is the gate); with every replica below
    the floor nothing admits and the backlog sheds past the bound
    instead of hanging."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    r0 = ContinuousBatcher(params, cfg, max_batch=2)
    r1 = ContinuousBatcher(params, cfg, max_batch=2)
    router = ReplicaRouter([r0, r1], slo_floor=0.9, shed_queue=0)
    # fake the PR 7 signal: r0 is violating, r1 is healthy
    snaps = {id(r0): 0.5, id(r1): 1.0}
    orig = ContinuousBatcher.health_snapshot

    def patched(self):
        snap = orig(self)
        snap["serving.slo_attainment"] = snaps[id(self)]
        return snap

    ContinuousBatcher.health_snapshot = patched
    try:
        rid = router.submit([1, 2, 3], 4)
        done = {}
        while not done:
            done.update(router.step())
        assert r1._next_rid == 1 and r0._next_rid == 0
        assert done[rid] is not None
        # now both violate: the request cannot admit and sheds
        snaps[id(r1)] = 0.5
        rid2 = router.submit([1, 2, 3], 4)
        out = router.step()
        assert out.get(rid2, "missing") is None
        assert rid2 in router.shed_rids
    finally:
        ContinuousBatcher.health_snapshot = orig


def test_router_env_knobs(monkeypatch):
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    monkeypatch.setenv("MXNET_ROUTER_SHED_QUEUE", "3")
    monkeypatch.setenv("MXNET_ROUTER_SLO_FLOOR", "0.75")
    r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=1)
    assert r.shed_queue == 3 and r.slo_floor == 0.75
    with pytest.raises(ValueError):
        ReplicaRouter([])
