"""Overload resilience in the serving stack (models/serving.py,
models/router.py): priorities + deadlines, KV-pressure preemption with
bit-exact resume, the brownout ladder, and replica circuit breakers.

The oracle never changes: every COMPLETED stream equals its solo
generate() output — preemption, brownout and breaker revival may move
work around, delay it, or refuse it, but they may never perturb a
token. Refused work is accounted (shed vs expired are different
counters) and the block pool balances to zero leak at quiesce
(check_invariants), which is what "degrade instead of die" means."""

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.router import ReplicaRouter
from mxnet_tpu.models.serving import BlockAllocator, ContinuousBatcher
from mxnet_tpu.observability import chaos
from mxnet_tpu.observability import core as obs


def _cfg(**kw):
    base = dict(vocab_size=97, d_model=16, n_heads=2, n_layers=1,
                d_ff=32, max_len=48, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _solo(params, prompt, n, cfg, **kw):
    return np.asarray(tf.generate(params, jnp.asarray([prompt],
                                                      jnp.int32),
                                  n, cfg, **kw)[0])


_P0 = [3, 5, 7, 5, 7, 5]
_P1 = [11, 2, 9, 4, 2, 6]
_P2 = [1, 9, 4, 9, 4, 9]


def _drive(srv, want, done=None, max_rounds=400):
    """Step until every rid in `want` finished. Bounded: a server that
    stops making progress fails the test instead of spinning."""
    done = {} if done is None else done
    for _ in range(max_rounds):
        if all(r in done for r in want):
            return done
        done.update(srv.step())
    missing = [r for r in want if r not in done]
    assert not missing, (
        "no progress: rids %s unfinished after %d rounds "
        "(active=%d, preempted=%d)" % (missing, max_rounds,
                                       srv.active_count,
                                       len(srv.preempted)))
    return done


# ---- allocator audit (satellite) ----


def test_block_allocator_check_invariants():
    """The standing leak detector: a fresh allocator audits clean
    (quiesce included), live mappings must conserve refcounts exactly,
    and every corruption class raises."""
    a = BlockAllocator(8)
    assert a.check_invariants(quiesce=True)
    ids = a.alloc(3)
    a.share(ids[:1])
    assert a.check_invariants(mappings=[ids, ids[:1]])
    # refcount without a mapping holding it -> leak
    with pytest.raises(RuntimeError, match="no mapping holds it"):
        a.check_invariants(mappings=[ids[:2], ids[:1]])
    # held blocks fail the quiesce bar
    with pytest.raises(RuntimeError, match="leaked"):
        a.check_invariants(quiesce=True)
    a.release(ids[:1])
    a.release(ids)
    assert a.check_invariants(quiesce=True)
    # free-list/refcount disjointness violations
    a.ref[3] = 1
    with pytest.raises(RuntimeError, match="free but refcount"):
        a.check_invariants()
    a.ref[3] = 0
    a._free.append(a._free[-1])
    with pytest.raises(RuntimeError, match="duplicate"):
        a.check_invariants()
    a._free.pop()
    b = a.alloc(1)[0]
    a.ref[b] = 0                     # drop without freeing -> leak
    with pytest.raises(RuntimeError, match="leaked"):
        a.check_invariants()
    a.ref[b] = 1
    a.reserve(100)
    with pytest.raises(RuntimeError, match="reserved"):
        a.check_invariants()


# ---- preemption with bit-exact resume (tentpole 2) ----


@pytest.fixture
def telemetry():
    """The serving.* counters count only with telemetry on."""
    obs.set_enabled(True)
    yield
    obs.set_enabled(None)
    obs.reset()


def test_preempt_resume_bit_exact_greedy(telemetry):
    """A higher-priority admission short on blocks preempts the
    lower-priority lane mid-stream; the victim's synced prefix is
    captured, its blocks fund the admission, and its resumed stream is
    bit-identical to the uninterrupted solo run."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=6)
    pre0 = obs.counter("serving.preemptions").value
    r0 = srv.admit(_P0, 14)          # 3 of the 5 usable blocks
    assert r0 is not None
    done = {}
    for _ in range(3):
        done.update(srv.step())
    solo0 = _solo(params, _P0, 14, cfg)
    r1 = srv.admit(_P1, 14, priority=1)   # needs 3 > 2 available
    assert r1 is not None
    assert obs.counter("serving.preemptions").value == pre0 + 1
    (req, t_ns), = srv.preempted
    srv.preempted = []
    assert req.rid == r0 and req.emitted >= 4
    # the captured prefix is exactly the solo stream so far
    np.testing.assert_array_equal(np.asarray(req.tokens),
                                  solo0[:len(req.tokens)])
    srv.check_invariants()
    done = _drive(srv, [r1], done)
    r0b = srv.admit_continuation(req.tokens, req.n_new - req.emitted,
                                 seed=req.seed, emitted=req.emitted,
                                 preempted_ns=t_ns)
    assert r0b is not None
    done = _drive(srv, [r0b], done)
    np.testing.assert_array_equal(np.asarray(done[r1]),
                                  _solo(params, _P1, 14, cfg))
    np.testing.assert_array_equal(np.asarray(done[r0b]), solo0)
    assert srv.check_invariants(quiesce=True)


def test_preempt_resume_bit_exact_sampled():
    """Sampled preemption resume: the per-request key chain is
    replayed to its post-emitted state, so the resumed stream matches
    solo sampling bit-for-bit — the stronger-than-requeue contract."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    kw = dict(temperature=0.8, top_k=20)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=6, **kw)
    r0 = srv.admit(_P0, 14, seed=11)
    done = {}
    for _ in range(3):
        done.update(srv.step())
    r1 = srv.admit(_P1, 14, seed=23, priority=1)
    assert r1 is not None
    (req, t_ns), = srv.preempted
    srv.preempted = []
    assert req.rid == r0
    done = _drive(srv, [r1], done)
    r0b = srv.admit_continuation(req.tokens, req.n_new - req.emitted,
                                 seed=req.seed, emitted=req.emitted,
                                 preempted_ns=t_ns)
    assert r0b is not None
    done = _drive(srv, [r0b], done)
    np.testing.assert_array_equal(
        np.asarray(done[r1]), _solo(params, _P1, 14, cfg, seed=23,
                                    **kw))
    np.testing.assert_array_equal(
        np.asarray(done[r0b]), _solo(params, _P0, 14, cfg, seed=11,
                                     **kw))
    assert srv.check_invariants(quiesce=True)


def test_preempt_resume_bit_exact_spec_pipelined():
    """The acceptance matrix's hard cell: paged x spec_k>0 x
    pipeline_depth=2. Preemption lands while speculative dispatches
    are in flight (their emissions discard by rid), the draft
    over-reservation returns with the lane's blocks, and the resume is
    still bit-exact."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=6, spec_k=2,
                            spec_ngram=2, pipeline_depth=2)
    r0 = srv.admit(_P0, 14)
    done = {}
    for _ in range(3):
        done.update(srv.step())
    r1 = srv.admit(_P1, 14, priority=1)
    assert r1 is not None
    (req, t_ns), = srv.preempted
    srv.preempted = []
    assert req.rid == r0
    srv.check_invariants()
    done = _drive(srv, [r1], done)
    r0b = srv.admit_continuation(req.tokens, req.n_new - req.emitted,
                                 seed=req.seed, emitted=req.emitted,
                                 preempted_ns=t_ns)
    assert r0b is not None
    done = _drive(srv, [r0b], done)
    np.testing.assert_array_equal(np.asarray(done[r1]),
                                  _solo(params, _P1, 14, cfg))
    np.testing.assert_array_equal(np.asarray(done[r0b]),
                                  _solo(params, _P0, 14, cfg))
    assert srv.check_invariants(quiesce=True)


def test_run_resumes_preempted_and_aliases_rid():
    """run() drains self.preempted automatically and returns the
    resumed stream under its ORIGINAL rid."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=6)
    jobs = [(_P0, 14, 0, None, 0), (_P1, 14, 0, None, 1)]
    results, order = srv.run(jobs)
    assert sorted(results) == sorted(order)
    np.testing.assert_array_equal(np.asarray(results[order[0]]),
                                  _solo(params, _P0, 14, cfg))
    np.testing.assert_array_equal(np.asarray(results[order[1]]),
                                  _solo(params, _P1, 14, cfg))
    assert not srv.preempted
    assert srv.check_invariants(quiesce=True)


def test_uniform_priority_never_preempts():
    """Equal priorities: a block-starved admission waits (returns
    None), exactly the pre-PR behavior — preemption needs a strictly
    higher class."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=6)
    pre0 = obs.counter("serving.preemptions").value
    assert srv.admit(_P0, 14) is not None
    assert srv.admit(_P1, 14) is None
    assert srv.admit(_P1, 14, priority=0) is None
    assert not srv.preempted
    assert obs.counter("serving.preemptions").value == pre0


# ---- router: priorities, deadlines, shed-vs-expired ----


def test_router_priority_admission_order():
    """Admission is priority-then-FIFO: on a one-lane fleet the
    completion order is the priority order, ties oldest-first."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    r = ReplicaRouter.build(params, cfg, n_replicas=1, max_batch=1)
    a = r.submit(_P0, 4)
    b = r.submit(_P1, 4)
    c = r.submit(_P2, 4, priority=2)
    d = r.submit(_P0, 4, priority=1)
    finish_order, results = [], {}
    while r._queue or r._live:
        done = r.step()
        finish_order.extend(sorted(done))
        results.update(done)
    assert finish_order == [c, d, a, b]
    for rid, p in zip((a, b, c, d), (_P0, _P1, _P2, _P0)):
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      _solo(params, p, 4, cfg))


def test_router_expired_vs_shed_separate_counters():
    """A blown deadline expires up front (serving.slo_violation.
    expired); a backlog past shed_queue sheds lowest-priority-newest
    (serving.slo_violation.shed) — distinct counters, distinct rid
    lists, both surfaced by health_snapshot()."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    exp0 = obs.counter("serving.slo_violation.expired").value
    shed0 = obs.counter("serving.slo_violation.shed").value
    r = ReplicaRouter.build(params, cfg, n_replicas=1, max_batch=1,
                            shed_queue=1)
    live = r.submit(_P0, 4)
    dead = r.submit(_P1, 4, deadline_ms=0)      # already blown
    keep_hi = r.submit(_P2, 4, priority=1)      # survives the shed
    victim = r.submit(_P1, 4)                   # lowest-newest -> shed
    results = {}
    while r._queue or r._live:
        results.update(r.step())
    assert r.expired_rids == [dead] and results[dead] is None
    assert r.shed_rids == [victim] and results[victim] is None
    assert obs.counter("serving.slo_violation.expired").value \
        == exp0 + 1
    assert obs.counter("serving.slo_violation.shed").value == shed0 + 1
    snap = r.health_snapshot()
    assert snap["serving.slo_violation.expired"] == 1
    assert snap["serving.slo_violation.shed"] == 1
    assert snap["router.replica_state.r0"] == 0
    for rid, p in ((live, _P0), (keep_hi, _P2)):
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      _solo(params, p, 4, cfg))


def test_router_infeasible_deadline_expires_by_eta():
    """Feasibility expiry: with measured TTFT/ITL medians on record, a
    deadline the queue position cannot possibly meet expires without
    wasting a prefill — and a generous deadline is untouched."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    obs.set_enabled(True)
    obs.reset()                 # the medians below are the seeded ones
    try:
        # seed the estimator: median TTFT 100ms, ITL 100ms -> any job
        # behind another costs >= 700ms end to end
        for _ in range(4):
            obs.histogram("serving.ttft_ms", "ms").observe(100.0)
            obs.histogram("serving.itl_ms", "ms").observe(100.0)
        r = ReplicaRouter.build(params, cfg, n_replicas=1, max_batch=1)
        ok = r.submit(_P0, 6, deadline_ms=600000.0)  # feasible
        bad = r.submit(_P1, 6, deadline_ms=300.0)    # one wave behind
        results = {}
        while r._queue or r._live:
            results.update(r.step())
    finally:
        obs.set_enabled(None)
        obs.reset()
    assert results[bad] is None and r.expired_rids == [bad]
    assert not r.shed_rids
    np.testing.assert_array_equal(np.asarray(results[ok]),
                                  _solo(params, _P0, 6, cfg))


def test_router_absorbs_preempted_and_resumes(telemetry):
    """Fleet-level preemption round trip: the replica preempts for the
    high-priority admission, the router requeues the victim as a
    continuation, and both streams complete bit-exactly."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    pre0 = obs.counter("serving.preemptions").value
    r = ReplicaRouter(
        [ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                           block_size=8, num_blocks=6)])
    lo = r.submit(_P0, 14)
    results = {}
    results.update(r.step())         # lo admitted and decoding
    hi = r.submit(_P1, 14, priority=2)
    while r._queue or r._live:
        results.update(r.step())
    assert obs.counter("serving.preemptions").value == pre0 + 1
    assert not r.shed_rids and not r.expired_rids
    np.testing.assert_array_equal(np.asarray(results[lo]),
                                  _solo(params, _P0, 14, cfg))
    np.testing.assert_array_equal(np.asarray(results[hi]),
                                  _solo(params, _P1, 14, cfg))
    assert r.replicas[0].check_invariants(quiesce=True)


# ---- brownout ladder (tentpole 3) ----


def test_brownout_ladder_climbs_and_recovers():
    """Block exhaustion walks the ladder up one rung per `trip` bad
    rounds; recovery walks it back down one per `clear` good rounds —
    the asymmetric hysteresis. The stream decoding through the whole
    episode is untouched."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, num_blocks=4,
                            brownout=True, brownout_trip=2,
                            brownout_clear=3)
    rid = srv.admit(_P0, 14)         # all 3 usable blocks -> available 0
    assert rid is not None and srv._alloc.available == 0
    done = {}
    for _ in range(4):
        done.update(srv.step())
    assert srv._bo_rung == 2
    assert srv.health_snapshot()["serving.brownout_rung"] == 2
    done = _drive(srv, [rid], done)
    assert srv._bo_rung >= 2
    np.testing.assert_array_equal(np.asarray(done[rid]),
                                  _solo(params, _P0, 14, cfg))
    for _ in range(5 * 3):           # idle rounds are healthy rounds
        srv.step()
    assert srv._bo_rung == 0
    assert srv.check_invariants(quiesce=True)


def test_brownout_admission_gates():
    """Rung 3 throttles to one admission per scheduling round; rung 5
    sheds the lowest priority class outright (higher classes still
    admit). Rung 4 is the kv_shrink rung (docs/ROBUSTNESS.md), which
    acts on the pool and not on admission."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=4, brownout=True)
    srv._bo_rung = 3
    assert srv.admit(_P0, 4) is not None
    assert srv.admit(_P1, 4) is None          # throttled this round
    srv.step()
    assert srv.admit(_P1, 4) is not None      # fresh round
    srv.step()
    srv._bo_rung = 5
    assert srv.admit(_P2, 4, priority=0) is None   # shed class
    assert srv.admit(_P2, 4, priority=1) is not None
    srv._bo_rung = 0
    while srv.active_count:
        srv.step()


def test_brownout_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT", "1")
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT_ATTAIN", "0.5")
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT_TRIP", "7")
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT_CLEAR", "9")
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    assert srv.brownout and srv._brownout_attain == 0.5
    assert srv._brownout_trip == 7 and srv._brownout_clear == 9


# ---- circuit breakers (tentpole 4) ----


def test_breaker_replica_recovers_via_half_open():
    """The kill-then-recover loop: four consecutive injected dispatch
    failures trip the batcher's re-raise, the breaker opens, backs
    off, routes one canary through HALF_OPEN, and the replica returns
    to rotation — with every completed stream still bit-exact."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(7)
    jobs = [(list(rng.randint(1, 97, rng.randint(3, 9))),
             int(rng.randint(6, 12))) for _ in range(10)]
    chaos.reset()
    try:
        chaos.install("serving.dispatch.r1:error:at=2;"
                      "serving.dispatch.r1:error:at=3;"
                      "serving.dispatch.r1:error:at=4;"
                      "serving.dispatch.r1:error:at=5")
        r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2,
                                paged=True, block_size=8, breaker=True)
        results, order = r.run(jobs)
    finally:
        chaos.reset()
    assert ("r1", "closed", "open") in r.breaker_events
    assert ("r1", "open", "half_open") in r.breaker_events
    assert ("r1", "half_open", "closed") in r.breaker_events
    assert r._alive == [True, True]
    assert r._brk_state == ["closed", "closed"]
    assert len(results) == len(jobs)
    assert not r.shed_rids and not r.expired_rids
    for rid, (p, n) in zip(order, jobs):
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      _solo(params, p, n, cfg),
                                      err_msg="rid %d" % rid)
    for rep in r.replicas:
        assert rep.check_invariants(quiesce=True)


def test_breaker_all_open_retries_exhausted_raises():
    """A fault that never clears exhausts the breaker's retries on
    every replica, and only THEN does the all-dead re-raise fire."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    chaos.reset()
    try:
        chaos.install("serving.dispatch.r0:error:every=1:count=0;"
                      "serving.dispatch.r1:error:every=1:count=0")
        reps = [ContinuousBatcher(params, cfg, max_batch=1)
                for _ in range(2)]
        r = ReplicaRouter(reps, breaker=True, breaker_backoff=1,
                          breaker_retries=1)
        with pytest.raises(Exception):
            r.run([(_P0, 8)])
    finally:
        chaos.reset()
    assert r._brk_state == ["open", "open"]
    assert all(t > 1 for t in r._brk_trips)


def test_breaker_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_ROUTER_BREAKER", "1")
    monkeypatch.setenv("MXNET_ROUTER_BREAKER_BACKOFF", "4")
    monkeypatch.setenv("MXNET_ROUTER_BREAKER_BACKOFF_MAX", "64")
    monkeypatch.setenv("MXNET_ROUTER_BREAKER_RETRIES", "2")
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    r = ReplicaRouter.build(params, cfg, n_replicas=1, max_batch=1)
    assert r.breaker and r._breaker_backoff == 4
    assert r._breaker_backoff_max == 64 and r._breaker_retries == 2


# ---- off-path guarantee ----


def test_overload_off_path_silence():
    """With none of the new knobs set, the machinery is inert: same
    dispatch count and bit-identical streams whether or not the new
    arguments ride along at their defaults, zero preemptions, ladder
    parked at rung 0."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    jobs = [(_P0, 10), (_P1, 12), (_P2, 9), (_P0, 7)]
    pre0 = obs.counter("serving.preemptions").value
    ref = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8)
    res_ref, order_ref = ref.run(jobs)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8)
    res, order = srv.run([(p, n, 0, None, 0) for p, n in jobs])
    assert srv.dispatch_count == ref.dispatch_count
    assert order == order_ref
    for rid in order:
        np.testing.assert_array_equal(np.asarray(res[rid]),
                                      np.asarray(res_ref[rid]))
    assert not srv.brownout and srv._bo_rung == 0
    assert not srv.preempted
    assert obs.counter("serving.preemptions").value == pre0
    # router: explicit default priority/deadline args change nothing
    r0 = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2)
    a0, _ = r0.run(jobs)
    r1 = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=2)
    a1, _ = r1.run([(p, n, 0, None, 0, None) for p, n in jobs])
    assert not r0.breaker and not r1.breaker
    for rid in a0:
        np.testing.assert_array_equal(np.asarray(a1[rid]),
                                      np.asarray(a0[rid]))
