"""Durable serving (ISSUE 15): the request write-ahead journal, crash
recovery, idempotent re-submission, and the lineage-verified weight
hot-swap / rolling rollout.

The journal's oracle is the batcher itself: a crash-and-recover run
must emit exactly the tokens an uninterrupted run emits (greedy AND
sampled), and with the journal attached but no crash, tokens and
dispatch counts must be bit-identical to a journal-less run — the WAL
is off-path by contract. The rollout's oracle is the fingerprint
lineage: a fleet only ever serves weights whose fingerprint matched a
verified manifest, and any canary failure restores the PRIOR verified
fingerprint without dropping an in-flight stream.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.models import checkpoint as ck
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.journal import RequestJournal
from mxnet_tpu.models.router import ReplicaRouter
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import integrity


def _cfg(**kw):
    base = dict(vocab_size=41, d_model=16, n_heads=2, n_layers=1,
                d_ff=32, max_len=32, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    return cfg, tf.init_params(cfg, seed=0)


# ---------------------------------------------------------- journal --


def test_journal_roundtrip(tmp_path):
    """submit/emit/park/finish fold back into exactly the live and
    finished state a recovering batcher needs."""
    j = RequestJournal(str(tmp_path))
    j.append_submit(0, [1, 2, 3, 9], 6, seed=4, stop_token=7,
                    priority=2, key="a", emitted=1)
    j.append_submit(1, [5, 6], 4, seed=1, emitted=1)
    j.append_emit(0, [8, 2], 3)
    j.append_park(1, [5, 6, 3], 2)
    j.append_submit(2, [7], 3, emitted=1)
    j.append_finish(2, "finish", tokens=[7, 1, 2, 3])
    j.close()

    live, fin, skipped = RequestJournal(str(tmp_path)).replay()
    assert skipped == []
    assert sorted(live) == [0, 1]
    assert live[0] == {"tokens": [1, 2, 3, 9, 8, 2], "n_new": 6,
                       "seed": 4, "stop": 7, "prio": 2, "key": "a",
                       "emitted": 3, "deadline_ms": None}
    assert live[1]["tokens"] == [5, 6, 3]
    assert live[1]["emitted"] == 2
    assert list(fin) == [2]
    assert fin[2]["tokens"] == [7, 1, 2, 3]


def test_journal_torn_and_crc_records_skipped(tmp_path):
    """A torn tail and a CRC-corrupt record are SKIPPED with named
    evidence; the valid records around them still replay."""
    j = RequestJournal(str(tmp_path))
    j.append_submit(0, [1, 2], 5, emitted=1)
    j.append_submit(1, [3, 4], 5, emitted=1)
    j.append_emit(0, [9], 2)
    j.close()
    seg = os.path.join(str(tmp_path), sorted(
        n for n in os.listdir(str(tmp_path)) if n.endswith(".wal"))[0])
    with open(seg, "rb") as f:
        lines = f.read().split(b"\n")
    bad = bytearray(lines[1])
    bad[-1] ^= 0x04                    # rid 1's submit: CRC mismatch
    lines[1] = bytes(bad)
    with open(seg, "wb") as f:
        f.write(b"\n".join(lines[:3]) + b"\n")
        f.write(b"00000000 {\"t\": \"submit\"")   # torn tail

    live, fin, skipped = RequestJournal(str(tmp_path)).replay()
    reasons = sorted(s["reason"] for s in skipped)
    assert len(skipped) == 2
    assert reasons[0].startswith("crc mismatch")
    assert reasons[1].startswith("torn tail")
    assert all(s["segment"].endswith(".wal") and s["record"] >= 0
               for s in skipped)
    assert sorted(live) == [0]         # rid 1 lost, rid 0 intact
    assert live[0]["tokens"] == [1, 2, 9]


def test_journal_gc_never_truncates_live_segments(tmp_path):
    """Segments rotate at segment_bytes; GC only removes a HEAD run of
    segments whose every rid is tombstoned — a segment holding a live
    record (or the active tail) survives every gc() call."""
    j = RequestJournal(str(tmp_path), segment_bytes=200)
    segs = lambda: sorted(n for n in os.listdir(str(tmp_path))
                          if n.endswith(".wal"))
    for rid in range(4):               # all live: GC must be a no-op
        j.append_submit(rid, [1, 2, rid], 4, emitted=1)
    assert len(segs()) > 1             # rotation actually happened
    before = segs()
    j.gc()
    assert segs() == before
    # finish-as-you-go so head segments become fully tombstoned runs
    for rid in range(4):
        j.append_finish(rid, "finish", tokens=[1, 2, rid, 5])
    for rid in range(4, 8):
        j.append_submit(rid, [1, 2, rid], 4, emitted=1)
        if rid < 7:                    # rid 7 stays LIVE in the tail
            j.append_finish(rid, "finish", tokens=[1, 2, rid, 5])
    pre_gc = segs()
    j.gc()
    after = segs()
    assert len(after) < len(pre_gc)    # head run collected
    live, fin, skipped = RequestJournal(str(tmp_path)).replay()
    assert skipped == []
    assert sorted(live) == [7]         # the live rid survived GC
    assert 7 not in fin
    j.close()


both_loops = pytest.mark.parametrize(
    "loop", [{}, {"pipeline_depth": 1}], ids=["default", "depth1"])


@both_loops
def test_journal_off_path_identity(setup, tmp_path, loop):
    """With the journal attached, every stream's tokens AND the
    dispatch count are bit-identical to a journal-less run."""
    cfg, params = setup
    jobs = [([1, 2, 3], 6, 0), ([4, 5], 6, 1), ([7, 8, 9], 5, 2)]

    def run(journal):
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                journal=journal, **loop)
        res, order = srv.run(list(jobs))
        return [res[r] for r in order], srv.dispatch_count

    toks_off, disp_off = run(False)
    toks_on, disp_on = run(str(tmp_path))
    assert toks_on == toks_off
    assert disp_on == disp_off
    live, fin, skipped = RequestJournal(str(tmp_path)).replay()
    assert not live and not skipped and len(fin) == len(jobs)


# --------------------------------------------------------- recovery --


@both_loops
@pytest.mark.parametrize("greedy", [True, False])
def test_recover_bit_exact(setup, tmp_path, greedy, loop, fresh_rows):
    """Drop the batcher mid-flight (simulated crash: the journal is
    all that survives); a fresh batcher's recover() + stepping yields
    exactly the uninterrupted run's streams — greedy and sampled."""
    cfg, params = setup
    jobs = [([1, 2, 3], 6, 0), ([4, 5], 6, 1), ([7, 8, 9], 6, 2)]
    ref_srv = ContinuousBatcher(params, cfg, max_batch=4,
                                greedy=greedy, journal=False, **loop)
    ref, order = ref_srv.run(list(jobs))
    ref = [ref[r] for r in order]

    srv = ContinuousBatcher(params, cfg, max_batch=4, greedy=greedy,
                            journal=str(tmp_path), **loop)
    for p, n, s in jobs:
        srv.admit(p, n, seed=s)
    srv.step()
    srv.step()                         # partial progress, then "crash"
    del srv

    srv2 = ContinuousBatcher(params, cfg, max_batch=4, greedy=greedy,
                             journal=str(tmp_path), **loop)
    made = len(fresh_rows.made)
    resumed, done, skipped = srv2.recover()
    assert skipped == []
    assert resumed                     # genuinely mid-flight
    # a resumed stream's row is one launch, like an admission's
    assert len(fresh_rows.made) - made == len(resumed)
    assert fresh_rows.eager == [4] * 3     # the three batchers' caches
    got = dict(done)
    new2old = {v: k for k, v in resumed.items() if v is not None}
    for _ in range(200):
        if all(n in got or o in got for n, o in new2old.items()):
            break
        for rid, toks in srv2.step().items():
            got[new2old.get(rid, rid)] = toks
    assert [got[rid] for rid in sorted(got)][:len(ref)] == ref
    srv2.check_invariants(quiesce=True)


def test_recover_rid_counter_bumped(setup, tmp_path):
    """Fresh admissions after recover() never collide with journaled
    rids (a replayed tombstone must not kill a new request)."""
    cfg, params = setup
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            journal=str(tmp_path))
    srv.admit([1, 2, 3], 4)
    del srv
    srv2 = ContinuousBatcher(params, cfg, max_batch=2,
                             journal=str(tmp_path))
    srv2.recover()
    rid = srv2.admit([4, 5], 4)
    assert rid > 0                     # past the journaled rid 0


_KILL9_WORKER = r"""
import sys
sys.path.insert(0, ".")
import jax.numpy as jnp
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
cfg = tf.TransformerConfig(vocab_size=41, d_model=16, n_heads=2,
                           n_layers=1, d_ff=32, max_len=32,
                           dtype=jnp.float32)
params = tf.init_params(cfg, seed=0)
srv = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                        block_size=4, num_blocks=24, pipeline_depth=2,
                        spec_k=2, spec_ngram=2, greedy=True,
                        journal=sys.argv[1])
for p, n, s in [([1, 2, 3], 6, 0), ([4, 5], 6, 1), ([7, 8, 9], 6, 2)]:
    srv.admit(p, n, seed=s)
done = {}
for _ in range(300):
    done.update(srv.step())
    if len(done) == 3:
        break
"""


@pytest.mark.slow
def test_recover_after_kill9_subprocess(setup, tmp_path):
    """A REAL hard kill (chaos crash at a journal commit point, exit
    code 9, no interpreter cleanup) under paged x spec x pipeline;
    the parent process recovers the journal bit-exactly.

    (chaos_smoke --durable runs the full greedy+sampled matrix; this
    is the in-suite witness.)"""
    cfg, params = setup
    jobs = [([1, 2, 3], 6, 0), ([4, 5], 6, 1), ([7, 8, 9], 6, 2)]
    ref_srv = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                                block_size=4, num_blocks=24,
                                pipeline_depth=2, spec_k=2,
                                spec_ngram=2, greedy=True,
                                journal=False)
    ref, order = ref_srv.run(list(jobs))
    ref = {r: ref[r] for r in order}

    env = dict(os.environ)
    env.pop("MXNET_SERVING_JOURNAL_DIR", None)
    # every record is two rule matches (pre-write fire + the at-rest
    # corrupt_file hook): at=8 kills on the 5th record's pre-write
    env.update({"MXNET_CHAOS": "journal.append:crash:at=8:code=9",
                "JAX_PLATFORMS": "cpu"})
    proc = subprocess.run(
        [sys.executable, "-c", _KILL9_WORKER, str(tmp_path)],
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 9, proc.stderr[-2000:]

    srv = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                            block_size=4, num_blocks=24,
                            pipeline_depth=2, spec_k=2, spec_ngram=2,
                            greedy=True, journal=str(tmp_path))
    resumed, done, skipped = srv.recover()
    assert skipped == []
    got = dict(done)
    new2old = {v: k for k, v in resumed.items() if v is not None}
    for _ in range(300):
        if all(n in got or o in got for n, o in new2old.items()):
            break
        for rid, toks in srv.step().items():
            got[new2old.get(rid, rid)] = toks
    for rid in sorted(ref):
        assert got.get(rid) == ref[rid], rid
    srv.check_invariants(quiesce=True)


# ------------------------------------------------------ idempotency --


def test_idempotent_submit_live_and_finished(setup):
    """A duplicate key while the original is LIVE returns the original
    rid; after it finishes, a duplicate re-delivers the recorded
    stream through the next step() — no second admission either way."""
    cfg, params = setup
    srv = ContinuousBatcher(params, cfg, max_batch=4, journal=False)
    rid = srv.admit([1, 2, 3], 5, key="req-1")
    disp0 = srv.dispatch_count
    assert srv.admit([1, 2, 3], 5, key="req-1") == rid
    assert srv.active_count == 1       # no double admission
    assert srv.dispatch_count == disp0
    done = {}
    while rid not in done:
        done.update(srv.step())
    assert srv.admit([1, 2, 3], 5, key="req-1") == rid
    redelivered = srv.step()
    assert redelivered.get(rid) == done[rid]


def test_idempotency_window_survives_recovery(setup, tmp_path):
    """The dedup window is journal-backed: after a crash, a re-submit
    of a FINISHED key re-delivers instead of recomputing."""
    cfg, params = setup
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            journal=str(tmp_path))
    rid = srv.admit([1, 2, 3], 5, key="k")
    done = {}
    while rid not in done:
        done.update(srv.step())
    del srv
    srv2 = ContinuousBatcher(params, cfg, max_batch=2,
                             journal=str(tmp_path))
    srv2.recover()
    disp0 = srv2.dispatch_count
    assert srv2.admit([1, 2, 3], 5, key="k") == rid
    out = srv2.step()
    assert out.get(rid) == done[rid]
    assert srv2.dispatch_count == disp0


# --------------------------------------------------------- hot-swap --


@both_loops
def test_swap_weights_verified(setup, tmp_path, loop):
    """A manifest-verified swap lands mid-stream without dropping the
    request, and the post-swap fingerprint matches the manifest."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    ckdir = str(tmp_path / "ck")
    ck.save_checkpoint(ckdir, cfg, p1, step=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2, journal=False,
                            **loop)
    rid = srv.admit([1, 2, 3], 8)
    srv.step()
    info = srv.swap_weights(p1, manifest=ckdir)
    assert info["fingerprint"] == integrity.params_fingerprint(p1)
    assert srv.weight_fingerprint == info["fingerprint"]
    done = {}
    while rid not in done:
        done.update(srv.step())
    assert len(done[rid]) == 3 + 8     # the stream survived the swap
    srv.check_invariants(quiesce=True)


@both_loops
def test_swap_weights_drops_cached_prefixes(setup, loop):
    """A cached prefix's row holds K/V of the OLD weights: after a swap
    an admission that starts with it prefills the whole prompt again
    and streams what solo generate() streams under the new ones."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2, journal=False,
                            **loop)
    prefix = [1, 2, 3, 4, 5, 6, 7, 8]
    srv.cache_prefix(prefix)
    srv.swap_weights(p1)
    prompt = prefix + [9, 10]
    got, order = srv.run([(prompt, 8)])
    want = tf.generate(p1, jnp.asarray([prompt], jnp.int32), 8, cfg)
    np.testing.assert_array_equal(np.asarray(got[order[0]]),
                                  np.asarray(want[0]))


def test_swap_weights_refuses_unverified(setup):
    """A fingerprint mismatch against the manifest refuses the swap
    BEFORE the serving weights change."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2, journal=False)
    fp = srv.weight_fingerprint
    with pytest.raises(ck.CheckpointCorrupt):
        srv.swap_weights(p1, manifest={"param_fingerprint": "0" * 8})
    assert srv.weight_fingerprint == fp


def test_swap_weights_rollback(setup):
    """Swapping back to the prior params restores the prior
    fingerprint exactly (the router's rollback path)."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2, journal=False)
    fp0 = srv.weight_fingerprint
    srv.swap_weights(p1)
    assert srv.weight_fingerprint != fp0
    srv.swap_weights(params)
    assert srv.weight_fingerprint == fp0


# ---------------------------------------------------------- rollout --


def _fleet(cfg, params, n=2):
    reps = [ContinuousBatcher(params, cfg, max_batch=4, journal=False)
            for _ in range(n)]
    return reps, ReplicaRouter(reps, journal=False)


def _drive(router, results, cap=500):
    for _ in range(cap):
        if not (router._queue or router._live or
                router.rollout_phase in ("draining", "canary")):
            return
        results.update(router.step())
    raise AssertionError("router stalled")


def test_rollout_happy_path(setup):
    """Rolling upgrade mid-traffic: every replica drains, swaps,
    passes its bit-exact canary; zero requests dropped."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    reps, router = _fleet(cfg, params)
    order = [router.submit([1, 2, 3], 6, seed=s) for s in range(5)]
    router.step()
    fp = router.start_rollout(p1)
    assert fp == integrity.params_fingerprint(p1)
    results = {}
    _drive(router, results)
    assert router.rollout_phase == "done"
    assert all(r.weight_fingerprint == fp for r in reps)
    assert all(results.get(r) is not None for r in order)
    kinds = [e[0] for e in router.rollout_events]
    assert kinds.count("upgraded") == 2 and kinds[-1] == "done"


def test_rollout_chaos_canary_rolls_back(setup):
    """An injected canary fault rolls EVERY replica back to the prior
    verified fingerprint; in-flight requests all still deliver."""
    from mxnet_tpu.observability import chaos
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    reps, router = _fleet(cfg, params)
    fp0 = reps[0].weight_fingerprint
    order = [router.submit([1, 2, 3], 6, seed=s) for s in range(5)]
    router.step()
    chaos.inject("router.rollout", "error", at=1)   # the canary fire
    try:
        router.start_rollout(p1)
        results = {}
        with pytest.warns(RuntimeWarning, match="rolled back"):
            _drive(router, results)
    finally:
        chaos.reset()
    assert router.rollout_phase == "rolled_back"
    assert all(r.weight_fingerprint == fp0 for r in reps)
    assert all(results.get(r) is not None for r in order)


def test_rollout_refuses_bad_lineage(setup):
    """A manifest whose fingerprint mismatches refuses the rollout
    with the fleet untouched."""
    cfg, params = setup
    p1 = tf.init_params(cfg, seed=1)
    reps, router = _fleet(cfg, params)
    fp0 = reps[0].weight_fingerprint
    with pytest.raises(ck.CheckpointCorrupt):
        router.start_rollout(p1, manifest={"param_fingerprint": "0" * 8})
    assert router._rollout is None
    assert all(r.weight_fingerprint == fp0 for r in reps)


# ----------------------------------------------------------- health --


def test_health_snapshot_durability_keys(setup, tmp_path):
    """/healthz carries the journal depth/lag gauges, the weight
    version, and the router's rollout phase."""
    cfg, params = setup
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            journal=str(tmp_path))
    srv.admit([1, 2, 3], 4)
    snap = srv.health_snapshot()
    assert snap["serving.journal_depth_bytes"] > 0
    assert snap["serving.journal_lag_records"] >= 1
    assert snap["serving.weight_version"] == int(
        srv.weight_fingerprint, 16)

    reps, router = _fleet(cfg, params)
    assert router.health_snapshot()["router.rollout_phase"] == 0
    router.start_rollout(tf.init_params(cfg, seed=1))
    snap = router.health_snapshot()
    assert snap["router.rollout_phase"] == 1       # draining
    assert snap["router.rollout_target_fp"] > 0
