"""Batched speculative decoding in the serving stack (models/serving.py).

The oracle is always the framework itself: per-round acceptance against
a NUMPY reimplementation fed the real device state, and whole streams
against solo greedy generate() — the bar every serving feature in this
repo ships under. Speculation must be invisible in the tokens and only
visible in the dispatch count.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import chaos


def _cfg(**kw):
    base = dict(vocab_size=97, d_model=16, n_heads=2, n_layers=1,
                d_ff=32, max_len=48, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _dcfg(**kw):
    base = dict(vocab_size=97, d_model=8, n_heads=1, n_layers=1,
                d_ff=16, max_len=48, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _solo(params, prompt, n_new, cfg):
    return np.asarray(tf.generate(
        params, jnp.asarray([prompt], jnp.int32), n_new, cfg,
        greedy=True)[0])


# prompts with internal repetition (the n-gram provider's habitat) —
# tiny greedy models loop quickly, so their continuations repeat too
_PROMPTS = [[3, 5, 7, 5, 7, 5], [11, 2, 2, 2, 2],
            [1, 9, 4, 9, 4, 9, 4]]
_N_NEW = [12, 10, 14]


def _run_pool(srv, jobs):
    """Drive admissions + steps to completion; {rid: tokens} plus the
    admission order (rid per job, FIFO)."""
    out, order = {}, []
    it = iter(jobs)
    nxt = next(it, None)
    while True:
        while nxt is not None and srv.has_capacity:
            rid = srv.admit(nxt[0], nxt[1])
            if rid is None:
                break
            order.append(rid)
            nxt = next(it, None)
        out.update(srv.step())
        if nxt is None and not srv.active_count:
            break
    return out, order


def test_spec_round_matches_numpy_oracle():
    """One speculative round's (targets, emits) against a full numpy
    reimplementation fed the REAL device state: n-gram proposal
    (latest-suffix-match, off-stream fallback, keff masking), stepped
    teacher-forced target argmax, cumprod prefix acceptance."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    k, ng = 4, 2
    srv = ContinuousBatcher(params, cfg, max_batch=3, spec_k=k,
                            spec_ngram=ng)
    for p, n in zip(_PROMPTS, _N_NEW):
        assert srv.admit(p, n) is not None
    # shrink one lane's effective k: the -1 sentinel masking is part
    # of the oracle contract
    srv._keff[1] = 2
    hist0 = np.asarray(srv._dev_hist)
    tok0 = np.asarray(srv._dev_tok)
    pos0 = np.asarray(srv._dev_pos)
    keff0 = np.array(srv._keff)
    lane_caches = [jax.tree.map(lambda x, i=i: x[i:i + 1], srv._cache)
                   for i in range(3)]
    targets, emits, _, _, _, _ = srv._spec_fn(
        srv.params, srv._cache, srv._dev_hist, srv._dev_tok,
        srv._dev_pos, jnp.asarray(srv._keff))
    targets = np.asarray(targets)[0]          # rounds=1 -> [B, k+1]
    emits = np.asarray(emits)[0]
    for b in range(3):
        # numpy n-gram proposal oracle
        hist, pos, tok = hist0[b], int(pos0[b]), int(tok0[b])
        suffix = [hist[max(pos - ng + 1 + o, 0)] for o in range(ng)]
        best = -1
        for j in range(hist.shape[0]):
            if j + ng - 1 >= pos:
                continue
            if all(hist[(j + o) % hist.shape[0]] == suffix[o]
                   for o in range(ng)):
                best = max(best, j)
        drafts = []
        for i in range(k):
            g = best + ng + i
            ok = best >= 0 and g <= pos
            d = int(hist[g]) if ok else tok
            drafts.append(d if i < keff0[b] else -1)
        # teacher-forced stepped target oracle over the window
        window = [tok] + [max(d, 0) for d in drafts]
        ci, oracle = lane_caches[b], []
        for i, t in enumerate(window):
            li, ci = tf.decode_step(params, ci, jnp.asarray(
                [t], jnp.int32), pos + i, cfg)
            oracle.append(int(np.argmax(np.asarray(li)[0])))
        np.testing.assert_array_equal(targets[b], oracle)
        acc = 0
        for i in range(k):
            if drafts[i] != oracle[i]:
                break
            acc += 1
        assert int(emits[b]) == acc + 1, (b, drafts, oracle)


@pytest.mark.parametrize("provider", ["ngram", "model"])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("depth", [1, 2])
def test_spec_streams_bitexact(provider, paged, depth, fresh_rows):
    """Every spec-enabled greedy stream equals solo generate() —
    dense/paged x depth 1/2 x both draft providers. An admission's
    zeroed rows, the target's and a draft model's, are each one launch
    of that config's program."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    kw = dict(max_batch=2, pipeline_depth=depth, spec_k=3, paged=paged)
    if paged:
        kw.update(block_size=8)
    if provider == "model":
        kw.update(draft_params=tf.init_params(_dcfg(), seed=9),
                  draft_cfg=_dcfg())
    srv = ContinuousBatcher(params, cfg, **kw)
    jobs = list(zip(_PROMPTS, _N_NEW))
    out, order = _run_pool(srv, jobs)
    assert len(out) == len(jobs)
    assert fresh_rows.made.count(cfg) == len(jobs)
    assert fresh_rows.made.count(_dcfg()) \
        == (len(jobs) if provider == "model" else 0)
    assert 1 not in fresh_rows.eager
    for rid, (p, n) in zip(order, jobs):
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _solo(params, p, n, cfg))
    if paged:
        # every block returned, every reservation released
        assert srv._alloc.free_blocks == srv.num_blocks - 1
        assert srv._alloc.reserved == 0


@pytest.mark.parametrize("paged", [False, True])
def test_spec_mid_flight_eviction(paged):
    """cancel() mid-decode under speculative pipelining: the evicted
    lane's in-flight emissions are discarded by rid, the survivors and
    the replacement admission stay bit-exact."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    kw = dict(max_batch=2, pipeline_depth=2, spec_k=3, paged=paged)
    if paged:
        kw.update(block_size=8)
    srv = ContinuousBatcher(params, cfg, **kw)
    r0 = srv.admit(_PROMPTS[0], 14)
    r1 = srv.admit(_PROMPTS[1], 14)
    done = {}
    done.update(srv.step())          # speculative chunks in flight
    partial = srv.cancel(r0)
    assert partial is not None
    np.testing.assert_array_equal(
        np.asarray(partial),
        _solo(params, _PROMPTS[0], 14, cfg)[:len(partial)])
    r2 = srv.admit(_PROMPTS[2], 10)  # reuses the evicted lane
    while r1 not in done or r2 not in done:
        done.update(srv.step())
    np.testing.assert_array_equal(np.asarray(done[r1]),
                                  _solo(params, _PROMPTS[1], 14, cfg))
    np.testing.assert_array_equal(np.asarray(done[r2]),
                                  _solo(params, _PROMPTS[2], 10, cfg))


def test_spec_cancel_mid_round_trims_draft_reservation():
    """cancel() landing while a speculative verify window is in flight
    (paged): the lane's whole block claim — worst-case draft
    over-reservation included — returns to the pool at the cut, the
    freed lane's table parks on the null block, and the allocator
    passes its conservation audit at the cut, every step after, and at
    quiesce (zero leak). The PR 10 matrix's untested cell."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, spec_k=3,
                            paged=True, block_size=8, pipeline_depth=2)
    r0 = srv.admit(_PROMPTS[0], 14)
    r1 = srv.admit(_PROMPTS[1], 14)
    lane0 = next(i for i, r in enumerate(srv._slots)
                 if r is not None and r.rid == r0)
    done = {}
    done.update(srv.step())          # verify windows in flight
    claim = len(srv._lane_blocks[lane0])
    need = srv._lane_need[lane0]
    assert claim >= 1 and need >= claim
    avail_before = srv._alloc.available
    assert srv.cancel(r0) is not None
    # the lane's mapped blocks AND its unconverted reservation came
    # back (shared prefixes would hold some — none are cached here)
    assert srv._alloc.available == avail_before + need
    assert not srv._lane_blocks[lane0] and not srv._lane_need[lane0]
    assert not np.asarray(srv._tables)[lane0].any()   # null routing
    srv.check_invariants()
    while r1 not in done:
        done.update(srv.step())
        srv.check_invariants()
    np.testing.assert_array_equal(np.asarray(done[r1]),
                                  _solo(params, _PROMPTS[1], 14, cfg))
    assert srv.check_invariants(quiesce=True)


@pytest.mark.parametrize("provider", ["ngram", "model"])
def test_spec_requeue_on_dispatch_failure(provider, fresh_rows):
    """The PR 6 recovery contract holds under speculation: an injected
    dispatch fault rebuilds the pool AND the draft state (history rows
    / draft cache died with the donated carry), requeues from the
    synced prefix, and greedy streams stay bit-exact."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    kw = dict(max_batch=2, spec_k=3, paged=True, block_size=8,
              pipeline_depth=2)
    if provider == "model":
        kw.update(draft_params=tf.init_params(_dcfg(), seed=9),
                  draft_cfg=_dcfg())
    chaos.reset()
    try:
        srv = ContinuousBatcher(params, cfg, **kw)
        r0 = srv.admit(_PROMPTS[0], 12)
        r1 = srv.admit(_PROMPTS[1], 10)
        done = {}
        done.update(srv.step())
        chaos.inject(srv._chaos_site, "error", at=0)
        while r0 not in done or r1 not in done:
            done.update(srv.step())
        assert srv._alloc.free_blocks == srv.num_blocks - 1
        # two admissions and two re-admissions, a draft model's rows
        # beside the target's, none of them built a launch a leaf
        assert fresh_rows.made.count(cfg) == 4
        assert fresh_rows.made.count(_dcfg()) \
            == (4 if provider == "model" else 0)
        assert 1 not in fresh_rows.eager
        np.testing.assert_array_equal(
            np.asarray(done[r0]), _solo(params, _PROMPTS[0], 12, cfg))
        np.testing.assert_array_equal(
            np.asarray(done[r1]), _solo(params, _PROMPTS[1], 10, cfg))
    finally:
        chaos.reset()


def test_spec_block_release_on_reject():
    """Paged composition invariants: every dispatch reserves worst-case
    coverage, every sync walks `_pos` back to measured acceptance
    and RELEASES the over-materialized tail (back into reservation, so
    admission accounting never drifts). With depth 1 the reconciled
    state is exact after every step."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, spec_k=4,
                            paged=True, block_size=8)
    bs = srv.block_size
    r0 = srv.admit(_PROMPTS[0], 14)
    r1 = srv.admit(_PROMPTS[2], 12)
    done = {}
    while r0 not in done or r1 not in done:
        done.update(srv.step())
        # allocator conservation: refcounted blocks + free = usable
        held = sum(1 for b in range(1, srv.num_blocks)
                   if srv._alloc.ref[b] > 0)
        assert held + srv._alloc.free_blocks == srv.num_blocks - 1
        for i, req in enumerate(srv._slots):
            if req is None:
                continue
            # depth 1: nothing in flight after step(), so the lane's
            # materialized blocks exactly cover its reconciled
            # position — the worst-case draft tail was trimmed
            want = min((int(srv._pos[i]) - 1) // bs + 1,
                       srv._lane_need[i])
            assert len(srv._lane_blocks[i]) == want, (i, want)
            # released tail went back into reservation, not thin air
            assert srv._alloc.reserved >= \
                srv._lane_need[i] - len(srv._lane_blocks[i])
    assert srv._alloc.free_blocks == srv.num_blocks - 1
    assert srv._alloc.reserved == 0
    np.testing.assert_array_equal(np.asarray(done[r0]),
                                  _solo(params, _PROMPTS[0], 14, cfg))
    np.testing.assert_array_equal(np.asarray(done[r1]),
                                  _solo(params, _PROMPTS[2], 12, cfg))


def test_spec_adaptive_k_floor():
    """The per-lane controller: a draft source that keeps missing (a
    draft MODEL from different init) drags the lane's acceptance EWMA
    under the floor and k shrinks to the 1-floor; an accepting source
    (n-gram on a looping greedy stream) holds k at spec_k. Streams
    stay bit-exact either way — k only changes the dispatch count."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    # adversarial: independently initialized draft model — its argmax
    # stream has nothing to do with the target's
    bad = ContinuousBatcher(params, cfg, max_batch=1, spec_k=4,
                            spec_accept_floor=0.6,
                            draft_params=tf.init_params(_dcfg(), seed=1),
                            draft_cfg=_dcfg())
    rid = bad.admit(_PROMPTS[0], 16)
    done = {}
    while rid not in done:
        done.update(bad.step())
    np.testing.assert_array_equal(np.asarray(done[rid]),
                                  _solo(params, _PROMPTS[0], 16, cfg))
    # the lane freed at finish (resetting _keff) — drive a second,
    # longer request and observe the shrink while it is LIVE
    rid = bad.admit(_PROMPTS[2], 20)
    shrunk = []
    while True:
        out = bad.step()
        if bad.active_count:
            shrunk.append(int(bad._keff[0]))
        if rid in out:
            break
    assert min(shrunk) == 1, shrunk          # floor reached, never 0
    assert bad.health_snapshot()["serving.spec_k_live"] == 4.0  # reset
    # accepting source: n-gram over a repetitive stream keeps k wide
    good = ContinuousBatcher(params, cfg, max_batch=1, spec_k=4,
                             spec_accept_floor=0.3)
    rid = good.admit(_PROMPTS[1], 16)
    kept = []
    while True:
        out = good.step()
        if good.active_count:
            kept.append(int(good._keff[0]))
        if rid in out:
            break
    assert max(kept) == 4, kept
    np.testing.assert_array_equal(np.asarray(out[rid]),
                                  _solo(params, _PROMPTS[1], 16, cfg))


def test_spec_env_knobs(monkeypatch):
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    monkeypatch.setenv("MXNET_SPEC_K", "3")
    monkeypatch.setenv("MXNET_SPEC_NGRAM", "4")
    monkeypatch.setenv("MXNET_SPEC_ACCEPT_FLOOR", "0.25")
    srv = ContinuousBatcher(params, cfg, max_batch=1)
    assert (srv.spec_k, srv.spec_ngram, srv.spec_accept_floor) \
        == (3, 4, 0.25)
    assert srv._spec_provider == "ngram"
    monkeypatch.delenv("MXNET_SPEC_K")
    off = ContinuousBatcher(params, cfg, max_batch=1)
    assert off.spec_k is None and not off._spec_on


def test_spec_validation():
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    with pytest.raises(ValueError, match="greedy-only"):
        ContinuousBatcher(params, cfg, spec_k=2, temperature=0.7,
                          greedy=False)
    with pytest.raises(ValueError, match="pair"):
        ContinuousBatcher(params, cfg, spec_k=2,
                          draft_params=tf.init_params(_dcfg(), seed=9))
    with pytest.raises(ValueError, match="vocab"):
        ContinuousBatcher(
            params, cfg, spec_k=2,
            draft_params=tf.init_params(_dcfg(vocab_size=31), seed=9),
            draft_cfg=_dcfg(vocab_size=31))
    with pytest.raises(ValueError, match="without spec_k"):
        ContinuousBatcher(params, cfg,
                          draft_params=tf.init_params(_dcfg(), seed=9),
                          draft_cfg=_dcfg())
    # paged + draft model: prefix sharing is refused, not corrupted
    srv = ContinuousBatcher(params, cfg, spec_k=2, paged=True,
                            block_size=8,
                            draft_params=tf.init_params(_dcfg(), seed=9),
                            draft_cfg=_dcfg())
    with pytest.raises(ValueError, match="prefix sharing"):
        srv.cache_prefix([1, 2, 3])


@pytest.mark.parametrize("paged", [False, True])
def test_spec_off_path_silence(paged):
    """spec_k unset => ZERO behavior change: identical streams AND an
    identical dispatch count to the pre-speculation batcher (the
    counter is the invariant the A/B bench divides by). One dispatch a
    step is a window of one's promise, so depth 1 is named."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    jobs = list(zip(_PROMPTS, _N_NEW))

    def drive(**kw):
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                pipeline_depth=1, **kw)
        out, order = _run_pool(srv, jobs)
        return srv, out, order

    base, out, order = drive()
    assert not base._spec_on and base._spec_provider is None
    for rid, (p, n) in zip(order, jobs):
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _solo(params, p, n, cfg))
    # the paged/dense non-spec batchers run the same one-dispatch-per-
    # step schedule — the counter itself must not care about paging
    srv2 = ContinuousBatcher(params, cfg, max_batch=2, paged=paged,
                             block_size=8 if paged else None,
                             pipeline_depth=1)
    out2, order2 = _run_pool(srv2, jobs)
    assert srv2.dispatch_count == base.dispatch_count
    for rid, (p, n) in zip(order2, jobs):
        np.testing.assert_array_equal(np.asarray(out2[rid]),
                                      _solo(params, p, n, cfg))
    # and speculation strictly REDUCES dispatches on this workload
    spec, out3, order3 = drive(spec_k=4)
    assert spec.dispatch_count < base.dispatch_count
    for rid, (p, n) in zip(order3, jobs):
        np.testing.assert_array_equal(np.asarray(out3[rid]),
                                      _solo(params, p, n, cfg))
