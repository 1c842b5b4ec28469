"""Continuous batching (models/serving.py): ragged decode + slot pool.

Reference counterpart: batch-at-a-time Module.predict serving
(/root/reference/python/mxnet/module/base_module.py:336-420); the
oracle here is the framework's own generate() — every request served
through the shared slot pool must emit exactly the tokens generate()
emits for it alone.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher, _bucket


def _cfg(**kw):
    base = dict(vocab_size=211, d_model=24, n_heads=4, n_layers=2,
                d_ff=48, max_len=64, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _prompts(rng, n, vocab=211):
    return [list(rng.randint(1, vocab, rng.randint(3, 12)))
            for _ in range(n)]


# the batcher as a user gets it (two rounds in flight) and a window of
# one on the same loop (a round's tokens in the step() that dispatched
# it), which stays an argument: both keep every case
both_loops = pytest.mark.parametrize(
    "loop", [{}, {"pipeline_depth": 1}], ids=["default", "depth1"])


def test_ragged_decode_matches_scalar():
    """decode_step with an all-equal pos vector == scalar pos."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=1)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(1, 211, (3, 7)), jnp.int32)
    cache = tf.init_cache(cfg, 3)
    logits, cache = tf.prefill(params, cache, prompt, cfg)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    l_s, c_s = tf.decode_step(params, cache, tok, 7, cfg)
    l_v, c_v = tf.decode_step(params, cache, tok,
                              jnp.full((3,), 7, jnp.int32), cfg)
    np.testing.assert_allclose(l_s, l_v, atol=1e-5)
    for a, b in zip(c_s, c_v):
        np.testing.assert_allclose(a["k"], b["k"], atol=1e-6)


@pytest.mark.parametrize("rope,kvh,flash", [
    (False, None, False), (True, 2, False), (True, 2, True)])
def test_ragged_decode_mixed_positions(rope, kvh, flash):
    """Rows at DIFFERENT positions decode exactly as if each ran in
    its own batch — across rope, GQA, and the flash-decode kernel."""
    cfg = _cfg(n_kv_heads=kvh, rope=rope, use_flash_kernel=flash,
               d_model=16, max_len=32, vocab_size=97)
    params = tf.init_params(cfg, seed=1)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(1, 97, (3, 8)), jnp.int32)
    cache = tf.init_cache(cfg, 3)
    logits, cache = tf.prefill(params, cache, prompt, cfg)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    rows = []
    for i in range(3):                  # advance row i to position 8+i
        ci = jax.tree.map(lambda x: x[i:i + 1], cache)
        ti, p = tok[i:i + 1], 8
        for _ in range(i):
            li, ci = tf.decode_step(params, ci, ti, p, cfg)
            ti = jnp.argmax(li, -1).astype(jnp.int32)
            p += 1
        rows.append((ci, ti, p))
    rag_cache = jax.tree.map(lambda *r: jnp.concatenate(r),
                             *[c for c, _, _ in rows])
    rag_tok = jnp.concatenate([t for _, t, _ in rows])
    rag_pos = jnp.asarray([p for _, _, p in rows], jnp.int32)
    l_r, _ = tf.decode_step(params, rag_cache, rag_tok, rag_pos, cfg)
    for i, (ci, ti, p) in enumerate(rows):
        l_i, _ = tf.decode_step(params, ci, ti, p, cfg)
        np.testing.assert_allclose(l_r[i], l_i[0], atol=1e-4)


def test_bucket():
    assert [_bucket(n) for n in (1, 8, 9, 16, 17)] == [8, 8, 16, 16, 32]


@both_loops
def test_batcher_matches_generate(loop):
    """Mixed-length requests served through the shared pool emit
    exactly generate()'s greedy tokens for each request alone."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(1)
    jobs = [(p, int(rng.randint(1, 10)))
            for p in _prompts(rng, 6)]
    srv = ContinuousBatcher(params, cfg, max_batch=3, **loop)
    results, order = srv.run(jobs)
    assert len(results) == len(jobs) and len(order) == len(jobs)
    # admission is FIFO, so rid i corresponds to jobs[i]
    for rid, (prompt, n_new) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n_new, cfg)
        np.testing.assert_array_equal(
            np.asarray(results[rid]), np.asarray(want[0]),
            err_msg="request %d (len %d, n_new %d)"
                    % (rid, len(prompt), n_new))


@both_loops
def test_batcher_slot_reuse_no_contamination(loop):
    """A slot retired and re-admitted must not leak the previous
    occupant's cache: serve two waves through ONE slot."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=5)
    rng = np.random.RandomState(2)
    srv = ContinuousBatcher(params, cfg, max_batch=1, **loop)
    for prompt in _prompts(rng, 3):
        rid = srv.admit(prompt, 6)
        assert rid is not None
        assert srv.admit([1, 2], 2) is None     # pool is full
        out = {}
        while rid not in out:
            out.update(srv.step())
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           6, cfg)
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      np.asarray(want[0]))


@both_loops
def test_batcher_mid_stream_admission(loop):
    """Admitting while another request is mid-decode leaves the running
    request's stream untouched."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=7)
    rng = np.random.RandomState(3)
    p1, p2 = _prompts(rng, 2)
    srv = ContinuousBatcher(params, cfg, max_batch=2, **loop)
    r1 = srv.admit(p1, 8)
    done = {}
    done.update(srv.step())
    done.update(srv.step())             # r1 two tokens into decode
    r2 = srv.admit(p2, 4)               # joins mid-stream
    while r1 not in done or r2 not in done:
        done.update(srv.step())
    for rid, prompt, n in ((r1, p1, 8), (r2, p2, 4)):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      np.asarray(want[0]))


def test_batcher_int8_weights():
    """Weight-only int8 trees serve through the pool unchanged."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=9)
    q8 = tf.quantize_weights_int8(params)
    rng = np.random.RandomState(4)
    prompt = _prompts(rng, 1)[0]
    srv = ContinuousBatcher(q8, cfg, max_batch=2)
    results, order = srv.run([(prompt, 5)])
    want = tf.generate(q8, jnp.asarray([prompt], jnp.int32), 5, cfg)
    np.testing.assert_array_equal(np.asarray(results[order[0]]),
                                  np.asarray(want[0]))


@both_loops
def test_batcher_sampling_matches_generate(loop):
    """Pool-level temperature/top-k sampling with per-request seeds:
    each request's stream equals its solo generate(seed=...) run —
    slot placement and pool mix must not perturb the key chain."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=17)
    rng = np.random.RandomState(6)
    jobs = [(p, int(rng.randint(2, 8)), 100 + i)
            for i, p in enumerate(_prompts(rng, 5))]
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            temperature=0.8, top_k=20, **loop)
    results, order = srv.run(jobs)
    for rid, (prompt, n_new, seed) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n_new, cfg, temperature=0.8, top_k=20,
                           seed=seed)
        np.testing.assert_array_equal(
            np.asarray(results[rid]), np.asarray(want[0]),
            err_msg="request %d seed %d" % (rid, seed))


def test_batcher_pure_ancestral_sampling():
    """greedy=False with default controls = unmodified softmax
    sampling (temperature=1.0 alone would read as greedy), matching
    generate(greedy=False, seed=...)."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=19)
    prompt = [4, 11, 7]
    srv = ContinuousBatcher(params, cfg, max_batch=2, greedy=False)
    results, order = srv.run([(prompt, 5, 42)])
    want = tf.generate(params, jnp.asarray([prompt], jnp.int32), 5,
                       cfg, greedy=False, seed=42)
    np.testing.assert_array_equal(np.asarray(results[order[0]]),
                                  np.asarray(want[0]))
    with pytest.raises(ValueError):
        ContinuousBatcher(params, cfg, greedy=True, top_k=5)


def test_bucket_clamped_to_max_len():
    """A prompt whose power-of-two bucket exceeds max_len must prefill
    at max_len width, not crash the cache update (max_len=96, t_p=70
    -> bucket 128 > 96)."""
    cfg = _cfg(max_len=96)
    params = tf.init_params(cfg, seed=13)
    prompt = list(np.random.RandomState(0).randint(1, 211, 70))
    srv = ContinuousBatcher(params, cfg, max_batch=1)
    results, order = srv.run([(prompt, 3)])
    want = tf.generate(params, jnp.asarray([prompt], jnp.int32), 3, cfg)
    np.testing.assert_array_equal(np.asarray(results[order[0]]),
                                  np.asarray(want[0]))


def test_admit_validation():
    cfg = _cfg()
    params = tf.init_params(cfg, seed=11)
    srv = ContinuousBatcher(params, cfg, max_batch=1)
    with pytest.raises(ValueError):
        srv.admit([], 4)
    with pytest.raises(ValueError):
        srv.admit([1, 2], 0)
    with pytest.raises(ValueError):
        srv.admit(list(range(1, 60)), 30)    # exceeds max_len


@both_loops
def test_cancel_mid_decode_frees_slot_without_perturbing_others(loop):
    """Evict one request mid-decode: its slot frees for the next
    admission and the surviving lane's stream stays exactly
    generate()'s."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=21)
    rng = np.random.RandomState(7)
    p1, p2, p3 = _prompts(rng, 3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, **loop)
    r1 = srv.admit(p1, 12)
    r2 = srv.admit(p2, 12)
    assert not srv.has_capacity
    done = {}
    done.update(srv.step())
    done.update(srv.step())             # both two tokens into decode
    partial = srv.cancel(r1)            # evict mid-decode
    assert partial is not None and len(partial) == len(p1) + 3
    assert srv.cancel(r1) is None       # double-cancel is a no-op
    assert srv.has_capacity
    r3 = srv.admit(p3, 5)               # reuses the evicted slot
    assert r3 is not None
    while r2 not in done or r3 not in done:
        done.update(srv.step())
    for rid, prompt, n in ((r2, p2, 12), (r3, p3, 5)):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      np.asarray(want[0]))
    # the canceled request's emitted prefix matches its solo run too
    want1 = tf.generate(params, jnp.asarray([p1], jnp.int32), 12, cfg)
    np.testing.assert_array_equal(np.asarray(partial),
                                  np.asarray(want1[0][:len(partial)]))


def test_ragged_lengths_at_bucket_boundaries():
    """Prompt lengths straddling every bucket edge (7/8/9, 15/16/17,
    31/32/33) served together in one pool — each must match its solo
    generate() despite hitting different compiled prefill widths."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=23)
    rng = np.random.RandomState(8)
    lens = [7, 8, 9, 15, 16, 17, 31, 32, 33]
    jobs = [(list(rng.randint(1, 211, L)), 4) for L in lens]
    srv = ContinuousBatcher(params, cfg, max_batch=4)
    results, order = srv.run(jobs)
    assert len(results) == len(jobs)
    for rid, (prompt, n) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(
            np.asarray(results[rid]), np.asarray(want[0]),
            err_msg="prompt len %d" % len(prompt))


def test_decode_to_max_len_boundary():
    """A request sized to land its final token exactly at max_len
    (t_p + n_new == max_len) next to a short request — the cache's
    last position is written, never overrun."""
    cfg = _cfg(max_len=32)
    params = tf.init_params(cfg, seed=25)
    rng = np.random.RandomState(9)
    long_p = list(rng.randint(1, 211, 20))
    short_p = list(rng.randint(1, 211, 4))
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    results, order = srv.run([(long_p, 12), (short_p, 3)])
    for rid, (prompt, n) in zip(order, [(long_p, 12), (short_p, 3)]):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      np.asarray(want[0]))


@both_loops
def test_churn_fuzz_admit_cancel_step(loop):
    """Randomized churn: interleaved admits, cancels, and steps over a
    seeded schedule. Every COMPLETED stream must equal its solo
    generate() run; every canceled stream must be a prefix of its solo
    run; the pool must end drained."""
    cfg = _cfg(max_len=48)
    params = tf.init_params(cfg, seed=27)
    rng = np.random.RandomState(10)
    srv = ContinuousBatcher(params, cfg, max_batch=3, **loop)
    spec = {}              # rid -> (prompt, n_new)
    done, canceled = {}, {}
    pending = [(list(rng.randint(1, 211, rng.randint(3, 20))),
                int(rng.randint(1, 12))) for _ in range(12)]
    live = []
    while pending or live:
        action = rng.randint(0, 4)
        if action == 0 and pending and srv.has_capacity:
            prompt, n = pending.pop()
            rid = srv.admit(prompt, n)
            assert rid is not None
            spec[rid] = (prompt, n)
            live.append(rid)
        elif action == 1 and live and rng.rand() < 0.3:
            rid = live[rng.randint(len(live))]
            out = srv.cancel(rid)
            assert out is not None
            canceled[rid] = out
            live.remove(rid)
        else:
            finished = srv.step()
            for rid in finished:
                done[rid] = finished[rid]
                live.remove(rid)
    assert srv.active_count == 0
    assert set(done) | set(canceled) == set(spec)
    for rid, (prompt, n) in spec.items():
        want = np.asarray(tf.generate(
            params, jnp.asarray([prompt], jnp.int32), n, cfg)[0])
        if rid in done:
            np.testing.assert_array_equal(np.asarray(done[rid]), want,
                                          err_msg="rid %d" % rid)
        else:
            got = np.asarray(canceled[rid])
            np.testing.assert_array_equal(got, want[:len(got)],
                                          err_msg="rid %d" % rid)


def test_stream_yields_run_streams_incrementally():
    """stream() must emit exactly run()'s per-request token streams,
    one (rid, token, done) at a time, with done marking the final
    token of each request."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=29)
    rng = np.random.RandomState(11)
    jobs = [(p, int(rng.randint(2, 9))) for p in _prompts(rng, 5)]
    want, order = ContinuousBatcher(params, cfg, max_batch=2).run(jobs)

    srv = ContinuousBatcher(params, cfg, max_batch=2)
    got, done_marks = {}, {}
    for rid, token, done in srv.stream(jobs):
        got.setdefault(rid, []).append(token)
        assert rid not in done_marks, "token after done for rid %d" % rid
        if done:
            done_marks[rid] = True
    assert set(got) == set(want)
    for rid, (prompt, n) in zip(order, jobs):
        assert rid in done_marks
        # run() returns prompt + generated; stream yields generated only
        np.testing.assert_array_equal(got[rid], want[rid][len(prompt):])


@both_loops
def test_stop_token_ends_request_early(loop):
    """A request whose stream hits its stop token finishes early (stop
    token included), freeing the slot; its output equals the solo
    generate() prefix through the stop token."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=31)
    rng = np.random.RandomState(12)
    prompt = _prompts(rng, 1)[0]
    solo = np.asarray(tf.generate(
        params, jnp.asarray([prompt], jnp.int32), 10, cfg)[0])
    generated = solo[len(prompt):]
    stop = int(generated[4])                 # stop mid-stream
    if any(int(t) == stop for t in generated[:4]):
        stop = int(generated[2])             # pick an earlier unique one
    cut = next(i for i, t in enumerate(generated) if int(t) == stop)

    srv = ContinuousBatcher(params, cfg, max_batch=1, **loop)
    results, order = srv.run([(prompt, 10, 0, stop)])
    out = results[order[0]]
    np.testing.assert_array_equal(out, solo[:len(prompt) + cut + 1])
    assert out[-1] == stop
    assert srv.active_count == 0             # slot freed for reuse
    # and a stop token that never fires changes nothing
    results2, order2 = srv.run([(prompt, 10, 0, -1)])
    np.testing.assert_array_equal(results2[order2[0]], solo)


def test_stream_emits_terminal_event_for_cancel():
    """cancel() between stream() yields must still produce a terminal
    (rid, None, True) event so consumers keyed on `done` clean up."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=33)
    rng = np.random.RandomState(13)
    p1, p2 = _prompts(rng, 2)
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    seen, canceled_rid = {}, None
    stream = srv.stream([(p1, 10), (p2, 4)])
    for rid, token, done in stream:
        seen.setdefault(rid, []).append((token, done))
        if canceled_rid is None and len(seen.get(rid, [])) == 2:
            canceled_rid = rid
            assert srv.cancel(rid) is not None
    assert canceled_rid is not None
    tokens, dones = zip(*seen[canceled_rid])
    assert tokens[-1] is None and dones[-1] is True
    assert all(t is not None for t in tokens[:-1])
    other = next(r for r in seen if r != canceled_rid)
    assert seen[other][-1][1] is True and seen[other][-1][0] is not None
    assert srv.active_count == 0


@both_loops
@pytest.mark.parametrize("chunk", [2, 4, 7])
def test_chunked_pool_matches_generate(chunk, loop):
    """Multi-step scheduling (chunk_size=k) emits exactly the same
    per-request greedy streams as chunk_size=1 and as solo generate(),
    including requests whose budget or stop token lands mid-chunk."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(7)
    jobs = [(p, int(rng.randint(1, 12))) for p in _prompts(rng, 6)]
    srv = ContinuousBatcher(params, cfg, max_batch=3, chunk_size=chunk,
                            **loop)
    results, order = srv.run(jobs)
    assert len(results) == len(jobs)
    for rid, (prompt, n_new) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n_new, cfg)
        np.testing.assert_array_equal(
            np.asarray(results[rid]), np.asarray(want[0]),
            err_msg="chunk %d request %d" % (chunk, rid))


def test_chunked_pool_sampling_matches_unchunked():
    """The per-row key chain is chunk-invariant: a sampled request's
    stream is identical at chunk_size 1 and 4 (and therefore to its
    solo generate(seed) run, which chunk_size=1 is tested against)."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=5)
    rng = np.random.RandomState(11)
    jobs = [(p, int(rng.randint(2, 10)), int(rng.randint(0, 99)))
            for p in _prompts(rng, 5)]
    out = {}
    for chunk in (1, 4):
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                temperature=0.7, top_k=13,
                                chunk_size=chunk)
        results, order = srv.run(jobs)
        out[chunk] = [results[rid] for rid in order]
    for a, b in zip(out[1], out[4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_stop_token_and_stream_events():
    """stop_token ends a request mid-chunk (tail discarded); stream()
    yields every chunk token individually with done on the last."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    prompt = [5, 9, 2]
    ref = [int(t) for t in np.asarray(
        tf.generate(params, jnp.asarray([prompt], jnp.int32), 12,
                    cfg)[0])][len(prompt):]
    stop = ref[5]          # force an early stop mid-stream
    want = ref[:ref.index(stop) + 1]       # up to and incl. the stop
    srv = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=4)
    events = list(srv.stream([(prompt, 12, 0, stop)]))
    toks = [t for _, t, _ in events]
    dones = [d for _, _, d in events]
    assert toks == want
    assert dones == [False] * (len(want) - 1) + [True]
    # same through run()
    srv2 = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=4)
    results, order = srv2.run([(prompt, 12, 0, stop)])
    assert results[order[0]][len(prompt):] == want


@both_loops
def test_chunked_churn_matches_oracle(loop):
    """Randomized admit/cancel/step churn on a chunked pool: every
    completed request still equals its solo generate() prefix."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=13)
    rng = np.random.RandomState(23)
    srv = ContinuousBatcher(params, cfg, max_batch=3, chunk_size=3,
                            **loop)
    jobs = {}
    done = {}
    rid_job = {}
    pending = [(p, int(rng.randint(1, 14))) for p in _prompts(rng, 8)]
    while pending or srv.active_count:
        act = rng.randint(0, 3)
        if act == 0 and pending and srv.has_capacity:
            job = pending.pop()
            rid = srv.admit(job[0], job[1])
            rid_job[rid] = job
        elif act == 1 and srv.active_count and rng.rand() < 0.3:
            live = [r.rid for r in srv._slots if r is not None]
            rid = live[rng.randint(len(live))]
            srv.cancel(rid)
            rid_job.pop(rid, None)      # canceled: no oracle check
        else:
            done.update(srv.step())
    for rid, toks in done.items():
        if rid not in rid_job:
            continue
        prompt, n_new = rid_job[rid]
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n_new, cfg)
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(want[0]))


@pytest.mark.parametrize("kw", [
    dict(), dict(pipeline_depth=2), dict(pipeline_depth=3),
    dict(pipeline_depth=2, chunk_size=3)],
    ids=["default", "depth2", "depth3", "depth2-chunk3"])
def test_pipelined_matches_sync_and_generate(kw):
    """Chunk pipelining (pipeline_depth>1; a default-constructed
    batcher runs it at depth 2) emits BIT-IDENTICAL greedy streams to
    a window of one and to solo generate(), across depths and
    chunk sizes — the depth>1 vs depth=1 identity contract."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(1)
    jobs = [(p, int(rng.randint(1, 10))) for p in _prompts(rng, 6)]
    sync, order_s = ContinuousBatcher(
        params, cfg, max_batch=3, **dict(kw, pipeline_depth=1)).run(jobs)
    srv = ContinuousBatcher(params, cfg, max_batch=3, **kw)
    assert srv.pipeline_depth == kw.get("pipeline_depth", 2)
    pipe, order_p = srv.run(jobs)
    assert len(pipe) == len(jobs)
    for rs, rp, (prompt, n_new) in zip(order_s, order_p, jobs):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n_new, cfg)
        np.testing.assert_array_equal(
            np.asarray(pipe[rp]), np.asarray(want[0]), err_msg=str(kw))
        assert sync[rs] == pipe[rp]


def test_pipelined_sampling_bit_identical():
    """The per-row key chain survives pipelining: sampled streams are
    identical at depth 1 and depth 2 (and therefore to solo
    generate(seed), which depth 1 is tested against)."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=17)
    rng = np.random.RandomState(6)
    jobs = [(p, int(rng.randint(2, 8)), 100 + i)
            for i, p in enumerate(_prompts(rng, 5))]
    out = {}
    for depth in (1, 2):
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                temperature=0.8, top_k=20,
                                pipeline_depth=depth)
        results, order = srv.run(jobs)
        out[depth] = [results[rid] for rid in order]
    for a, b in zip(out[1], out[2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipelined_admission_staleness():
    """A request admitted while chunks are in flight enters at the
    NEXT dispatch boundary — the in-flight chunks keep decoding the
    lane's previous occupant and none of their emissions leak into the
    new stream, which stays bit-exact vs solo generate()."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=7)
    rng = np.random.RandomState(3)
    p1, p2 = _prompts(rng, 2)
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=3)
    r1 = srv.admit(p1, 10)
    done = {}
    done.update(srv.step())             # window fills to depth 3
    assert len(srv._inflight) > 0
    r2 = srv.admit(p2, 5)               # admitted MID-FLIGHT
    # the staleness rule, observable: no chunk already in flight may
    # carry the new request's lane identity
    assert all(r2 not in rec[1] for rec in srv._inflight)
    while r1 not in done or r2 not in done:
        done.update(srv.step())
    for rid, prompt, n in ((r1, p1, 10), (r2, p2, 5)):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      np.asarray(want[0]))


def test_pipelined_mid_flight_eviction():
    """cancel() with chunks in flight: the canceled stream is a prefix
    of its solo run (in-flight emissions discarded by rid identity),
    the slot frees for a new admission whose stream is exact, and the
    surviving lane is untouched."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=21)
    rng = np.random.RandomState(7)
    p1, p2, p3 = _prompts(rng, 3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=2)
    r1 = srv.admit(p1, 12)
    r2 = srv.admit(p2, 12)
    done = {}
    done.update(srv.step())
    done.update(srv.step())
    assert len(srv._inflight) > 0       # eviction happens mid-flight
    partial = srv.cancel(r1)
    assert partial is not None
    assert srv.cancel(r1) is None       # double-cancel is a no-op
    r3 = srv.admit(p3, 5)               # reuses the evicted slot
    assert r3 is not None
    while r2 not in done or r3 not in done:
        done.update(srv.step())
    for rid, prompt, n in ((r2, p2, 12), (r3, p3, 5)):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                           n, cfg)
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      np.asarray(want[0]))
    want1 = np.asarray(tf.generate(
        params, jnp.asarray([p1], jnp.int32), 12, cfg)[0])
    np.testing.assert_array_equal(np.asarray(partial),
                                  want1[:len(partial)])


def test_pipelined_stream_stop_token_and_churn():
    """stream() + stop tokens + randomized churn on a pipelined pool:
    completed streams equal the solo oracle, canceled streams are
    prefixes, stop tokens end requests with in-chunk tails discarded,
    and the pool drains."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    prompt = [5, 9, 2]
    ref = [int(t) for t in np.asarray(
        tf.generate(params, jnp.asarray([prompt], jnp.int32), 12,
                    cfg)[0])][len(prompt):]
    stop = ref[5]
    want = ref[:ref.index(stop) + 1]
    srv = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=4,
                            pipeline_depth=2)
    events = list(srv.stream([(prompt, 12, 0, stop)]))
    assert [t for _, t, _ in events] == want
    assert [d for _, _, d in events] == \
        [False] * (len(want) - 1) + [True]
    # churn: admit/cancel/step interleaved on a deeper pipeline
    rng = np.random.RandomState(10)
    srv = ContinuousBatcher(params, cfg, max_batch=3, pipeline_depth=3)
    spec, done, canceled, live = {}, {}, {}, []
    pending = [(list(rng.randint(1, 211, rng.randint(3, 20))),
                int(rng.randint(1, 12))) for _ in range(10)]
    while pending or live:
        action = rng.randint(0, 4)
        if action == 0 and pending and srv.has_capacity:
            prompt, n = pending.pop()
            rid = srv.admit(prompt, n)
            spec[rid] = (prompt, n)
            live.append(rid)
        elif action == 1 and live and rng.rand() < 0.3:
            rid = live[rng.randint(len(live))]
            canceled[rid] = srv.cancel(rid)
            live.remove(rid)
        else:
            for rid, toks in srv.step().items():
                done[rid] = toks
                live.remove(rid)
    assert srv.active_count == 0
    assert set(done) | set(canceled) == set(spec)
    for rid, (prompt, n) in spec.items():
        want = np.asarray(tf.generate(
            params, jnp.asarray([prompt], jnp.int32), n, cfg)[0])
        got = np.asarray(done.get(rid, canceled.get(rid)))
        np.testing.assert_array_equal(got, want[:len(got)],
                                      err_msg="rid %d" % rid)
        if rid in done:
            assert len(got) == len(want)


def test_pipelined_prefix_cache_streams_exact():
    """Prefix-cached admissions (suffix-only prefill, incl. the
    exact-match fast path) compose with pipelining: streams equal solo
    generate() under greedy and sampled chains."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    system = [7, 3, 9, 1, 4]
    jobs = [(system + [11, 22], 8), ([5, 6], 6), (system, 5)]
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=2)
    srv.cache_prefix(system)
    results, order = srv.run(jobs)
    for rid, (p, n) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([p], jnp.int32), n, cfg)
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      np.asarray(want[0]))
    srv2 = ContinuousBatcher(params, cfg, max_batch=2, temperature=0.7,
                             top_k=13, pipeline_depth=2)
    srv2.cache_prefix([2, 4, 6, 8])
    rid = srv2.admit([2, 4, 6, 8], 5, seed=9)   # exact-match admission
    out = {}
    while srv2.active_count:
        out.update(srv2.step())
    want = tf.generate(params, jnp.asarray([[2, 4, 6, 8]], jnp.int32),
                       5, cfg, temperature=0.7, top_k=13, seed=9)
    np.testing.assert_array_equal(np.asarray(out[rid]),
                                  np.asarray(want[0]))


def test_pipelined_obs_spans_and_zero_when_off():
    """With telemetry on, the pipelined pool records dispatch/sync/
    patch spans and depth/occupancy gauges; with it off, a serving run
    leaves the ring untouched (the one-guarded-branch contract)."""
    from mxnet_tpu.observability import core as obs
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    jobs = [([4, 7, 2], 4), ([9, 1], 3)]
    obs.reset()
    obs.set_enabled(False)
    try:
        ContinuousBatcher(params, cfg, max_batch=2,
                          pipeline_depth=2).run(jobs)
        assert obs.records() == [] and obs.counters() == {}
        obs.set_enabled(True)
        ContinuousBatcher(params, cfg, max_batch=2,
                          pipeline_depth=2).run(jobs)
        names = {r[1] for r in obs.records()}
        for needed in ("serving.dispatch", "serving.sync",
                       "serving.patch", "serving.inflight_depth",
                       "serving.lane_occupancy"):
            assert needed in names, needed
        from mxnet_tpu.observability import histogram as obs_h
        assert "serving.ttft_ms" in obs_h.histograms()
    finally:
        obs.set_enabled(None)
        obs.reset()
    with pytest.raises(ValueError):
        ContinuousBatcher(params, cfg, pipeline_depth=0)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_dispatch_counters_say_how_many_ran_ahead(depth):
    """While spans record, serving.dispatches counts every chunk
    dispatch and serving.dispatch_ahead those issued while an older
    chunk was still unsynced: every one but the first after a drained
    window when pipelined, none at a window of one."""
    from mxnet_tpu.observability import core as obs
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    obs.reset()
    obs.set_enabled(True)
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                pipeline_depth=depth)
        srv.run([([4, 7, 2], 6)])
        srv.run([([9, 1], 4)])          # the window drained in between
        ahead = obs.counter("serving.dispatch_ahead").value
        assert obs.counter("serving.dispatches").value \
            == srv.dispatch_count
        if depth == 1:
            # one dispatch a decode round, and none runs ahead
            assert (srv.dispatch_count, ahead) == (5 + 3, 0)
        else:
            assert ahead == srv.dispatch_count - 2
    finally:
        obs.set_enabled(None)
        obs.reset()


@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_window_of_one_returns_a_rounds_tokens_in_its_step(paged, chunk):
    """pipeline_depth=1 is a window of one on the one loop: every
    step() dispatches a chunk against the device-resident carry and
    syncs it before it returns, so nothing stays in flight and each
    live request grew by chunk_size tokens, or finished."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    kw = dict(paged=True, block_size=8) if paged else {}
    srv = ContinuousBatcher(params, cfg, max_batch=3, chunk_size=chunk,
                            pipeline_depth=1, **kw)
    jobs = [([4, 7, 2], 9), ([9, 1, 5, 3], 4), ([8, 8], 7)]
    rids = [srv.admit(p, n) for p, n in jobs]
    done, rounds = {}, 0
    while srv.active_count:
        live = {r.rid: len(r.tokens) for r in srv._slots if r is not None}
        out = srv.step()
        rounds += 1
        assert len(srv._inflight) == 0
        assert srv.dispatch_count == rounds
        now = {r.rid: len(r.tokens) for r in srv._slots if r is not None}
        assert set(live) == set(now) | set(out)
        for rid, n in live.items():
            assert rid in out or now[rid] == n + chunk
        done.update(out)
    for rid, (p, n) in zip(rids, jobs):
        want = tf.generate(params, jnp.asarray([p], jnp.int32), n, cfg)
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      np.asarray(want[0]))


@pytest.mark.parametrize("controls", [
    {}, dict(greedy=False, temperature=0.8, top_k=5)],
    ids=["greedy", "sampled"])
def test_every_depth_shares_one_decode_program(controls):
    """pipeline_depth is the width of one loop's window, not the choice
    of a loop: batchers of one configuration at depths 1, 2 and 3 hold
    the same compiled decode program, and the module has no other."""
    from mxnet_tpu.models import serving
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    fns = [ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=d,
                             **controls)._pipe_fn for d in (1, 2, 3)]
    assert fns[0] is fns[1] is fns[2]
    for gone in ("_jitted_ragged_step", "_jitted_ragged_chunk"):
        assert not hasattr(serving, gone)
    assert not hasattr(ContinuousBatcher, "_step_sync")


@both_loops
def test_prefix_cache_streams_equal_no_prefix(loop):
    """Shared-prefix admission (suffix-only prefill) emits the same
    streams as the pool without prefix caching and as solo
    generate() — greedy, mixed prefix/non-prefix prompts, slot
    reuse after the prefix entries."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    system = [7, 3, 9, 1, 4]                     # the shared preamble
    jobs = [(system + [11, 22], 8), ([5, 6], 6),
            (system + [33], 9), (system, 5)]     # incl. exact match
    srv = ContinuousBatcher(params, cfg, max_batch=2, **loop)
    assert srv.cache_prefix(system) == len(system)
    results, order = srv.run(jobs)
    for rid, (p, n) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([p], jnp.int32), n, cfg)
        np.testing.assert_array_equal(
            np.asarray(results[rid]), np.asarray(want[0]),
            err_msg="prefix-cached request %d" % rid)


def test_prefix_cache_lru_and_validation():
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            prefix_cache_slots=2)
    srv.cache_prefix([1, 2])
    srv.cache_prefix([3, 4])
    srv.cache_prefix([1, 2])        # refresh: [3,4] is now oldest
    srv.cache_prefix([5, 6])        # evicts [3,4]
    assert set(srv._prefix_cache) == {(1, 2), (5, 6)}
    with pytest.raises(ValueError):
        srv.cache_prefix([])
    with pytest.raises(ValueError):
        srv.cache_prefix(list(range(cfg.max_len)))
    off = ContinuousBatcher(params, cfg, max_batch=2,
                            prefix_cache_slots=0)
    with pytest.raises(ValueError):
        off.cache_prefix([1])


def test_prefix_cache_longest_match_and_sampling():
    """Two nested cached prefixes: admission uses the longest; the
    sampled per-request chain is unchanged by prefix reuse."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=5)
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            temperature=0.7, top_k=13)
    srv.cache_prefix([2, 4])
    srv.cache_prefix([2, 4, 6, 8])
    prompt = [2, 4, 6, 8, 10]
    p_len, _, _ = srv._lookup_prefix(prompt)
    assert p_len == 4
    rid = srv.admit(prompt, 7, seed=42)
    # exact-match admission under sampling too: the whole prompt IS a
    # cached prefix, so the first token comes from the stored logits —
    # the key chain must be identical to solo generate(seed=...)
    rid2 = srv.admit([2, 4, 6, 8], 5, seed=9)
    out = {}
    while srv.active_count:
        out.update(srv.step())
    want = tf.generate(params, jnp.asarray([prompt], jnp.int32), 7,
                       cfg, temperature=0.7, top_k=13, seed=42)
    np.testing.assert_array_equal(np.asarray(out[rid]),
                                  np.asarray(want[0]))
    want2 = tf.generate(params, jnp.asarray([[2, 4, 6, 8]], jnp.int32),
                        5, cfg, temperature=0.7, top_k=13, seed=9)
    np.testing.assert_array_equal(np.asarray(out[rid2]),
                                  np.asarray(want2[0]))


# ---- the fresh row: one launch an admission ---------------------------
# An admission that finds no cached prefix starts from a zeroed one-lane
# row. Eagerly that is a launch a leaf; the batcher takes it from ONE
# small jitted program (serving._jitted_fresh_row), whatever state the
# model's layers keep.

_ROW_CFGS = {
    "attention": dict(),
    "int8": dict(kv_cache_int8=True),
    "mamba": dict(n_kv_heads=1, n_layers=3,
                  layer_kinds=("mamba", "attention", "mamba"),
                  ffn="gated_silu", positions="none", ssm_state=8,
                  ssm_dt_rank=4),
    "kda-mla": dict(n_layers=2, layer_kinds=("kda", "mla"),
                    positions="none", kda_heads=4, kda_head_dim=8,
                    kda_conv=4, mla_rank=16, mla_nope_dim=8,
                    mla_rope_dim=4, mla_v_dim=8),
}
row_kinds = pytest.mark.parametrize("kind",
                                    ["attention", "mamba", "kda-mla"])


@pytest.mark.parametrize("kind", sorted(_ROW_CFGS))
def test_the_rows_program_makes_init_caches_row(kind):
    """Leaf by leaf the same tree, shapes, dtypes and zeros, whatever
    dtype the cache states (bf16 rows beside float32 state and int8
    codes beside their float32 scales)."""
    from mxnet_tpu.models.serving import _jitted_fresh_row
    cfg = _cfg(dtype=jnp.bfloat16, **_ROW_CFGS[kind])
    want = tf.init_cache(cfg, 1)
    make = _jitted_fresh_row(cfg)
    assert _jitted_fresh_row(_cfg(dtype=jnp.bfloat16,
                                  **_ROW_CFGS[kind])) is make
    got = make()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert g.shape[0] == 1 and not np.asarray(g, np.float32).any()
    # every leaf of every row is a buffer of its own, though all the
    # zeros of a shape are one value to the compiler
    leaves = jax.tree.leaves([got, make()])
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == len(leaves)


@row_kinds
def test_five_admissions_trace_the_row_once_and_launch_it_five_times(
        kind, fresh_rows):
    from mxnet_tpu.observability import core as obs
    cfg = _cfg(**_ROW_CFGS[kind])
    params = tf.init_params(cfg, seed=3)
    jobs = [(p, 4) for p in _prompts(np.random.RandomState(5), 5)]
    obs.reset()
    obs.set_enabled(True)
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2)
        built, traced = list(fresh_rows.eager), fresh_rows.traced
        results, order = srv.run(jobs)
    finally:
        obs.set_enabled(None)
        obs.reset()
    # init_cache's body ran once more, under the program's trace, and
    # never eagerly: a launch a row, not a launch a leaf
    assert fresh_rows.traced - traced == 1
    assert fresh_rows.eager == built == [2]
    assert len(fresh_rows.made) == 5
    for rid, (p, n) in zip(order, jobs):
        want = tf.generate(params, jnp.asarray([p], jnp.int32), n, cfg)
        np.testing.assert_array_equal(np.asarray(results[rid]),
                                      np.asarray(want[0]))


@row_kinds
def test_an_admission_from_a_cached_prefix_needs_no_fresh_row(
        kind, fresh_rows):
    """cache_prefix starts from one; the admissions that continue from
    its row make none, a miss makes its own."""
    cfg = _cfg(**_ROW_CFGS[kind])
    params = tf.init_params(cfg, seed=3)
    system = [7, 3, 9, 1, 4]
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.cache_prefix(system)
    assert len(fresh_rows.made) == 1
    srv.admit(system + [11, 22], 3)
    srv.admit(system, 3)
    assert len(fresh_rows.made) == 1
    while srv.active_count:
        srv.step()
    srv.admit([5, 6], 3)
    assert len(fresh_rows.made) == 2 and 1 not in fresh_rows.eager
