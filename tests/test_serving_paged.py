"""Paged KV cache (models/serving.py paged=True): block pool + block
tables + refcounted prefix sharing.

The oracle stays the framework's own generate(): every stream through
the paged batcher must be BIT-exact vs its solo run — the gathered
block view feeds the identical attention contraction, so this is an
equality contract, not a tolerance. The allocator invariants (blocks
accounted at admission, lazily allocated, refcounted on sharing,
returned at refcount zero) are asserted directly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import serving
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import BlockAllocator, ContinuousBatcher
from mxnet_tpu.observability import chaos


def _cfg(**kw):
    base = dict(vocab_size=211, d_model=24, n_heads=4, n_layers=2,
                d_ff=48, max_len=64, dtype=jnp.float32)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _prompts(rng, n, vocab=211):
    return [list(rng.randint(1, vocab, rng.randint(3, 12)))
            for _ in range(n)]


def _solo(params, prompt, n, cfg, **kw):
    return np.asarray(tf.generate(params, jnp.asarray([prompt],
                                                      jnp.int32),
                                  n, cfg, **kw)[0])


@pytest.mark.parametrize("kw", [
    dict(), dict(chunk_size=3), dict(pipeline_depth=2),
    dict(pipeline_depth=2, chunk_size=3), dict(pipeline_depth=1),
    dict(pipeline_depth=1, chunk_size=3)])
def test_paged_streams_bit_exact(kw, fresh_rows):
    """Greedy streams through the paged pool == solo generate(), in
    a window of one, chunked, and pipelined scheduling — and the pool drains back
    to every block free with zero reservation. Each admission's row is
    one launch of the row's program."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(1)
    jobs = [(p, int(rng.randint(1, 10))) for p in _prompts(rng, 6)]
    srv = ContinuousBatcher(params, cfg, max_batch=3, paged=True,
                            block_size=8, **kw)
    results, order = srv.run(jobs)
    assert len(results) == len(jobs)
    assert fresh_rows.made == [cfg] * len(jobs) and not fresh_rows.eager
    for rid, (prompt, n_new) in zip(order, jobs):
        np.testing.assert_array_equal(
            np.asarray(results[rid]), _solo(params, prompt, n_new, cfg),
            err_msg="paged %s rid %d" % (kw, rid))
    assert srv._alloc.free_blocks == srv.num_blocks - 1
    assert srv._alloc.reserved == 0
    assert all(int(r) == 0 for r in srv._alloc.ref[1:])


def test_paged_sampled_streams_bit_exact():
    """Per-request sampled key chains survive the block pool: streams
    equal solo generate(seed=...) exactly."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=17)
    rng = np.random.RandomState(6)
    jobs = [(p, int(rng.randint(2, 8)), 100 + i)
            for i, p in enumerate(_prompts(rng, 5))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, temperature=0.8, top_k=20)
    results, order = srv.run(jobs)
    for rid, (prompt, n_new, seed) in zip(order, jobs):
        np.testing.assert_array_equal(
            np.asarray(results[rid]),
            _solo(params, prompt, n_new, cfg, temperature=0.8,
                  top_k=20, seed=seed))


def test_paged_admission_accounts_in_blocks():
    """Admission is bounded by BLOCKS, not lanes: with lanes to spare,
    a request whose worst-case demand exceeds the free list is turned
    away (admit -> None) and admitted once blocks free up."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=3)
    # 8 lanes but only 4 usable blocks of 8 positions = 32 positions
    srv = ContinuousBatcher(params, cfg, max_batch=8, paged=True,
                            block_size=8, num_blocks=5)
    p = list(range(1, 6))
    r1 = srv.admit(p, 10)            # lifetime: pos 13 -> 2 blocks
    r2 = srv.admit(p, 10)            # 2 more
    assert r1 is not None and r2 is not None
    assert srv._alloc.available == 0
    assert srv.active_count == 2 and srv.max_batch == 8
    assert srv.admit(p, 10) is None  # lanes free, blocks are not
    # an impossible request raises rather than queuing forever
    with pytest.raises(ValueError):
        srv.admit(list(range(1, 8)), 50)    # needs > 4 blocks
    done = {}
    while r1 not in done or r2 not in done:
        done.update(srv.step())
    assert srv._alloc.available == 4
    r3 = srv.admit(p, 10)            # blocks returned -> admissible
    assert r3 is not None
    for rid in (r1, r2):
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      _solo(params, p, 10, cfg))


def test_paged_lazy_allocation_as_positions_advance():
    """Blocks materialize per dispatch window, not at admission: a
    long-budget request starts with its prompt's blocks (rest
    reserved) and grows its table as decode crosses block
    boundaries."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=3)
    srv = ContinuousBatcher(params, cfg, max_batch=1, paged=True,
                            block_size=8)
    rid = srv.admit([1, 2, 3], 40)   # lifetime: pos 41 -> 6 blocks
    assert len(srv._lane_blocks[0]) == 1      # covers positions 0..7
    assert srv._alloc.reserved == 5
    out, peak = {}, 1
    while rid not in out:
        out.update(srv.step())
        peak = max(peak, len(srv._lane_blocks[0]))
    assert peak > 1                  # the table grew during decode
    np.testing.assert_array_equal(np.asarray(out[rid]),
                                  _solo(params, [1, 2, 3], 40, cfg))
    assert srv._alloc.reserved == 0
    assert srv._alloc.free_blocks == srv.num_blocks - 1


def test_prefix_sharing_refcounts_and_nesting():
    """Nested cached prefixes share blocks longest-wins; an admission
    maps the full shared blocks (no copy), copy-on-extends the partial
    tail, and a shared block frees only at refcount zero."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=5)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8)
    usable = srv.num_blocks - 1
    base = list(range(1, 10))            # 9 tokens: 1 full + 1 partial
    srv.cache_prefix(base)
    assert srv._alloc.free_blocks == usable - 2
    # the nested longer prefix shares base's FULL block and
    # copy-on-extends base's partial tail into ONE own block (16
    # tokens = 2 entries total, 1 shared + 1 own)
    longer = base + [11, 12, 13, 14, 15, 16, 17]      # 16 tokens
    srv.cache_prefix(longer)
    assert srv._alloc.free_blocks == usable - 3
    shared_block = srv._prefix_cache[tuple(base)][0][0]
    assert srv._prefix_cache[tuple(longer)][0][0] == shared_block
    assert int(srv._alloc.ref[shared_block]) == 2
    # longest-wins at admission
    prompt = longer + [21, 22]
    p_len, blocks, _ = srv._lookup_prefix_blocks(prompt)
    assert p_len == 16 and blocks == srv._prefix_cache[tuple(longer)][0]
    rid = srv.admit(prompt, 5)
    # admission shares the two FULL blocks of `longer` (16 tokens) —
    # refcount up, nothing copied, nothing newly scattered over them
    assert int(srv._alloc.ref[shared_block]) == 3
    out = {}
    while rid not in out:
        out.update(srv.step())
    np.testing.assert_array_equal(np.asarray(out[rid]),
                                  _solo(params, prompt, 5, cfg))
    assert int(srv._alloc.ref[shared_block]) == 2   # lane released
    # evicting one sharer keeps the block (the other entry holds it);
    # evicting the last frees it to the free list
    srv._evict_prefixes(srv.num_blocks)    # drain the prefix cache
    assert not srv._prefix_cache
    assert int(srv._alloc.ref[shared_block]) == 0
    assert srv._alloc.free_blocks == usable


def test_prefix_lru_eviction_under_block_pressure():
    """An unreferenced cached prefix is LRU-evicted when admission
    needs its blocks — and its blocks actually come back. A prefix
    shared with a LIVE lane yields nothing until the lane finishes."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=7)
    # 6 usable blocks of 8
    srv = ContinuousBatcher(params, cfg, max_batch=4, paged=True,
                            block_size=8, num_blocks=7)
    a, b = list(range(1, 9)), list(range(21, 29))   # 1 full block each
    srv.cache_prefix(a)
    srv.cache_prefix(b)
    assert srv._alloc.free_blocks == 4
    # keep `a` shared with a live lane (1 shared + 2 own/reserved)
    ra = srv.admit(a + [31], 12)
    assert ra is not None
    # demand 3 > available 2: LRU eviction must free blocks — `a` is
    # older but pinned by the live lane (releasing it frees nothing),
    # so the UNREFERENCED `b` is the one evicted
    rid = srv.admit(list(range(41, 47)), 18)   # lifetime 3 blocks
    assert rid is not None
    assert tuple(b) not in srv._prefix_cache
    assert tuple(a) in srv._prefix_cache       # pinned sharer survives
    done = {}
    while rid not in done or ra not in done:
        done.update(srv.step())
    np.testing.assert_array_equal(np.asarray(done[ra]),
                                  _solo(params, a + [31], 12, cfg))
    np.testing.assert_array_equal(
        np.asarray(done[rid]), _solo(params, list(range(41, 47)), 18,
                                     cfg))
    # everything but `a`'s cached block came home
    assert srv._alloc.free_blocks == 5


def test_paged_pipelined_staleness_eviction_and_prefix():
    """The pipelined paged pool: admission staleness (mid-flight
    admission enters at the next boundary), mid-flight eviction
    (in-flight emissions discarded by rid), and prefix-shared
    admissions — all bit-exact vs solo."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=7)
    rng = np.random.RandomState(3)
    p1, p2, p3 = _prompts(rng, 3)
    srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                            block_size=8, pipeline_depth=3)
    system = [7, 3, 9, 1, 4]
    srv.cache_prefix(system)
    r1 = srv.admit(system + p1, 10)
    done = {}
    done.update(srv.step())             # window fills to depth 3
    assert len(srv._inflight) > 0
    r2 = srv.admit(p2, 8)               # admitted MID-FLIGHT
    assert all(r2 not in rec[1] for rec in srv._inflight)
    done.update(srv.step())
    partial = srv.cancel(r1)            # evicted MID-FLIGHT
    assert partial is not None
    r3 = srv.admit(p3, 5)               # reuses the lane + its blocks
    while r2 not in done or r3 not in done:
        done.update(srv.step())
    want1 = _solo(params, system + p1, 10, cfg)
    np.testing.assert_array_equal(np.asarray(partial),
                                  want1[:len(partial)])
    np.testing.assert_array_equal(np.asarray(done[r2]),
                                  _solo(params, p2, 8, cfg))
    np.testing.assert_array_equal(np.asarray(done[r3]),
                                  _solo(params, p3, 5, cfg))


def test_paged_int8_kv_matches_dense_int8():
    """kv_cache_int8 through the block pool (int8 codes + per-block
    scale planes) emits BIT-identical streams to the dense int8 path
    (the gathered view reproduces the same codes and scales at every
    unmasked position), and both sit within the documented ~0.5-1%
    attention error of the fp32 pool on logits."""
    cfg8 = _cfg(kv_cache_int8=True)
    params = tf.init_params(cfg8, seed=3)
    rng = np.random.RandomState(1)
    jobs = [(p, int(rng.randint(2, 10))) for p in _prompts(rng, 5)]
    dense, od = ContinuousBatcher(params, cfg8, max_batch=2).run(jobs)
    paged, op = ContinuousBatcher(params, cfg8, max_batch=2,
                                  paged=True, block_size=8).run(jobs)
    for rd, rp in zip(od, op):
        np.testing.assert_array_equal(np.asarray(dense[rd]),
                                      np.asarray(paged[rp]))
    # the int8 attention error bound, measured through the paged pool:
    # per-step logits stay within ~1% relative of the fp32 cache path
    cfg = _cfg()
    prompt = jnp.asarray([jobs[0][0]], jnp.int32)
    cache = tf.init_cache(cfg, 1)
    logits_fp, cache = tf.prefill(params, cache, prompt, cfg)
    # prefill the paged int8 pool through an admission-shaped path
    srv = ContinuousBatcher(params, cfg8, max_batch=1, paged=True,
                            block_size=8)
    srv.admit(jobs[0][0], 2)
    tok = jnp.argmax(logits_fp, -1).astype(jnp.int32)
    pos = jnp.full((1,), prompt.shape[1], jnp.int32)
    l8, _ = tf.decode_step_paged(params, srv._pool, srv._tables, tok,
                                 pos, cfg8)
    lfp, _ = tf.decode_step(params, cache, tok, pos, cfg)
    rel = float(np.max(np.abs(np.asarray(l8) - np.asarray(lfp)))
                / np.max(np.abs(np.asarray(lfp))))
    assert rel < 0.02, "int8-paged logits drifted %.3f%% from fp" \
        % (100 * rel)


@pytest.mark.parametrize("controls", [
    (True, 1.0, None, None), (False, 0.8, 5, 0.9)],
    ids=["greedy", "sampled"])
def test_one_decode_program_serves_both_caches(controls):
    """The decode program is written once over (cache, tables): built
    dense (tables None) and paged it is two entries of _serving_jit,
    never one wrapper, and on a pool that gathers to the dense cache
    the two emit the same tokens, advance the same keys and leave the
    same K/V."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    build = serving._jitted_pipeline_chunk
    dense = build(cfg, *controls, 3, False)
    paged = build(cfg, *controls, 3, True)
    assert dense is not paged
    assert build(cfg, *controls, 3, False) is dense
    assert build(cfg, *controls, 3, True) is paged
    cache_at, tables_at = 1, 2      # of (toks, cache, tables, tok, ...)
    b, bs = 3, 8
    nb = cfg.max_len // bs
    rng = np.random.RandomState(4)
    cache = jax.tree.map(
        lambda x: jnp.asarray(rng.randn(*x.shape) * 0.1, x.dtype),
        tf.init_cache(cfg, b))
    tables = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)
    pool = jax.tree.map(
        lambda x: jnp.concatenate([
            jnp.zeros((1, bs) + x.shape[2:], x.dtype),
            x.reshape((b * nb, bs) + x.shape[2:])]), cache)
    tok = jnp.asarray([5, 17, 90], jnp.int32)
    pos = jnp.asarray([4, 20, 0], jnp.int32)
    keys = jnp.asarray(rng.randint(0, 2 ** 31, (b, 2)), jnp.uint32)
    got_d = dense(params, cache, None, tok, pos, keys)
    got_p = paged(params, pool, tables, tok, pos, keys)
    assert len(got_d) == len(got_p)
    for i, (d, p) in enumerate(zip(got_d, got_p)):
        if i == tables_at:      # passed through: None, or the tables
            assert d is None
            np.testing.assert_array_equal(np.asarray(p),
                                          np.asarray(tables))
        elif i == cache_at:
            for layer_d, layer_p in zip(d, p):
                view = tf._paged_gather(layer_p, tables)
                for name in layer_d:
                    np.testing.assert_array_equal(
                        np.asarray(layer_d[name]), np.asarray(view[name]))
        else:                   # emissions, keys, the rolling carry
            np.testing.assert_array_equal(np.asarray(d), np.asarray(p))


def test_paged_capacity_2x_dense_at_equal_hbm():
    """The acceptance bar: at a FIXED cache-HBM budget, the paged pool
    admits >= 2x the concurrent requests of the dense-lane batcher on
    a mixed-length workload (dense burns a [max_len] row per request
    regardless of its actual context)."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(5)
    jobs = [(list(rng.randint(1, 211, 5)), 8) for _ in range(8)]
    # budget: 2 dense lanes = 128 cache positions = 16 blocks of 8
    dense = ContinuousBatcher(params, cfg, max_batch=2)
    paged = ContinuousBatcher(params, cfg, max_batch=8, paged=True,
                              block_size=8, num_blocks=17)
    dense_adm = [dense.admit(p, n) for p, n in jobs]
    paged_adm = [paged.admit(p, n) for p, n in jobs]
    n_dense = sum(1 for r in dense_adm if r is not None)
    n_paged = sum(1 for r in paged_adm if r is not None)
    assert n_dense == 2
    assert n_paged >= 2 * n_dense, (n_paged, n_dense)
    # and the over-admitted pool still emits exact streams
    done = {}
    while paged.active_count:
        done.update(paged.step())
    for rid, (p, n) in zip(paged_adm, jobs):
        if rid is None:
            continue
        np.testing.assert_array_equal(np.asarray(done[rid]),
                                      _solo(params, p, n, cfg))


def test_paged_requeue_on_dispatch_failure(fresh_rows):
    """The PR 6 recovery path composes: an injected dispatch fault
    frees the lanes, rebuilds pool + allocator, and requeues live
    requests from their token prefix — greedy streams stay
    bit-exact."""
    cfg = _cfg()
    params = tf.init_params(cfg, seed=21)
    rng = np.random.RandomState(7)
    p1, p2 = _prompts(rng, 2)
    chaos.reset()
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                block_size=8)
        r1 = srv.admit(p1, 12)
        r2 = srv.admit(p2, 9)
        done = {}
        done.update(srv.step())
        chaos.inject("serving.dispatch", "error", at=0)
        while r1 not in done or r2 not in done:
            done.update(srv.step())
        assert srv._alloc.free_blocks == srv.num_blocks - 1
        # the re-admissions' rows come from the row's program too
        assert fresh_rows.made == [cfg] * 4 and not fresh_rows.eager
        np.testing.assert_array_equal(np.asarray(done[r1]),
                                      _solo(params, p1, 12, cfg))
        np.testing.assert_array_equal(np.asarray(done[r2]),
                                      _solo(params, p2, 9, cfg))
    finally:
        chaos.reset()


def test_paged_gauges_and_health_snapshot():
    """serving.kv_free_blocks / kv_block_utilization ride the gauge
    API (and therefore every exporter + /healthz), and
    health_snapshot() carries the router's signals."""
    from mxnet_tpu.observability import core as obs
    cfg = _cfg()
    params = tf.init_params(cfg, seed=3)
    obs.reset()
    obs.set_enabled(True)
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                block_size=8)
        srv.run([([4, 7, 2], 4), ([9, 1], 3)])
        names = {r[1] for r in obs.records()}
        for needed in ("serving.kv_free_blocks",
                       "serving.kv_block_utilization",
                       "serving.lane_occupancy"):
            assert needed in names, needed
    finally:
        obs.set_enabled(None)
        obs.reset()
    snap = srv.health_snapshot()
    assert snap["serving.kv_free_blocks"] == srv.num_blocks - 1
    assert snap["serving.kv_block_utilization"] == 0.0
    assert snap["serving.lane_occupancy"] == 0
    assert "serving.slo_attainment" in snap
    # dense snapshots carry no block signals
    dense = ContinuousBatcher(params, cfg, max_batch=2)
    assert "serving.kv_free_blocks" not in dense.health_snapshot()


def test_allocator_invariants_and_validation():
    alloc = BlockAllocator(5)
    assert alloc.free_blocks == 4 and alloc.available == 4
    ids = alloc.alloc(2)
    assert 0 not in ids
    alloc.share(ids)
    alloc.release(ids)
    assert alloc.free_blocks == 2          # still referenced once
    alloc.release(ids)
    assert alloc.free_blocks == 4          # refcount zero -> freed
    with pytest.raises(RuntimeError):
        alloc.alloc(5)
    with pytest.raises(RuntimeError):
        alloc.release([ids[0]])            # double free
    alloc.reserve(3)
    assert alloc.available == 1
    with pytest.raises(ValueError):
        BlockAllocator(1)
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=3)
    with pytest.raises(ValueError):        # 7 does not divide 64
        ContinuousBatcher(params, cfg, paged=True, block_size=7)


def test_env_defaults(monkeypatch):
    """MXNET_KV_PAGED turns paging on by default; MXNET_KV_BLOCK_SIZE
    picks the block size."""
    cfg = _cfg(max_len=64)
    params = tf.init_params(cfg, seed=3)
    monkeypatch.setenv("MXNET_KV_PAGED", "1")
    monkeypatch.setenv("MXNET_KV_BLOCK_SIZE", "8")
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    assert srv.paged and srv.block_size == 8
    monkeypatch.setenv("MXNET_KV_PAGED", "0")
    assert not ContinuousBatcher(params, cfg, max_batch=2).paged
