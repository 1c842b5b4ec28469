"""int8 KV cache (TransformerConfig.kv_cache_int8): accuracy against
the full-precision cache, exactness of pool-vs-solo under the same
quantizer, prefill/decode path consistency, mesh layout, and the
memory halving the feature exists for."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import transformer as tf


def _cfg(int8, **kw):
    base = dict(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                d_ff=96, max_len=32, dtype=jnp.float32,
                kv_cache_int8=int8)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _logits_close(a, b, rtol=0.08, atol=0.15):
    # logits are O(1-10); int8 K/V + int8 probabilities contribute
    # ~0.5-1% per attention, compounded across layers
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("kvh", [None, 2])
def test_decode_step_int8_close_to_fp(kvh):
    """Scalar decode through the int8 cache tracks the fp cache."""
    cfg_f = _cfg(False, n_kv_heads=kvh)
    cfg_q = _cfg(True, n_kv_heads=kvh)
    params = tf.init_params(cfg_f, seed=5)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(1, 97, (2, 10)), jnp.int32)
    cf, cq = tf.init_cache(cfg_f, 2), tf.init_cache(cfg_q, 2)
    for pos in range(10):
        lf, cf = tf.decode_step(params, cf, toks[:, pos], pos, cfg_f)
        lq, cq = tf.decode_step(params, cq, toks[:, pos], pos, cfg_q)
    _logits_close(lq, lf)


def test_ragged_decode_int8_close_to_fp():
    """Ragged (per-row position) decode with the int8 cache: replay
    the same token stream through both cache formats."""
    cfg_f, cfg_q = _cfg(False), _cfg(True)
    params = tf.init_params(cfg_f, seed=7)
    rng = np.random.RandomState(1)
    stream = [jnp.asarray(rng.randint(1, 97, (3,)), jnp.int32)
              for _ in range(6)]
    res = {}
    for cfg in (cfg_f, cfg_q):
        cache = tf.init_cache(cfg, 3)
        for pos in range(5):
            _, cache = tf.decode_step(params, cache, stream[pos], pos,
                                      cfg)
        ragged_pos = jnp.asarray([5, 3, 4], jnp.int32)
        logits, _ = tf.decode_step(params, cache, stream[5],
                                   ragged_pos, cfg)
        res[cfg.kv_cache_int8] = logits
    _logits_close(res[True], res[False])


def test_generate_int8_pool_equals_solo_and_tracks_fp():
    """Same quantizer on both sides -> the continuous-batching pool is
    BIT-identical to solo generate under int8; and the int8 stream
    stays close to the fp stream (greedy ties may flip on near-equal
    logits, so the check is on agreement fraction, not equality)."""
    from mxnet_tpu.models.serving import ContinuousBatcher
    cfg_q = _cfg(True, max_len=48)
    cfg_f = _cfg(False, max_len=48)
    params = tf.init_params(cfg_f, seed=11)
    jobs = [([3, 7, 2], 10), ([9, 1], 8), ([5, 5, 5, 5], 6)]
    srv = ContinuousBatcher(params, cfg_q, max_batch=2, chunk_size=3)
    results, order = srv.run(jobs)
    agree = total = 0
    for rid, (p, n) in zip(order, jobs):
        solo = np.asarray(tf.generate(
            params, jnp.asarray([p], jnp.int32), n, cfg_q)[0])
        np.testing.assert_array_equal(np.asarray(results[rid]), solo)
        fp = np.asarray(tf.generate(
            params, jnp.asarray([p], jnp.int32), n, cfg_f)[0])
        agree += int((solo == fp).sum())
        total += solo.size
    assert agree / total > 0.7, (agree, total)


def test_prefill_chunk_consistent_with_steps_int8():
    """Chunked prefill reads its own rows through the quantizer, so it
    matches stepping decode_step token by token (same cache contents,
    logits within quantization noise of each other)."""
    cfg = _cfg(True, n_kv_heads=2, rope=True)
    params = tf.init_params(cfg, seed=13)
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(1, 97, (2, 8)), jnp.int32)
    step_cache = tf.init_cache(cfg, 2)
    for pos in range(8):
        step_logits, step_cache = tf.decode_step(
            params, step_cache, toks[:, pos], pos, cfg)
    chunk_logits, chunk_cache = tf.prefill_chunk(
        params, tf.init_cache(cfg, 2), toks, 0, cfg)
    for lc_s, lc_c in zip(step_cache, chunk_cache):
        # compare DEQUANTIZED values: a +-1 code flip on a rounding
        # boundary is within quantizer noise, raw codes are not
        for codes, scales in (("k", "ks"), ("v", "vs")):
            ds = np.asarray(tf._kv_dequant(
                lc_s[codes][:, :8], lc_s[scales][:, :8], jnp.float32))
            dc = np.asarray(tf._kv_dequant(
                lc_c[codes][:, :8], lc_c[scales][:, :8], jnp.float32))
            atol = 2.0 * float(np.abs(ds).max()) / 127.0
            np.testing.assert_allclose(dc, ds, rtol=2e-2, atol=atol)
    _logits_close(chunk_logits[:, -1], step_logits)


def test_generate_int8_mesh_matches_single_device():
    """shard_cache lays the scale planes out alongside the codes; the
    dp/tp-sharded int8 generation equals the single-device one."""
    from mxnet_tpu.parallel import make_mesh
    cfg = _cfg(True, max_len=40, n_kv_heads=2)
    params = tf.init_params(cfg, seed=17)
    prompt = jnp.asarray([[4, 8, 1], [2, 6, 3]], jnp.int32)
    plain = np.asarray(tf.generate(params, prompt, 8, cfg))
    mesh = make_mesh({"dp": 2, "tp": 2, "rest": 2})
    sp = tf.shard_params(params, cfg, mesh)
    sharded = np.asarray(tf.generate(sp, prompt, 8, cfg, mesh=mesh))
    np.testing.assert_array_equal(sharded, plain)


def test_beam_search_int8_runs_and_beam1_is_greedy():
    cfg = _cfg(True, max_len=40)
    params = tf.init_params(cfg, seed=19)
    prompt = jnp.asarray([[3, 1, 4]], jnp.int32)
    seqs, scores = tf.beam_search(params, prompt, 6, cfg, beam=1)
    greedy = np.asarray(tf.generate(params, prompt, 6, cfg))
    np.testing.assert_array_equal(np.asarray(seqs)[:, 0], greedy)


def test_int8_cache_memory_halves():
    cfg_f = _cfg(False, dtype=jnp.bfloat16, max_len=128, d_model=128)
    cfg_q = _cfg(True, dtype=jnp.bfloat16, max_len=128, d_model=128)
    nbytes = lambda c: sum(x.nbytes for x in jax.tree.leaves(c))
    f = nbytes(tf.init_cache(cfg_f, 4))
    q = nbytes(tf.init_cache(cfg_q, 4))
    # int8 codes (1/2 the bf16 bytes) + fp32 scale planes (4/(2*D))
    assert q < 0.6 * f, (q, f)


def test_speculative_generate_int8_target_cache():
    """Speculative decoding composes with the int8 target cache: the
    output equals the int8-cache greedy generate (verification reads
    the same quantized cache decode would)."""
    cfg = _cfg(True, max_len=40)
    dcfg = _cfg(False, d_model=32, n_heads=2, n_layers=1, d_ff=48,
                max_len=40)
    params = tf.init_params(cfg, seed=23)
    draft = tf.init_params(dcfg, seed=24)
    prompt = jnp.asarray([[7, 2, 9]], jnp.int32)
    ref = np.asarray(tf.generate(params, prompt, 8, cfg))
    spec = np.asarray(tf.speculative_generate(
        params, draft, prompt, 8, cfg, dcfg, k_draft=3))
    np.testing.assert_array_equal(spec, ref)


def test_prefill_delegates_to_chunk_exactly_int8():
    """Under int8, prefill() and prefill_chunk() are the SAME path
    (delegation), so solo generate() and the batcher's admission read
    identical quantized caches — first tokens can never diverge."""
    cfg = _cfg(True, n_kv_heads=2)
    params = tf.init_params(cfg, seed=29)
    toks = jnp.asarray(
        np.random.RandomState(4).randint(1, 97, (2, 7)), jnp.int32)
    lp, cp = tf.prefill(params, tf.init_cache(cfg, 2), toks, cfg)
    lc, cc = tf.prefill_chunk(params, tf.init_cache(cfg, 2), toks, 0,
                              cfg, logits_row=6)
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lc))
    for a, b in zip(jax.tree.leaves(cp), jax.tree.leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _filled_cache(cfg, batch, seed):
    """A dense cache with something at every position (int8: codes and
    positive scales), as a stream that ran for a while leaves it."""
    rng = np.random.RandomState(seed)

    def fill(x):
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, x.shape), jnp.int8)
        return jnp.asarray(np.abs(rng.randn(*x.shape)) * 0.1 + 0.01,
                           x.dtype)
    return jax.tree.map(fill, tf.init_cache(cfg, batch))


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("int8", [False, True])
def test_verify_window_of_one_is_the_ragged_decode_step(int8):
    """verify_chunk with C = 1 and decode_step at ragged positions are
    the same layer body over the same store and the same masked read.
    Through the int8 cache both contract in _int8_cache_attention and
    agree bit for bit; a float cache is read by two forms of one
    contraction (_cached_attention's chunk form, _decode_attention's
    row form), which XLA may sum in another order: a few ulps."""
    cfg = _cfg(int8, n_kv_heads=2)
    params = tf.init_params(cfg, seed=31)
    cache = _filled_cache(cfg, 3, 1)
    tok = jnp.asarray([5, 17, 90], jnp.int32)
    pos = jnp.asarray([4, 20, 0], jnp.int32)
    ld, cd = jax.jit(lambda p, c, t, q: tf.decode_step(p, c, t, q, cfg))(
        params, cache, tok, pos)
    lv, cv = jax.jit(lambda p, c, t, q: tf.verify_chunk(p, c, t, q, cfg))(
        params, cache, tok[:, None], pos)
    if int8:
        _same(lv[:, 0], ld)
        _same(cv, cd)
        return
    np.testing.assert_allclose(np.asarray(lv[:, 0]), np.asarray(ld),
                               rtol=0, atol=2e-6)
    for x, y in zip(jax.tree.leaves(cv), jax.tree.leaves(cd)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=2e-6)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("door", ["decode", "verify"])
def test_paged_doors_equal_their_dense_forms_on_a_gathered_pool(door,
                                                                int8):
    """A pool whose gathered view IS the dense cache: the paged step
    and the paged verifier give the dense ones' logits bit for bit, and
    the pool they leave gathers to the cache the dense ones leave,
    whether the cache holds floats or int8 codes and scales."""
    cfg = _cfg(int8, n_kv_heads=2, rope=True)
    params = tf.init_params(cfg, seed=37)
    b, bs = 3, 8
    nb = cfg.max_len // bs
    cache = _filled_cache(cfg, b, 2)
    tables = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)

    def blocks(leaf):       # lane i's rows become blocks 1+i*nb ...
        body = leaf.reshape((b * nb, bs) + leaf.shape[2:])
        return jnp.concatenate([jnp.zeros_like(body[:1]), body])
    pool = jax.tree.map(blocks, cache)
    _same([tf._paged_gather(layer, tables) for layer in pool], cache)
    pos = jnp.asarray([4, 20, 0], jnp.int32)
    if door == "decode":
        tok = jnp.asarray([5, 17, 90], jnp.int32)
        dense, paged = tf.decode_step, tf.decode_step_paged
    else:
        tok = jnp.asarray(
            np.random.RandomState(5).randint(1, 97, (b, 4)), jnp.int32)
        dense, paged = tf.verify_chunk, tf.verify_chunk_paged
    ld, cd = jax.jit(lambda p, c, t, q: dense(p, c, t, q, cfg))(
        params, cache, tok, pos)
    lp, pp = jax.jit(lambda p, c, tb, t, q: paged(p, c, tb, t, q, cfg))(
        params, pool, tables, tok, pos)
    _same(lp, ld)
    _same([tf._paged_gather(layer, tables) for layer in pp], cd)


def test_beam_search_int8_on_mesh():
    """Beam search's traced cache sharding handles the rank-3 scale
    planes (rank-sliced spec, like shard_cache)."""
    from mxnet_tpu.parallel import make_mesh
    cfg = _cfg(True, max_len=40, n_kv_heads=2)
    params = tf.init_params(cfg, seed=31)
    prompt = jnp.asarray([[3, 1, 4], [2, 7, 7]], jnp.int32)
    plain, _ = tf.beam_search(params, prompt, 6, cfg, beam=2)
    mesh = make_mesh({"dp": 2, "tp": 2, "rest": 2})
    sp = tf.shard_params(params, cfg, mesh)
    sharded, _ = tf.beam_search(sp, prompt, 6, cfg, beam=2, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(sharded),
                                  np.asarray(plain))
