"""A chunk's attention against cached K/V rows (kernels/chunk_attention.py):
the kernel, interpreted on the CPU, against both XLA forms it replaces
where its rule engages (transformer._cached_plane and _blocked_attention,
which stay the fallback); the rule itself (chunk_blocks;
transformer.chunk_attention_blocks) case by case, the floor of 1,024
queries among them (the kernel's own door has none); an admission through
the batcher at an engaged width; and the batcher's counters of both.

A CPU run says nothing about lowering or speed: tests/test_tpu_compile.py
compiles the kernel for a described v5e, PERF.md has the chip's times."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.chunk_attention import (MIN_QUERIES, chunk_attention,
                                               chunk_blocks)
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

D = 128
# (K/V heads, query heads a K/V head): Cerebras-GPT's, SmallThinker's,
# Nemotron's, Jamba2's, and a grouping whose block of queries is cut by
# the heads (QUERY_ROWS)
HEADS = {"mha16": (16, 1), "gqa4x7": (4, 7), "gqa2x16": (2, 16),
         "mqa1x20": (1, 20), "mha4": (4, 1)}
# sums taken in another order, and unnormalised weights rounded to the
# rows' dtype for the second dot (readings 8e-7 and 1.6e-2, an output's
# last bfloat16 bit)
TOL = {jnp.float32: 3e-6, jnp.bfloat16: 3.2e-2}


def _cfg(kvh, group, t, **kw):
    kw = dict(dict(vocab_size=64, d_model=64, n_heads=kvh * group,
                   n_kv_heads=kvh, attn_head_dim=D, n_layers=2, d_ff=64,
                   max_len=t), **kw)
    return tf.TransformerConfig(**kw)


def _sides(c, t, kvh, group, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (1, c, kvh * group, D), dtype)
    k = jax.random.normal(keys[1], (1, t, kvh, D), dtype)
    v = jax.random.normal(keys[2], (1, t, kvh, D), dtype)
    return q, k, v


def _close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# (queries, rows, start): a fresh prompt, one behind a prefix, a chunk
# that ends with the rows, one whose start lies inside a key block
CHUNKS = {
    "fresh-256": (256, 1024, 0),
    "prefix-512": (512, 2048, 1024),
    "to-the-end-512": (512, 1024, 512),
    "ragged-start-256": (256, 1024, 333),
}
# the widest bucket against rows it fills: once a dtype, interpreting is
# slow
WIDEST = {"full-2048": (2048, 2048, 0)}


CASES = [(heads, dtype, chunk) for chunk in CHUNKS for heads, dtype in (
    ("mha16", jnp.bfloat16), ("gqa4x7", jnp.bfloat16),
    ("gqa2x16", jnp.bfloat16), ("mqa1x20", jnp.bfloat16),
    ("mha4", jnp.float32), ("gqa4x7", jnp.float32))] \
    + [("mha16", jnp.bfloat16, "full-2048"), ("mha4", jnp.float32,
                                               "full-2048")]


@pytest.mark.parametrize(
    "heads,dtype,chunk", CASES,
    ids=["%s-%s-%s" % (h, d.__name__, c) for h, d, c in CASES])
def test_the_kernel_is_the_xla_contraction(heads, dtype, chunk):
    """Against the plane and against the contraction in blocks."""
    kvh, group = HEADS[heads]
    c, t, start = {**CHUNKS, **WIDEST}[chunk]
    q, k, v = _sides(c, t, kvh, group, dtype)
    cfg = _cfg(kvh, group, t)
    at = start + jnp.arange(c)
    got = jax.jit(chunk_attention)(q, k, v, jnp.int32(start))
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, tf._cached_plane(q, {"k": k, "v": v}, at, cfg, q.dtype),
           dtype)
    _close(got, tf._blocked_attention(q, k, v, at), dtype)


def test_rows_behind_the_chunk_are_never_read():
    """A key block behind the chunk's last position is no step and no
    fetch, whatever it holds (NaNs here); the rows behind that position
    inside its own block are fetched and masked, as the plane masks
    them (a weight of 0, so they must be numbers: a fresh row's zeros,
    or what an earlier occupant left)."""
    q, k, v = _sides(256, 1024, 4, 1, jnp.float32)
    start = 128
    assert chunk_blocks(256, 1024, 4, 4, D, 4, floor=128) == (256, 256)
    dirty = [x.at[:, start + 256:512].set(1e4).at[:, 512:].set(jnp.nan)
             for x in (k, v)]
    got = chunk_attention(q, *dirty, jnp.int32(start))
    want = chunk_attention(q, k, v, jnp.int32(start))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_buckets_padding_changes_no_real_row():
    """A prompt of 300 in a bucket of 512: the rows behind the last real
    token see more than the real ones and tell them nothing."""
    q, k, v = _sides(512, 1024, 4, 7, jnp.bfloat16, seed=2)
    whole = chunk_attention(q, k, v, jnp.int32(0))
    noise = jax.random.normal(jax.random.PRNGKey(9), q[:, 300:].shape,
                              q.dtype)
    other = chunk_attention(q.at[:, 300:].set(noise), k, v, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(whole[:, :300], np.float32),
                                  np.asarray(other[:, :300], np.float32))
    # other blocks (an explicit pair, as a timing sweep passes them) are
    # the same sums in another order
    _close(chunk_attention(q, k, v, jnp.int32(0), block_q=128, block_k=256),
           whole, jnp.bfloat16)


BLOCKS = {
    # queries, rows, heads, K/V heads, head width, bytes[, floor] -> blocks
    "cerebras-1024": ((1024, 2048, 16, 16, 128, 2), (512, 1024)),
    "cerebras-2048": ((2048, 2048, 16, 16, 128, 2), (512, 1024)),
    "cerebras-256-no-floor": ((256, 2048, 16, 16, 128, 2, 128), (256, 256)),
    "cerebras-512-no-floor": ((512, 2048, 16, 16, 128, 2, 128), (512, 512)),
    "smallthinker-8192": ((8192, 16384, 28, 4, 128, 2), (256, 1024)),
    "nemotron-8192": ((8192, 8192, 32, 2, 128, 2), (128, 1024)),
    "jamba2-2048": ((2048, 4096, 20, 1, 128, 2), (128, 1024)),
    "rows-of-384": ((256, 384, 4, 4, 128, 4, 128), (256, 128)),
    "queries-of-1152": ((1152, 2304, 4, 4, 128, 2), (384, 768)),
    "under-the-floor": ((512, 2048, 16, 16, 128, 2), None),
    "toy-width": ((1024, 2048, 16, 16, 64, 2), None),
    "rows-128-does-not-divide": ((1024, 2000, 16, 16, 128, 2), None),
    "queries-128-does-not-divide": ((1100, 2048, 16, 16, 128, 2), None),
    "int8-rows": ((1024, 2048, 16, 16, 128, 1), None),
    "three-packed-heads": ((1024, 2048, 3, 3, 128, 2), None),
}


@pytest.mark.parametrize("case", BLOCKS)
def test_chunk_blocks(case):
    args, want = BLOCKS[case]
    assert chunk_blocks(*args) == want
    if want is None and case != "under-the-floor":
        # the kernel's own door has no floor: it tiles what 128 divides
        c, t, heads, kvh, d, itemsize = args
        dtype = {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[itemsize]
        with pytest.raises(ValueError, match="_cached_plane"):
            chunk_attention(jnp.zeros((1, c, heads, d), dtype),
                            jnp.zeros((1, t, kvh, d), dtype),
                            jnp.zeros((1, t, kvh, d), dtype), 0)


def _lowered(fn, *args):
    # under one name: a module is called after its function
    return jax.jit(lambda *xs: fn(*xs)).lower(*args).as_text()


def _call(c=1024, t=2048, heads=2, kvh=2, d=D, dtype=jnp.float32,
          rows_dtype=None, lanes=1, per_lane=False):
    q = jax.ShapeDtypeStruct((lanes, c, heads, d), dtype)
    rows = jax.ShapeDtypeStruct((lanes, t, kvh, d), rows_dtype or dtype)
    at = jax.ShapeDtypeStruct((lanes, c) if per_lane else (c,), jnp.int32)
    return q, {"k": rows, "v": rows}, at


RULE = {
    # what the call holds -> (arguments of _call, window)
    "window": (dict(), 300),
    "positions-a-lane": (dict(lanes=2, per_lane=True), None),
    "toy-width": (dict(d=64), None),
    "under-the-floor": (dict(c=512), None),
    "rows-128-does-not-divide": (dict(t=2000), None),
    "mixed-dtypes": (dict(rows_dtype=jnp.bfloat16), None),
}


@pytest.mark.parametrize("what", RULE)
def test_cached_attention_keeps_the_xla_text(what):
    """Where the rule does not engage the program is the reference's,
    letter for letter: the plane, or for a window layer the blocks."""
    kw, window = RULE[what]
    q, view, at = _call(**kw)
    cfg = _cfg(view["k"].shape[2], q.shape[2] // view["k"].shape[2],
               view["k"].shape[1], attn_head_dim=q.shape[3])
    assert tf.chunk_attention_blocks(q, view, at, window) is None

    def got(q, k, v, at):
        return tf._cached_attention(q, {"k": k, "v": v}, at, cfg, q.dtype,
                                    window)

    def want(q, k, v, at):
        with tf._attn_scope(window):
            if window is not None:
                return tf._blocked_attention(q, k, v, at, 0, window
                                             ).astype(q.dtype)
            return tf._cached_plane(q, {"k": k, "v": v}, at, cfg, q.dtype)

    args = (q, view["k"], view["v"], at)
    assert _lowered(got, *args) == _lowered(want, *args)


def test_int8_rows_keep_the_xla_text():
    cfg = _cfg(2, 1, 2048, kv_cache_int8=True)
    view = jax.eval_shape(lambda: tf.init_cache(cfg, 1))[0]
    q, _, at = _call()
    assert view["k"].dtype == jnp.int8
    assert tf.chunk_attention_blocks(q, view, at) is None
    text = _lowered(lambda q, view, at: tf._cached_attention(
        q, view, at, cfg, q.dtype), q, view, at)
    assert "stablehlo.while" not in text


def test_cached_attention_engages_by_what_the_call_holds():
    q, view, at = _call()
    cfg = _cfg(2, 1, 2048)
    assert tf.chunk_attention_blocks(q, view, at) == (512, 1024)
    text = _lowered(lambda q, k, v, at: tf._cached_attention(
        q, {"k": k, "v": v}, at, cfg, q.dtype), q, view["k"], view["v"], at)
    # interpreted on the CPU: the grid is a loop, no einsum is left
    assert "stablehlo.while" in text and "dot_general" in text
    assert "1x1024x2x1x2048xf32" not in text
    # a plane past ATTN_PLANE_ELEMS that the rule has blocks for is the
    # kernel's too, no longer the XLA blocks'
    big = _call(c=8192, t=16384, heads=28, kvh=4, dtype=jnp.bfloat16)
    assert tf._attn_blocked(8192, 16384, _cfg(4, 7, 16384))
    assert tf.chunk_attention_blocks(*big) == (256, 1024)
    assert MIN_QUERIES == 1024


def test_cached_attention_is_the_plane_at_an_engaged_shape():
    """Through the model's own call, at a start inside the rows."""
    q, k, v = _sides(1024, 2048, 2, 1, jnp.float32, seed=4)
    cfg = _cfg(2, 1, 2048)
    at = 700 + jnp.arange(1024)
    got = tf._cached_attention(q, {"k": k, "v": v}, at, cfg, q.dtype)
    want = tf._cached_plane(q, {"k": k, "v": v}, at, cfg, q.dtype)
    _close(got, want, jnp.float32)


@pytest.fixture(scope="module")
def engaged():
    """Two heads of 128 over a stream of 64, float32, rows for 2,048
    positions: a prompt past 512 tokens is admitted in a bucket of 1,024
    or more, which the kernel takes."""
    cfg = _cfg(2, 1, 2048, dtype=jnp.float32)
    return cfg, tf.init_params(cfg, 3)


def test_the_batcher_serves_generates_tokens_at_an_engaged_shape(engaged):
    """An admission through the kernel (a bucket of 1,024) and two under
    the floor (buckets of 8 and 512) beside it: each stream is
    generate()'s, whose whole-prompt prefill is the XLA text at these
    lengths."""
    cfg, params = engaged
    prompts = [list(np.arange(700) % 60 + 1), [5, 9, 2, 44, 17],
               list(np.arange(300) % 50 + 3)]
    srv = ContinuousBatcher(params, cfg, max_batch=3)
    served, rids = srv.run([(p, 6) for p in prompts])
    for rid, prompt in zip(rids, prompts):
        want = tf.generate(params, jnp.asarray([prompt], jnp.int32), 6, cfg)
        assert list(served[rid]) == list(np.asarray(want)[0])


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind (see
    tests/test_kimi_linear.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


def test_an_admission_counts_its_contractions_by_the_rule(engaged,
                                                          telemetry):
    """attn.chunk_calls counts a K/V layer a prefill_chunk call,
    attn.chunk_kernel those of them the kernel ran: a bucket of 1,024
    both, a bucket of 8 the first only."""
    cfg, params = engaged
    srv = ContinuousBatcher(params, cfg, max_batch=3)
    srv.admit(list(np.arange(700) % 60 + 1), 3)
    assert obs.counter("attn.chunk_calls").value == 2
    assert obs.counter("attn.chunk_kernel").value == 2
    srv.admit([5, 9, 2, 44, 17], 3)
    assert obs.counter("attn.chunk_calls").value == 4
    assert obs.counter("attn.chunk_kernel").value == 2
    snap = srv.health_snapshot()
    assert snap["attn.chunk_calls"] == 4 and snap["attn.chunk_kernel"] == 2
    assert tf.chunk_contractions(params, cfg, srv._lane_row, 1024) == (2, 2)
    assert tf.chunk_contractions(params, cfg, srv._lane_row, 512) == (2, 0)
    # a window layer beside a full one: the ring's view keeps the blocks
    mixed = _cfg(2, 1, 2048, dtype=jnp.float32, positions="none",
                 layer_kinds=("attention", "window"), attn_window=64)
    obs.reset()
    srv = ContinuousBatcher(tf.init_params(mixed, 1), mixed, max_batch=2)
    srv.admit(list(np.arange(700) % 60 + 1), 3)
    assert obs.counter("attn.chunk_calls").value == 2
    assert obs.counter("attn.chunk_kernel").value == 1
    # a toy width counts its calls and no kernel
    toy = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=3, d_ff=64, max_len=2048)
    obs.reset()
    srv = ContinuousBatcher(tf.init_params(toy, 0), toy, max_batch=2)
    srv.admit(list(np.arange(700) % 60 + 1), 3)
    assert obs.counter("attn.chunk_calls").value == 3
    assert obs.counter("attn.chunk_kernel").value == 0
