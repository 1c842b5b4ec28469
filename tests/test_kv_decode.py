"""Decode's contraction over dense K/V rows (kernels/kv_decode.py): the
kernel, interpreted on the CPU, against the XLA text it replaces where
its rule engages (kv_decode_reference, which stays the fallback); the
rule itself (kv_block; transformer.kv_decode_block); the model of what
it fetches (rows_fetched) against the index maps' own arithmetic; and
the batcher's counters of both.

A CPU run says nothing about lowering or speed: tests/test_tpu_compile.py
compiles the kernel for a described v5e, PERF.md has the chip's times."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.kv_decode import (kv_block, kv_decode,
                                         kv_decode_reference, rows_fetched)
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

D = 128
# (K/V heads, query heads a K/V head): Cerebras-GPT's, SmallThinker's
# and a small grouping
HEADS = {"mha16": (16, 1), "gqa4x7": (4, 7), "gqa2x2": (2, 2)}
# sums taken in another order, and unnormalised weights rounded to the
# rows' dtype for the second dot (readings 3e-7 and 5e-3)
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def _rows(kvh, group, dtype, lanes=2, blocks=2, seed=0):
    """(q, k, v, block) at the smallest cache of `blocks` blocks that the
    kernel tiles for these heads."""
    itemsize = jnp.dtype(dtype).itemsize
    block = max((1 << 20) // (kvh * D * itemsize), 128)
    t = block * blocks
    assert kv_block(t, kvh, D, itemsize) == block
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (lanes, kvh * group, D), dtype)
    k = jax.random.normal(keys[1], (lanes, t, kvh, D), dtype)
    v = jax.random.normal(keys[2], (lanes, t, kvh, D), dtype)
    return q, k, v, block


LENGTHS = {
    "one-row": lambda block, t: [1, 1],
    "block-less-1": lambda block, t: [block - 1, 3],
    "block": lambda block, t: [block, 3],
    "block-plus-1": lambda block, t: [block + 1, 3],
    "full": lambda block, t: [t, t],
    "scalar": lambda block, t: block + 7,
    "idle-lane": lambda block, t: [t - 5, 0],   # clamped to one row
}


@pytest.mark.parametrize("case", LENGTHS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("heads", HEADS)
def test_the_kernel_is_the_xla_contraction(heads, dtype, case):
    kvh, group = HEADS[heads]
    q, k, v, block = _rows(kvh, group, dtype)
    lengths = jnp.asarray(LENGTHS[case](block, k.shape[1]), jnp.int32)
    got = kv_decode(q, k, v, lengths)
    want = kv_decode_reference(q, k, v, jnp.maximum(lengths, 1) - 1)
    assert got.shape == q.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("length", [1, 100, 256, 300],
                         ids=["one-row", "ragged-first", "whole-first",
                              "ragged-second"])
@pytest.mark.parametrize("heads", ["mha16", "gqa4x7"])
def test_rows_past_a_length_never_reach_a_sum(heads, length):
    """Whatever lies at or past a lane's length (here NaN, in the last
    live block's dead rows and in the blocks behind it) is neither
    scored nor weighted: 0 x NaN would be NaN."""
    kvh, group = HEADS[heads]
    q, k, v, block = _rows(kvh, group, jnp.bfloat16, lanes=1, blocks=3)
    length = length * block // 256
    dead = (jnp.arange(k.shape[1]) >= length)[None, :, None, None]
    got = kv_decode(q, jnp.where(dead, jnp.nan, k),
                    jnp.where(dead, jnp.nan, v), length)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(kv_decode(q, k, v, length)))


@pytest.mark.parametrize("shape,block", [
    ((2048, 16, 128, 2), 256),      # Cerebras-GPT-1.3B: 4 KB a row
    ((16384, 4, 128, 2), 1024),     # SmallThinker's full layers: 1 KB
    ((4096, 1, 128, 2), None),      # Jamba2: 1 MB of rows is all of it
    ((2048, 16, 64, 2), None),      # a head no multiple of 128 wide
    ((2000, 16, 128, 2), None),     # a cache 128 does not divide
    ((2048, 16, 128, 4), 128),      # float32: 8 KB a row
    ((1920, 16, 128, 2), 128),      # 15 x 128: 256 does not divide
    ((2048, 64, 128, 4), 128),      # a row wider than 8 KB: 128 rows
], ids=["cerebras", "smallthinker", "jamba", "d64", "t2000", "float32",
        "t1920", "wide-row"])
def test_the_block_follows_the_rows_shape(shape, block):
    assert kv_block(*shape) == block
    if block is None:
        dtype = jnp.bfloat16 if shape[3] == 2 else jnp.float32
        rows = jnp.zeros((1,) + shape[:3], dtype)
        with pytest.raises(ValueError, match="kv_decode_reference"):
            kv_decode(jnp.zeros((1, shape[1], shape[2]), dtype), rows, rows, 1)


@pytest.mark.parametrize("t,block", [(2048, 256), (16384, 1024), (512, None)])
def test_rows_fetched_counts_the_index_maps_blocks(t, block):
    """The model behind kv.rows_read against the arithmetic of the
    kernel's own index map (_call's `rows`): a lane's distinct block
    indices over the grid's steps, times the block."""
    lengths = np.array([-3, 0, 1, 2, 255, 256, 257, 1023, 1024, 1025, t - 1,
                        t, t + 9])
    clamped = np.clip(lengths, 1, t)
    if block is None:
        assert rows_fetched(lengths, t, None) == len(lengths) * t
        return
    distinct = [len({min(ki, (n - 1) // block) for ki in range(t // block)})
                for n in clamped]
    assert rows_fetched(lengths, t, block) == block * sum(distinct)
    assert rows_fetched(lengths.reshape(1, -1), t, block) \
        == rows_fetched(lengths, t, block)


def _cfg(**kw):
    """Two heads of 128 over a stream of 64, float32: rows of 1 KB, which
    the kernel takes in blocks of 1,024 of a cache of 2,048."""
    kw = dict(dict(vocab_size=64, d_model=64, n_heads=2, attn_head_dim=128,
                   n_layers=2, d_ff=64, max_len=2048), **kw)
    return tf.TransformerConfig(**kw)


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("what", ["window", "toy-width", "short-cache",
                                  "mixed-dtypes"])
def test_decode_attention_keeps_the_xla_text(what):
    """Where the rule does not engage the program is the reference's,
    letter for letter."""
    cfg, window, dt, q_dt = _cfg(), None, jnp.float32, jnp.float32
    t, d = 2048, 128
    if what == "window":
        window = 300
    elif what == "toy-width":
        d = 16
    elif what == "short-cache":
        t = 1024
    else:
        dt = jnp.bfloat16
    q = jax.ShapeDtypeStruct((3, 2, d), q_dt)
    rows = jax.ShapeDtypeStruct((3, t, 2, d), dt)
    pos = jax.ShapeDtypeStruct((3,), jnp.int32)
    assert tf.kv_decode_block(cfg, rows, window, q.dtype) is None
    got = _lowered(lambda q, k, v, p: tf._decode_attention(
        q, {"k": k, "v": v}, p, cfg, window), q, rows, rows, pos)
    want = _lowered(lambda q, k, v, p: kv_decode_reference(
        q, k, v, p, window).astype(q.dtype), q, rows, rows, pos)
    assert got == want


def test_decode_attention_engages_by_what_the_call_holds():
    cfg = _cfg()
    q = jax.ShapeDtypeStruct((3, 2, 128), jnp.float32)
    rows = jax.ShapeDtypeStruct((3, 2048, 2, 128), jnp.float32)
    assert tf.kv_decode_block(cfg, rows, None, q.dtype) == 1024
    for pos in (jax.ShapeDtypeStruct((3,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)):
        text = _lowered(lambda q, k, v, p: tf._decode_attention(
            q, {"k": k, "v": v}, p, cfg), q, rows, rows, pos)
        # interpreted on the CPU: the grid is a loop, no einsum is left
        assert "stablehlo.while" in text and text != _lowered(
            lambda q, k, v, p: kv_decode_reference(q, k, v, p).astype(
                q.dtype), q, rows, rows, pos)
    # the int8 and flash arms return before the rule
    for flag in ("kv_cache_int8", "use_flash_kernel"):
        assert tf.kv_decode_block(_cfg(**{flag: True}), rows) is None
    int8 = _cfg(kv_cache_int8=True)
    cache = jax.eval_shape(lambda: tf.init_cache(int8, 3))[0]
    text = _lowered(lambda q, c, p: tf._decode_attention(q, c, p, int8),
                    q, cache, jax.ShapeDtypeStruct((3,), jnp.int32))
    assert "stablehlo.while" not in text


def test_decode_attention_is_the_reference_at_an_engaged_shape():
    """Through the model's own call, scalar and ragged positions."""
    cfg = _cfg()
    q, k, v, _ = _rows(2, 1, jnp.float32, lanes=3, blocks=2)
    assert k.shape[1] == cfg.max_len
    for pos in (jnp.asarray([0, 1023, 1500], jnp.int32), jnp.int32(1024)):
        got = tf._decode_attention(q, {"k": k, "v": v}, pos, cfg)
        want = kv_decode_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)


@pytest.fixture(scope="module")
def engaged():
    cfg = _cfg()
    return cfg, tf.init_params(cfg, 3)


def test_the_batcher_serves_generates_tokens_at_an_engaged_shape(engaged):
    """batcher == generate() stays structural: ragged [B] positions and
    a scalar go through the same kernel, a lane a grid row."""
    cfg, params = engaged
    prompts = [[5, 9, 2, 44, 17], [3, 1, 4, 1, 5, 9, 2, 6]]
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


def test_a_dispatch_counts_its_contractions_by_the_rule(engaged, telemetry):
    cfg, params = engaged
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.admit([1, 2, 3], 5)
    dispatched = srv.dispatch_count
    srv.step()
    rounds = srv.dispatch_count - dispatched
    assert rounds >= 1
    assert obs.counter("kv.decode_kernel").value == rounds * 2
    assert obs.counter("kv.decode_reference").value == 0
    snap = srv.health_snapshot()
    assert snap["kv.decode_kernel"] == rounds * 2
    assert snap["kv.decode_reference"] == 0
    # no window layer: the rows' counters stay a window model's
    assert "kv.rows_read" not in snap
    toy = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=3, d_ff=64, max_len=32)
    obs.reset()
    srv = ContinuousBatcher(tf.init_params(toy, 0), toy, max_batch=2)
    srv.admit([1, 2, 3], 5)
    dispatched = srv.dispatch_count
    srv.step()
    assert obs.counter("kv.decode_reference").value \
        == (srv.dispatch_count - dispatched) * 3
    assert obs.counter("kv.decode_kernel").value == 0


def test_rows_read_is_what_the_kernel_fetches(telemetry):
    """A full layer beside a window layer at an engaged width: the full
    layer's contraction is the kernel and kv.rows_read counts whole
    blocks of 1,024 up to each of the three lanes' dispatched positions;
    the window layer's ring of 8 keeps the XLA text and counts whole."""
    cfg = _cfg(layer_kinds=("attention", "window"), attn_window=8,
               positions="none")
    srv = ContinuousBatcher(tf.init_params(cfg, 1), cfg, max_batch=3,
                            pipeline_depth=1)
    assert srv._leaf_blocks == [1024, None]
    srv.admit(list(range(1, 21)), 4)
    srv.admit(list(range(1, 8)), 4)
    srv.step()
    assert obs.counter("kv.decode_kernel").value == 1
    assert obs.counter("kv.decode_reference").value == 1
    # every lane's position lies in the first block: one block a lane in
    # the full layer, the ring's 8 rows in the window layer
    assert obs.counter("kv.rows_read").value == 3 * (1024 + 8)
    assert obs.counter("kv.rows_ring").value == 3 * 8
    # a lane's tokens so far: its prompt and the admission's first token
    assert obs.counter("kv.rows_live").value == (21 + 8) + (8 + 8)
    # a lane past the first block fetches two
    srv2 = ContinuousBatcher(tf.init_params(cfg, 1), cfg, max_batch=3,
                             pipeline_depth=1)
    obs.reset()
    srv2.admit(list(np.arange(1100) % 60 + 1), 3)
    srv2.step()
    assert obs.counter("kv.rows_read").value == (2048 + 2 * 1024) + 3 * 8
