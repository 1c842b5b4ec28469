"""Window layers beside full layers through transformer.py and the
ContinuousBatcher at a toy size on the CPU, against their plain reference
(chipbench/reference/smallthinker.py, which imports nothing of the program):
heads of 16 where hidden / heads is 12, 2 K/V heads under 4 query heads, the
pattern full, window, window, window twice with a window of 8 whose K/V rows
are a ring, rotation on the window layers only, 8 ReGLU experts routed 3 a
token from the layer's input by the softmax over the chosen logits. The same
seeded weights on both sides; float32 unless a case says otherwise, where
1e-4 is what sums taken in another order leave (readings 4e-7 to 7e-7) and
a float8 control reads 1e-2 or more."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import smallthinker as ref
from chipbench.reference.common import fp8_operand
from chipbench.runners import serve_smallthinker
from mxnet_tpu.models import serving, transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

TINY = json.load(open(os.path.join(
    os.path.dirname(__file__), "bench_harness", "tiny", "smallthinker.json")))
WINDOW = TINY["sliding_window_size"]
TOL = 1e-4


def _sides(seed, dtype=jnp.float32, config=TINY):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(config, seed, dtype)
    cfg = dataclasses.replace(serve_smallthinker.program_config(config),
                              dtype=dtype)
    return ref.as_tree(weights, config), cfg, weights


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def _reference_logits(weights, toks, config=TINY, q=ref.exact):
    """The reference's full forward over toks (padded to its width)."""
    padded = np.zeros((ref.padded_width(len(toks), config),), np.int32)
    padded[: len(toks)] = toks
    return np.asarray(ref.forward_row(weights, jnp.asarray(padded), config,
                                      q))[: len(toks)]


def _one_kind(kind, **kw):
    """A one-layer model of `kind` at the toy widths: what its output
    sees is the layer's own span."""
    config = dict(TINY, num_hidden_layers=1,
                  rope_layout=[int(kind == "window")],
                  sliding_window_layout=[int(kind == "window")], **kw)
    return _sides(2, config=config) + (config,)


@pytest.fixture(scope="module")
def sides():
    return _sides(5)


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind (see
    tests/test_kimi_linear.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


@pytest.fixture
def small_blocks(monkeypatch):
    """The blocked contraction in blocks of 4 queries over 8 rows, and
    every chunk through it: the toy's 64 positions are then 16 blocks of
    queries, each over the few blocks of rows it may see, as the real
    cell's 8,192 are 32 of 256 over blocks of 512."""
    monkeypatch.setattr(tf, "ATTN_QUERY_BLOCK", 4)
    monkeypatch.setattr(tf, "ATTN_KEY_BLOCK", 8)
    monkeypatch.setattr(tf, "ATTN_PLANE_ELEMS", 0)
    tf._PREFILL_JIT_CACHE.clear()
    yield
    tf._PREFILL_JIT_CACHE.clear()


# ---------------------------------------------------- the configuration ---

def test_the_toy_configuration_states_the_architecture():
    cfg = serve_smallthinker.program_config(TINY)
    assert tf._layer_kinds(cfg) == ("attention", "window", "window",
                                    "window") * 2
    assert tf._layer_rope(cfg) == (False, True, True, True) * 2
    assert (tf._head_dim(cfg), cfg.d_model // cfg.n_heads, tf._kvh(cfg),
            tf._window(cfg)) == (16, 12, 2, 8)
    assert tf._experts(cfg) == (8, 3, 0, 8, 32)
    assert (cfg.ffn, cfg.expert_scoring, cfg.router_input, cfg.tied_head) \
        == ("gated_relu", "softmax_topk", "layer", False)
    assert not tf._learned_pos(cfg)
    # a configuration that holds the new fields still hashes by value
    assert dataclasses.astuple(cfg) == dataclasses.astuple(
        serve_smallthinker.program_config(TINY))


def test_the_programs_own_init_makes_the_runners_tree(sides):
    params, cfg, _ = sides
    mine = tf.init_params(cfg, 0)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), mine) \
        == jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    assert mine["layers"][1]["wq"].shape == (48, 4, 16)
    assert mine["layers"][1]["wo"].shape == (4, 16, 48)
    assert "pos" not in mine and "w3" in mine["layers"][0]


def test_a_lane_holds_a_ring_in_its_window_layers(sides):
    """Two kinds of K/V state in one lane: max_len rows in the full
    layers, a ring of the window's rows in the others (and no more than
    max_len of them)."""
    _, cfg, _ = sides
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 3))
    assert [layer["k"].shape for layer in row] \
        == [(3, 64, 2, 16), (3, 8, 2, 16), (3, 8, 2, 16), (3, 8, 2, 16)] * 2
    short = dataclasses.replace(cfg, max_len=6)
    assert jax.eval_shape(lambda: tf.init_cache(short, 1))[1]["v"].shape \
        == (1, 6, 2, 16)


@pytest.mark.parametrize("bad,match", [
    (dict(attn_window=None), "attn_window"),
    (dict(attn_window=0), "attn_window"),
    (dict(use_flash_kernel=True), "use_flash_kernel"),
    (dict(rope_layers=(True,) * 3), "rope_layers"),
    (dict(rope=False, positions="none"), "rope_layers"),
    (dict(layer_kinds=("window", "ring") * 4), "'window'"),
    (dict(expert_scoring="topk"), "softmax_topk"),
    (dict(router_input="mixer"), "router_input"),
    (dict(router_input="layer", hc_mult=4), "hc_mult=4"),
    (dict(ffn="reglu"), "gated_relu"),
])
def test_a_configuration_that_cannot_be_built_is_refused(sides, bad, match):
    cfg = dataclasses.replace(sides[1], **bad)
    with pytest.raises(ValueError, match=match):
        tf.forward(tf.init_params(cfg, 0), jnp.zeros((1, 8), jnp.int32), cfg)


# ------------------------------------------ the program and the reference

@pytest.mark.parametrize("dtype,stat,tol,why", [
    (jnp.float32, jnp.max, TOL, "float32 both sides, sums in another order"),
    # the MEAN gap over all logits: bfloat16 through 8 layers reads
    # 0.0020-0.0072 over seeds 1-6, float8 operands 0.028-0.038. The
    # widest single logit has no room (0.10-0.28 against 0.24-0.36): a
    # pick of 3 in 8 that bfloat16 orders otherwise than float32 moves a
    # whole expert of this toy model, as in tests/test_xing4.py
    (jnp.bfloat16, jnp.mean, 0.015,
     "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, stat, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 64)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = _reference_logits(weights, toks)
    gap = float(stat(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)
    control = _reference_logits(weights, toks, q=fp8_operand)
    assert float(stat(jnp.abs(control - want))) > tol


@pytest.mark.parametrize("t_p,width", [(5, 8), (21, 32), (40, 40)],
                         ids=["inside-a-window", "past-two", "past-five"])
def test_prefill_then_decode_through_the_ring_equals_the_full_forward(
        sides, t_p, width):
    """The admission path (a bucket wider than the prompt whose padding
    must not reach the ring, the logits of the last real row) and then
    one position after another to position 62, past seven windows of 8,
    each lane's row at its own position: logits, not tokens."""
    params, cfg, weights = sides
    toks = _tokens(4, 63)
    want = _reference_logits(weights, toks)
    padded = np.zeros((1, width), np.int32)
    padded[0, :t_p] = toks[:t_p]
    logits, cache = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(t_p - 1)))(
            params, tf.init_cache(cfg, 1), jnp.asarray(padded))
    np.testing.assert_allclose(logits[0], want[t_p - 1], atol=TOL)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(t_p, 63):
        logits, cache = step(params, cache, jnp.asarray(toks[t:t + 1]),
                             jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=TOL)
    control = _reference_logits(weights, toks, q=fp8_operand)
    assert np.abs(control - want).max() > 100 * TOL


@pytest.mark.parametrize("widths", [
    [20, 44], [5, 7, 3, 9, 40], [13, 6, 45], [64]],
    ids=["wider-than-the-window", "narrower", "across-the-wrap", "one-call"])
@pytest.mark.parametrize("blocks", ["as-sized", "small-blocks"])
def test_an_admission_in_chunks_equals_the_one_call(sides, widths, blocks,
                                                    request):
    """A chunk's early queries must still see rows its late ones
    overwrite in a ring of exactly the window: a chunk wider than the
    window (20 and 44 rows into 8 slots), chunks narrower than it, and
    one that starts at slot 5 and wraps (13 + 6 = 19 = 2 x 8 + 3); every
    row's logits equal the reference's full forward, so they equal the
    one call's. `small-blocks` runs every contraction in many blocks."""
    if blocks == "small-blocks":
        request.getfixturevalue("small_blocks")
    params, cfg, weights = sides
    toks = _tokens(7, 64)
    want = _reference_logits(weights, toks)
    cache, at = tf.init_cache(cfg, 1), 0
    for width in widths:
        logits, cache = tf._jitted_prefill_chunk(cfg)(
            params, cache, jnp.asarray(toks[None, at:at + width]),
            jnp.int32(at))
        np.testing.assert_allclose(logits[0], want[at:at + width], atol=TOL)
        at += width


def test_prefill_at_position_zero_and_chunks_behind_it_equal_the_forward(
        sides, small_blocks):
    """generate()'s path: the whole prompt in one call at position 0 (the
    full layers' self-attention in blocks too, as a prompt of 8,192 at
    the real widths), the ring left as the chunks behind it need it."""
    params, cfg, weights = sides
    toks = _tokens(6, 60)
    want = _reference_logits(weights, toks)
    last, cache = tf._jitted_prefill(cfg)(params, tf.init_cache(cfg, 1),
                                          jnp.asarray(toks[None, :23]))
    np.testing.assert_allclose(last[0], want[22], atol=TOL)
    logits, cache = tf._jitted_prefill_chunk(cfg)(
        params, cache, jnp.asarray(toks[None, 23:37]), jnp.int32(23))
    np.testing.assert_allclose(logits[0], want[23:37], atol=TOL)
    logits, _ = tf._jitted_decode_step(cfg)(
        params, cache, jnp.asarray(toks[37:38]), jnp.int32(37))
    np.testing.assert_allclose(logits[0], want[37], atol=TOL)


def test_a_plane_under_the_limit_keeps_the_one_plane_form(sides):
    """A full layer's chunk contracts in blocks only past
    ATTN_PLANE_ELEMS score entries: the accepted configurations' chunks
    stay under it (tests/test_program_text.py holds their text)."""
    _, cfg, _ = sides
    assert not tf._attn_blocked(2048, 2048, dataclasses.replace(
        cfg, n_heads=16))                    # cerebras-gpt-1.3b
    assert not tf._attn_blocked(2048, 4096, dataclasses.replace(
        cfg, n_heads=20))                    # jamba2-3b
    real = dataclasses.replace(cfg, n_heads=28)
    assert tf._attn_blocked(8192, 16384, real)
    assert not tf._attn_blocked(8, 16384, real)


# ------------------------------------------------- the window, by position

def _last_logits(entry, params, cfg, toks):
    """The logits behind the last of toks through one entry point."""
    toks = jnp.asarray(toks)
    if entry == "forward":
        return tf.forward(params, toks[None], cfg)[0, -1]
    if entry == "chunks":
        cache, at = tf.init_cache(cfg, 1), 0
        for width in (11, len(toks) - 11):
            logits, cache = tf.prefill_chunk(
                params, cache, toks[None, at:at + width], jnp.int32(at), cfg)
            at += width
        return logits[0, -1]
    logits, cache = tf.prefill(params, tf.init_cache(cfg, 1),
                               toks[None, :3], cfg)
    for t in range(3, len(toks)):
        logits, cache = tf.decode_step(params, cache, toks[t:t + 1],
                                       jnp.int32(t), cfg)
    return logits[0]


@pytest.mark.parametrize("entry", ["forward", "chunks", "decode"])
def test_a_window_query_sees_its_own_and_the_seven_positions_before(entry):
    """One window layer, so the last row's logits see exactly the layer's
    span: the token 8 positions back (just outside) changes nothing, the
    token 7 back (just inside) does."""
    params, cfg, _, _ = _one_kind("window")
    toks = _tokens(8, 30)
    base = _last_logits(entry, params, cfg, toks)
    for back, seen in ((WINDOW, False), (WINDOW + 3, False),
                       (WINDOW - 1, True), (0, True)):
        moved = toks.copy()
        moved[len(toks) - 1 - back] += 1
        gap = float(jnp.abs(_last_logits(entry, params, cfg, moved)
                            - base).max())
        assert (gap > 1e-3) == seen, (back, gap)


def test_a_full_layer_has_no_positions_and_a_window_layer_rotates():
    """A full layer attends without positional encoding: its last row's
    logits are unchanged (to rounding) when two earlier tokens swap
    places, and the keys it caches are the same wherever a chunk starts;
    a window layer's logits change with the order and its cached keys
    with the start."""
    toks = _tokens(9, 8)
    swapped = toks.copy()
    swapped[[2, 5]] = toks[[5, 2]]
    for kind, same in (("attention", True), ("window", False)):
        params, cfg, _, _ = _one_kind(kind)
        gap = float(jnp.abs(
            tf.forward(params, jnp.asarray(swapped)[None], cfg)[0, -1]
            - tf.forward(params, jnp.asarray(toks)[None], cfg)[0, -1]).max())
        assert (gap < 1e-5) == same, (kind, gap)
        keys = []
        for start in (0, 8):
            _, cache = tf.prefill_chunk(
                params, tf.init_cache(cfg, 1), jnp.asarray(toks)[None],
                jnp.int32(start), cfg)
            # 8 rows from position `start`: there in a full layer's
            # rows, from slot start mod 8 = 0 in a ring of 8
            at = start if kind == "attention" else 0
            keys.append(np.asarray(cache[0]["k"])[0, at:at + 8])
        assert np.allclose(*keys, atol=1e-6) == same, kind


def test_a_lanes_next_occupant_never_sees_the_last_ones_rows(sides):
    """Every contraction masks by the absolute position a slot holds: a
    short request's logits are the same from a zeroed ring and from one
    a long request left full."""
    params, cfg, weights = sides
    long, short = _tokens(10, 50), _tokens(11, 6)
    want = _reference_logits(weights, short)
    _, used = tf._jitted_prefill_chunk(cfg)(
        params, tf.init_cache(cfg, 1), jnp.asarray(long[None]), jnp.int32(0))
    assert float(jnp.abs(used[1]["k"]).min()) > 0      # the ring is full
    logits, cache = tf._jitted_prefill_chunk(cfg)(
        params, used, jnp.asarray(short[None, :4]), jnp.int32(0))
    np.testing.assert_allclose(logits[0], want[:4], atol=TOL)
    for t in (4, 5):
        logits, cache = tf._jitted_decode_step(cfg)(
            params, cache, jnp.asarray(short[t:t + 1]), jnp.int32(t))
        np.testing.assert_allclose(logits[0], want[t], atol=TOL)


# ------------------------------------------------- the router, the experts

def _loads(params, cfg, x):
    """The per-expert token counts of layer 0 for the rows x [1, T, d],
    around a mixer that halves what it reads."""
    loads = []
    tf._layer(x, params["layers"][0], "attention", cfg,
              lambda kind, h, p, state, rotate: (0.5 * h, state),
              loads=loads)
    return np.asarray(loads[0])


def test_the_router_reads_the_layers_input(sides):
    """A change of ln1's weight moves what the mixer reads and so what
    the experts read, but not the picks: the router scored the layer's
    input before the norm. With router_input="ffn" the same change moves
    them."""
    params, cfg, _ = sides
    x = jnp.asarray(np.random.RandomState(0).randn(1, 40, 48), jnp.float32)
    scaled = jax.tree.map(lambda v: v, params)
    scaled["layers"][0] = dict(
        params["layers"][0],
        ln1=jnp.asarray(np.random.RandomState(1).rand(48) * 4, jnp.float32))
    assert (_loads(params, cfg, x) == _loads(scaled, cfg, x)).all()
    assert _loads(params, cfg, x).sum() == 40 * 3
    late = dataclasses.replace(cfg, router_input="ffn")
    assert (_loads(params, late, x) != _loads(scaled, late, x)).any()


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer(sides):
    """The guide's share test: the 8 experts held two a chip by four
    chips (experts_held), each routing over all 8 from the layer's input
    and computing its own experts' part; the parts summed are the uncut
    reference's expert layer."""
    params, cfg, weights = sides
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(24, 48), jnp.float32)     # the layer's input
    u = jnp.asarray(rng.randn(24, 48), jnp.float32)     # what the FFN reads
    p = params["layers"][2]
    want = ref.experts(u, ref.route(x, p, ref.exact, 3), p, ref.exact)
    total = 0
    for first in range(0, 8, 2):
        share = dict(p, **{k: p[k][first:first + 2]
                           for k in ("w1", "w3", "w2")})
        total = total + tf._expert_ffn(
            u[None], share, dataclasses.replace(cfg, experts_held=(first, 2)),
            None, route_from=x[None])[0]
    np.testing.assert_allclose(total, want, atol=1e-5)
    whole = tf._expert_ffn(u[None], p, cfg, None, route_from=x[None])[0]
    np.testing.assert_allclose(whole, want, atol=1e-5)


# ------------------------------------------------------------- refusals ---

def _mesh(**axes):
    from jax.sharding import Mesh
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


@pytest.mark.parametrize("what,call", [
    ("paged", lambda p, c: ContinuousBatcher(p, c, max_batch=2, paged=True)),
    ("spec_k", lambda p, c: ContinuousBatcher(p, c, max_batch=2, spec_k=2)),
    ("kv_cache_int8", lambda p, c: ContinuousBatcher(
        p, dataclasses.replace(c, kv_cache_int8=True), max_batch=2)),
    ("kv_cache_int8", lambda p, c: tf.init_cache(
        dataclasses.replace(c, kv_cache_int8=True), 1)),
    ("paged KV pool", lambda p, c: tf.init_paged_cache(c, 8, 4)),
    ("speculative verification", lambda p, c: tf.verify_chunk(
        p, tf.init_cache(c, 1), jnp.zeros((1, 3), jnp.int32),
        jnp.zeros((1,), jnp.int32), c)),
    ("speculative decoding", lambda p, c: tf.speculative_generate(
        p, p, jnp.zeros((1, 4), jnp.int32), 4, c, c)),
    ("mesh-sharded forward", lambda p, c: tf.forward(
        p, jnp.zeros((2, 8), jnp.int32), c, mesh=_mesh(dp=2))),
    ("shard_params", lambda p, c: tf.shard_params(p, c, _mesh(tp=2))),
    ("shard_cache", lambda p, c: tf.shard_cache(
        tf.init_cache(c, 2), c, _mesh(dp=2))),
])
def test_what_cannot_carry_a_ring_refuses_the_kind_by_name(sides, what, call):
    params, cfg, _ = sides
    with pytest.raises(ValueError, match="'window'") as e:
        call(params, cfg)
    assert what in str(e.value) and "ring of K/V rows" in str(e.value)


# ------------------------------------------------------------- batcher ---

def _solo(params, cfg, prompt, n_new):
    out = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new, cfg)
    return [int(t) for t in np.asarray(out)[0]]


@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth1"])
def test_a_short_request_reuses_the_lane_a_long_one_left(sides, kw):
    """Three requests on two lanes: the long one (past five windows)
    fills its rings, and the short one admitted behind it into the same
    lane equals solo generate() token for token, as do the others."""
    params, cfg, _ = sides
    rng = np.random.RandomState(9)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((30, 14), (5, 40), (4, 9))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, **kw)
    got, order = srv.run(jobs)
    assert len(got) == 3
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _solo(params, cfg, prompt, n_new)


def test_beam_search_regathers_a_ring_like_any_lanes_rows(sides):
    """The beams' re-gather moves a ring as it moves any batch-first
    leaf: at beam 1 the sequence is greedy generate()'s."""
    params, cfg, _ = sides
    prompt = list(_tokens(3, 11))
    seqs, _ = tf.beam_search(params, jnp.asarray([prompt], jnp.int32), 20,
                             cfg, beam=1)
    assert [int(t) for t in np.asarray(seqs)[0, 0]] \
        == _solo(params, cfg, prompt, 20)


def test_the_batchers_streams_follow_the_references_logits(sides):
    """Logits, not tokens: every served token's reference logit lies
    within 1e-3 of the reference's best at its position (float32; a
    near-tie may tip either way, a wrong row could not hide there)."""
    params, cfg, weights = sides
    rng = np.random.RandomState(21)
    jobs = [(list(rng.randint(1, 256, n)), m) for n, m in ((19, 30), (7, 50))]
    got, order = ContinuousBatcher(params, cfg, max_batch=2).run(jobs)
    for (prompt, n_new), rid in zip(jobs, order):
        toks = np.asarray(got[rid], np.int32)
        served, _ = ref.stream_gaps(weights, TINY, len(prompt), toks)
        assert len(served) == n_new and served.max() < 1e-3


@pytest.fixture
def chunks_of_16(monkeypatch):
    """An admission's prefill in whole chunks of 16 tokens at the toy
    width (one stream of 48), two windows wide, as the real one's are
    8,192 against a window of 4,096."""
    monkeypatch.setattr(serving, "PREFILL_CHUNK_ELEMS", 16 * 48)


def test_an_admission_in_chunks_and_a_cached_prefix_run_through_the_ring(
        sides, chunks_of_16):
    """_prefill_rows: 37 tokens are two chunks of 16 and a rest of 5 in
    its bucket of 8, whose padding stays out of the ring; a cached
    prefix's row holds only its last 8 rows in a window layer and the
    suffix continues from them; a continuation re-prefills through the
    same path. Each equals solo generate()."""
    params, cfg, _ = sides
    assert serving.prefill_widths(cfg, 37) == [16, 16, 8]
    rng = np.random.RandomState(31)
    prompt = list(rng.randint(1, 256, 37))
    want = _solo(params, cfg, prompt, 12)
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    got, order = srv.run([(prompt, 12)])
    assert list(got[order[0]]) == want
    assert srv.cache_prefix(prompt[:21]) == 21
    row = srv._prefix_cache[tuple(prompt[:21])][0]
    assert row[1]["k"].shape[1] == WINDOW
    got, order = srv.run([(prompt, 12)])
    assert list(got[order[0]]) == want
    rid = srv.admit_continuation(want[:40], 9, emitted=3)
    done = {}
    while rid not in done:
        done.update(srv.step())
    assert list(done[rid]) == want


# ------------------------------------------------- scopes and counters ---

def test_the_contractions_carry_their_scopes(sides):
    params, cfg, _ = sides
    decode = jax.jit(lambda p, c, t: tf.decode_step(
        p, c, t, jnp.int32(3), cfg)).lower(
            params, tf.init_cache(cfg, 2),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    chunk = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg)).lower(
            params, tf.init_cache(cfg, 1),
            jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    for text in (decode, chunk):
        for scope in ("mx.attn.window", "mx.attn.full", "mx.moe.route",
                      "mx.moe.experts"):
            assert scope in text
    plain = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, max_len=32)
    text = jax.jit(lambda p, c, t: tf.decode_step(
        p, c, t, jnp.int32(3), plain)).lower(
            tf.init_params(plain, 0), tf.init_cache(plain, 2),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    assert "mx.attn.full" in text and "mx.attn.window" not in text


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["two-in-flight", "depth1"])
def test_rounds_count_the_rows_they_read(sides, telemetry, loop):
    """A round reads, a lane with a request or not, 64 rows in each of
    the two full layers and the 8 of a ring in each of the six window
    layers: 176 a lane, 48 of them in rings. A live lane's masks admit
    its positions so far in a full layer and no more than 8 of them in a
    window layer. The gauge counts a ring at its own rows."""
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=3, **loop)
    srv.admit(list(_tokens(15, 13)), 20)
    srv.admit(list(_tokens(16, 5)), 20)
    each = 2 * 2 * 16 * 4                     # k and v, 2 heads of 16
    assert srv.health_snapshot()["serving.kv_bytes"] \
        == each * (2 * (14 + 6) + 6 * (8 + 6))
    synced, live = 0, 0
    for _ in range(4):
        held = [len(r.tokens) for r in srv._slots if r is not None]
        before = obs.counter("kv.rows_read").value
        srv.step()
        if obs.counter("kv.rows_read").value > before:
            synced += 1
            live += sum(2 * n + 6 * min(n, 8) for n in held)
    assert synced >= 3
    assert obs.counter("kv.rows_read").value == synced * 3 * 176
    assert obs.counter("kv.rows_ring").value == synced * 3 * 48
    # what a round was dispatched with is what it is counted with; the
    # lanes' tokens at the step that synced it are at most a round ahead
    assert 0 < obs.counter("kv.rows_live").value <= live
    snap = srv.health_snapshot()
    assert snap["kv.rows_read"] == synced * 3 * 176
    assert snap["kv.rows_ring"] * 176 == snap["kv.rows_read"] * 48
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    frozen = dict(obs.counters())
    srv.step()
    assert {k: c.value for k, c in obs.counters().items()} \
        == {k: c.value for k, c in frozen.items()}


def test_a_model_without_a_window_counts_no_kv_rows(telemetry):
    cfg = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=1, d_ff=64, max_len=32)
    srv = ContinuousBatcher(tf.init_params(cfg, 0), cfg, max_batch=2)
    srv.admit([1, 2, 3], 4)
    srv.step()
    # its contractions are counted (kv.decode_reference at this toy
    # width: tests/test_kv_decode.py), its rows are not
    assert not any(name.startswith("kv.rows") for name in obs.counters())
    assert "kv.rows_read" not in srv.health_snapshot()
    held = sum(len(r.tokens) for r in srv._slots if r is not None)
    assert srv.health_snapshot()["serving.kv_bytes"] \
        == held * 2 * 2 * 16 * 4             # k and v, 2 heads, float32
