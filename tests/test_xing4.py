"""The four-stream residual frame (manifold-constrained hyper-connections,
`TransformerConfig.hc_mult`) through transformer.py and the
ContinuousBatcher at a toy size on the CPU, against its plain reference
(chipbench/reference/xing4.py, which imports nothing of the program): every
mixer latent attention, a leading dense layer, routed experts all held, and
around every mixer and every FFN the frame. The same seeded weights on both
sides; float32 unless a case says otherwise."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import manifest
from chipbench.reference import xing4 as ref
from chipbench.reference.common import fp8_operand
from chipbench.runners import serve_xing4
from mxnet_tpu.models import serving, transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

TINY = json.load(open(os.path.join(
    os.path.dirname(__file__), "bench_harness", "tiny", "xing4.json")))
MAN = manifest.Manifest()
REAL = MAN.config_of(MAN.cell("xing4.0-29b-a4b-serve-rag32"))


def _sides(seed, dtype=jnp.float32, config=TINY):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(config, seed, dtype)
    cfg = dataclasses.replace(serve_xing4.program_config(config), dtype=dtype)
    return serve_xing4.program_params(weights, config), cfg, weights


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def _reference_logits(weights, toks, config=TINY, q=ref.exact):
    """The reference's full forward over toks (padded to its width)."""
    width = ref.padded_width(len(toks), config)
    padded = np.zeros((width,), np.int32)
    padded[: len(toks)] = toks
    return ref.forward_row(weights, jnp.asarray(padded), config,
                           q)[: len(toks)]


def _alone(params, cfg, prompt, n_new):
    srv = ContinuousBatcher(params, cfg, max_batch=1, pipeline_depth=1)
    got, order = srv.run([(prompt, n_new)])
    return list(got[order[0]])


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
def chunks_of_8(monkeypatch):
    """An admission's prefill in whole chunks of 8 tokens at the toy
    width (four streams of 64), as the real one's are 2,048 at four of
    3,584."""
    monkeypatch.setattr(serving, "PREFILL_CHUNK_ELEMS", 8 * 4 * 64)


def _attention_model(**kw):
    """A small model of "attention" layers in the four-stream frame: the
    kind paged blocks, speculation and int8 K/V take."""
    cfg = tf.TransformerConfig(
        vocab_size=256, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=64, rope=True, hc_mult=4, **kw)
    return tf.init_params(cfg, 3), cfg


# ---------------------------------------------------- the configuration ---

def test_the_toy_configuration_states_the_architecture():
    cfg = serve_xing4.program_config(TINY)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_clamp_min, cfg.hc_clamp_max) == (4, 20, 1e-6, -30.0, 30.0)
    assert tf._layer_kinds(cfg) == ("mla",) * 3
    assert tf._experts(cfg) == (8, 4, 0, 8, 32)
    assert cfg.first_dense_layers == 1 and cfg.mla_q_rank == 24
    real = serve_xing4.program_config(REAL)
    assert (real.hc_mult, real.d_model, real.n_layers) == (4, 3584, 5)
    assert tf._experts(real) == (64, 4, 0, 64, 1024)
    # a configuration that holds the new fields still hashes by value
    assert dataclasses.astuple(cfg) == dataclasses.astuple(
        serve_xing4.program_config(TINY))


def test_a_configuration_that_states_no_streams_has_no_frame_leaves():
    cfg = dataclasses.replace(serve_xing4.program_config(TINY), hc_mult=None)
    leaves = set(tf.init_params(cfg, 0)["layers"][0])
    assert not any(k.startswith("hc") for k in leaves)
    with_frames = set(tf.init_params(
        serve_xing4.program_config(TINY), 0)["layers"][0])
    assert with_frames - leaves == {
        "%s_%s" % (f, k) for f in ("hc1", "hc2") for k in ("phi", "b", "a")}
    x = jnp.ones((2, 3, 64))
    assert tf._streams_in(x, cfg) is x and tf._streams_out(x, cfg) is x


@pytest.mark.parametrize("bad", [
    {"hc_mult": 1}, {"hc_mult": 2.5}, {"hc_mult": 4, "hc_sinkhorn_iters": 0},
    {"hc_mult": 4, "hc_clamp_min": 30.0}])
def test_a_frame_that_cannot_be_built_is_refused(bad):
    cfg = dataclasses.replace(serve_xing4.program_config(TINY), **bad)
    with pytest.raises(ValueError, match="hc_mult"):
        tf.init_params(cfg, 0)


# ------------------------------------------------------ the three weights

def _weights_of(x, p, f, cfg):
    return [np.asarray(h) for h in tf._hc_weights(
        x, p[f + "_phi"], p[f + "_b"], p[f + "_a"], cfg)]


@pytest.mark.parametrize("shape", [(4, 2, 9, 64), (4, 5, 64)],
                         ids=["a-chunk", "decodes-row"])
def test_the_mixing_weights_lie_where_the_equations_put_them(sides, shape):
    """H_pre in (0, 1), H_post in (0, 2); H_res's rows sum to 1 (the last
    division) and its columns to 1 within what 20 iterations leave (5e-4
    at these gains, 0.02 at gains of 1); the same function for a chunk
    [n, B, C, d] and for decode's row [n, B, d]."""
    params, cfg, _ = sides
    x = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
    for f in ("hc1", "hc2"):
        pre, post, res = _weights_of(x, params["layers"][1], f, cfg)
        assert pre.shape == shape[1:-1] + (4,) and res.shape \
            == shape[1:-1] + (4, 4)
        assert 0 < pre.min() and pre.max() < 1
        assert 0 < post.min() and post.max() < 2
        assert res.min() > 0
        np.testing.assert_allclose(res.sum(-1), 1.0, atol=2e-6)
        np.testing.assert_allclose(res.sum(-2), 1.0, atol=5e-4)


def test_sinkhorn_is_the_iteration_written_out():
    m = np.exp(np.random.RandomState(2).randn(3, 4, 4)).astype(np.float32)
    want = m.copy()
    for _ in range(20):
        want = want / (want.sum(-2, keepdims=True) + 1e-6)
        want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(tf._sinkhorn(jnp.asarray(m), 20, 1e-6), want,
                               rtol=1e-5)
    # one iteration is columns first, then rows: rows sum to 1, columns not
    one = np.asarray(tf._sinkhorn(jnp.asarray(m), 1, 1e-6))
    np.testing.assert_allclose(one.sum(-1), 1.0, atol=1e-5)
    assert np.abs(one.sum(-2) - 1.0).max() > 0.01


def test_the_clamp_is_on_the_logits_before_exp(sides):
    """A gain that would send a logit to 200 leaves every weight finite:
    exp sees at most 30."""
    params, cfg, _ = sides
    p = dict(params["layers"][0])
    p["hc1_a"] = jnp.asarray([1.0, 1.0, 200.0], jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(4, 1, 6, 64), jnp.float32)
    _, _, res = _weights_of(x, p, "hc1", cfg)
    assert np.isfinite(res).all()
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_seeded_weights_mix_away_from_identity_and_uniform(side):
    """The stated range: over the tokens of a seeded forward, H_res's
    largest entry lies in 0.4-0.9 (the identity has 1, the uniform matrix
    0.25), stream 0 is read with a mean weight of 0.8-0.95 and the
    others of under 0.2, and written with about 1 and under 0.4.
    `init_params` and the benchmark's seeded weights use the same
    gains and biases."""
    if side == "program":
        cfg = dataclasses.replace(serve_xing4.program_config(TINY))
        p = tf.init_params(cfg, 11)["layers"][2]
    else:
        params, cfg, _ = _sides(11)
        p = params["layers"][2]
    x = jnp.asarray(np.random.RandomState(4).randn(4, 1, 256, 64),
                    jnp.float32)
    pre, post, res = _weights_of(x, p, "hc2", cfg)
    top = res.max(axis=(-1, -2))
    assert 0.4 < np.percentile(top, 1) and np.percentile(top, 99) < 0.9
    assert 0.6 < pre[..., 0].min() and 0.8 < pre[..., 0].mean() < 0.95
    assert pre[..., 1:].mean() < 0.2 and pre[..., 1:].max() < 0.45
    assert 0.8 < post[..., 0].mean() < 1.2 and post[..., 1:].mean() < 0.4


def test_the_program_and_the_reference_make_the_same_weights(sides):
    params, cfg, weights = sides
    x = np.random.RandomState(5).randn(7, 4, 64).astype(np.float32)
    p = {k: weights["layers.1." + k].astype(jnp.float32)
         for k in ref.layer_leaves(TINY, 1)}
    want = ref.mixing(jnp.asarray(x), p, "hc1", ref.exact, 1e-6,
                      ref.hc_of(TINY))
    got = _weights_of(jnp.asarray(x.transpose(1, 0, 2)),
                      params["layers"][1], "hc1", cfg)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-6)


# ------------------------------------------- logits against the reference

# what float8 operands do to the same logits, the step below bfloat16:
# every tolerance below lies under it
def _float8_gap(weights, toks, config=TINY, stat=jnp.max):
    return float(stat(jnp.abs(
        _reference_logits(weights, toks, config, fp8_operand)
        - _reference_logits(weights, toks, config))))


@pytest.mark.parametrize("dtype,stat,tol,why", [
    (jnp.float32, jnp.max, 1e-4, "float32 both sides, sums in another order"),
    # the MEAN gap over all logits: bfloat16 through 3 layers of four
    # streams reads 0.005-0.010 over seeds 1-6, float8 operands
    # 0.039-0.049. The widest single logit has no room (0.14-0.32 against
    # 0.32-0.46): a pick that bfloat16 orders otherwise than float32 moves
    # a whole expert of this toy model, as in tests/test_kimi_k2.py
    (jnp.bfloat16, jnp.mean, 0.02,
     "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, stat, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 64)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = ref.forward_row(weights, jnp.asarray(toks), TINY)
    gap = float(stat(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)
    assert _float8_gap(weights, toks, stat=stat) > tol


@pytest.mark.parametrize("dense", [2, 1], ids=["as-published", "the-cut"])
@pytest.mark.parametrize("t_p,width", [(19, 32), (40, 40)])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        dense, t_p, width):
    """The admission path (a bucket wider than the prompt, the logits of
    the last real row) and then one position after another, the four
    streams made anew from the token at every call: logits, not tokens,
    at `first_k_dense_replace` 2 as published and at the cell's 1. 1e-4:
    float32, the absorbed and the chunked forms against the reference's
    full attention."""
    config = dict(TINY, first_k_dense_replace=dense)
    params, cfg, weights = _sides(5, config=config)
    assert cfg.first_dense_layers == dense
    toks = _tokens(4, 56)
    want = _reference_logits(weights, toks, config)
    padded = np.zeros((1, width), np.int32)
    padded[0, :t_p] = toks[:t_p]
    logits, cache = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(t_p - 1)))(
            params, tf.init_cache(cfg, 1), jnp.asarray(padded))
    np.testing.assert_allclose(logits[0], want[t_p - 1], atol=1e-4)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(t_p, 56):
        logits, cache = step(params, cache, jnp.asarray(toks[t:t + 1]),
                             jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=1e-4)
    assert _float8_gap(weights, toks, config) > 1e-4


def test_prefill_at_position_zero_and_a_chunk_behind_it_equal_the_forward(
        sides):
    params, cfg, weights = sides
    toks = _tokens(6, 48)
    want = _reference_logits(weights, toks)
    last, cache = tf._jitted_prefill(cfg)(params, tf.init_cache(cfg, 1),
                                          jnp.asarray(toks[None, :23]))
    np.testing.assert_allclose(last[0], want[22], atol=1e-4)
    logits, _ = tf._jitted_prefill_chunk(cfg)(
        params, cache, jnp.asarray(toks[None, 23:]), jnp.int32(23))
    np.testing.assert_allclose(logits[0], want[23:], atol=1e-4)


def test_a_lane_keeps_latent_rows_only(sides):
    """The four streams live across depth, inside a program: a lane's row
    is what it is without them."""
    _, cfg, _ = sides
    one = dataclasses.replace(cfg, hc_mult=None)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), jax.eval_shape(
        lambda: tf.init_cache(cfg, 2))) == jax.tree.map(
            lambda x: (x.shape, x.dtype),
            jax.eval_shape(lambda: tf.init_cache(one, 2)))


def test_a_buckets_padding_stays_in_its_own_rows(sides):
    """The frame is per token: whatever the bucket's padded rows hold,
    the real rows' latents and the last real row's logits are the same
    to the bit."""
    params, cfg, _ = sides
    toks = _tokens(7, 11)
    fn = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(10)))
    out = []
    for fill in (0, 201):
        padded = np.full((1, 16), fill, np.int32)
        padded[0, :11] = toks
        out.append(fn(params, tf.init_cache(cfg, 1), jnp.asarray(padded)))
    (la, ca), (lb, cb) = out
    assert np.array_equal(la, lb)
    for a, b in zip(ca, cb):
        for name in ("c", "kr"):
            assert np.array_equal(a[name][0, :11], b[name][0, :11])
            assert not np.array_equal(a[name][0, 11:16], b[name][0, 11:16])


def test_a_bfloat16_stream_stays_near_the_float32_one():
    """The program against itself: the streams, weights and products in
    bfloat16, every H in float32 either way. The mean gap over all
    logits under 0.02 (it reads 0.005-0.010; float8 operands 0.04-0.05:
    test_forward_logits_equal_the_references says why the mean)."""
    params, cfg, _ = _sides(8)
    half = _sides(8, jnp.bfloat16)[0]   # the same draws, rounded
    toks = _tokens(8, 48)[None]
    low_cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    full = jax.jit(lambda p: tf.forward(p, toks, cfg))(params)
    low = jax.jit(lambda p: tf.forward(p, toks, low_cfg))(half)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.mean(jnp.abs(low.astype(jnp.float32) - full))) < 0.02
    pre, _, res = tf._hc_weights(
        jnp.ones((4, 1, 3, 64), jnp.bfloat16), half["layers"][0]["hc1_phi"],
        half["layers"][0]["hc1_b"], half["layers"][0]["hc1_a"], cfg)
    assert pre.dtype == res.dtype == jnp.float32


# ------------------------------------------------------------- training ---

def test_the_loss_gradient_reaches_every_frame_leaf(sides):
    params, cfg, _ = sides
    toks = jnp.asarray(_tokens(9, 2 * 24).reshape(2, 24))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: tf.loss_fn(p, toks, cfg)))(params)
    assert np.isfinite(float(loss))
    for i, layer in enumerate(grads["layers"]):
        for f in ("hc1", "hc2"):
            for k in ("phi", "b", "a"):
                g = np.asarray(layer["%s_%s" % (f, k)])
                assert np.isfinite(g).all() and np.abs(g).max() > 0, (i, f, k)


def test_remat_layers_carries_the_four_streams(sides):
    """jax.checkpoint around a layer takes whatever the carry is: the
    same loss and the same gradients."""
    params, cfg, _ = sides
    toks = jnp.asarray(_tokens(10, 24)[None])
    remat_cfg = dataclasses.replace(cfg, remat_layers=True)
    plain = jax.jit(jax.value_and_grad(
        lambda p: tf.loss_fn(p, toks, cfg)))(params)
    remat = jax.jit(jax.value_and_grad(
        lambda p: tf.loss_fn(p, toks, remat_cfg)))(params)
    np.testing.assert_allclose(remat[0], plain[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(remat[1]), jax.tree.leaves(plain[1])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_a_train_step_moves_the_frame():
    params, cfg, _ = _sides(12)     # its own: the step donates them
    before = np.asarray(params["layers"][0]["hc1_phi"])
    step = tf.make_train_step(cfg, lr=0.1)
    toks = jnp.asarray(_tokens(11, 2 * 16).reshape(2, 16))
    new, _, loss = step(params, tf.init_momentum(params), toks)[:3]
    assert np.isfinite(float(loss))
    assert not np.array_equal(new["layers"][0]["hc1_phi"], before)


# ------------------------------------------------------------- refusals ---

def _mesh(**axes):
    from jax.sharding import Mesh
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


@pytest.mark.parametrize("what,call", [
    ("mesh-sharded forward", lambda p, c: tf.forward(
        p, jnp.zeros((2, 8), jnp.int32), c, mesh=_mesh(dp=2))),
    ("pp_axis", lambda p, c: tf.forward(
        p, jnp.zeros((2, 8), jnp.int32),
        dataclasses.replace(c, pp_axis="pp"), mesh=_mesh(pp=2))),
    ("shard_params", lambda p, c: tf.shard_params(p, c, _mesh(tp=2))),
    ("make_train_step", lambda p, c: tf.make_train_step(
        c, mesh=_mesh(dp=2))(p, tf.init_momentum(p),
                             jnp.zeros((2, 8), jnp.int32))),
])
def test_a_mesh_refuses_the_streams_by_name(what, call):
    params, cfg = _attention_model()
    with pytest.raises(ValueError, match="hc_mult=4"):
        call(params, cfg)


def test_int8_weights_refuse_the_frame_by_name():
    params, _ = _attention_model()
    with pytest.raises(ValueError, match="hyper-connection frame"):
        tf.quantize_weights_int8(params)


@pytest.mark.parametrize("what,make", [
    ("paged", lambda p, c: ContinuousBatcher(p, c, max_batch=2, paged=True)),
    ("kv_cache_int8", lambda p, c: ContinuousBatcher(
        p, dataclasses.replace(c, kv_cache_int8=True), max_batch=2)),
    ("spec_k", lambda p, c: ContinuousBatcher(p, c, max_batch=2, spec_k=2)),
])
def test_paged_blocks_int8_and_speculation_still_refuse_the_kind_by_name(
        sides, what, make):
    params, cfg, _ = sides
    with pytest.raises(ValueError, match="'mla'") as e:
        make(params, cfg)
    assert what in str(e.value) and "latent rows" in str(e.value)


@pytest.mark.parametrize("kw", [
    {"paged": True}, {"spec_k": 2, "spec_ngram": 2},
    {"paged": True, "spec_k": 2, "spec_ngram": 2}],
    ids=["paged", "speculation", "both"])
def test_the_entry_points_that_refuse_latent_rows_run_the_frame(kw):
    """decode_step_paged, verify_chunk and verify_chunk_paged on the
    kind they take, K/V heads, in the four-stream frame: every stream
    equals the dense lanes' and solo generate()."""
    params, cfg = _attention_model()
    rng = np.random.RandomState(12)
    jobs = [(list(rng.randint(1, 256, n)), m) for n, m in ((5, 9), (11, 6))]
    got, order = ContinuousBatcher(params, cfg, max_batch=2, **kw).run(jobs)
    for (prompt, n_new), rid in zip(jobs, order):
        solo = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new,
                           cfg)
        assert list(got[rid]) == [int(t) for t in np.asarray(solo)[0]]


# ------------------------------------------------------------- batcher ---

@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth1"])
def test_three_staggered_requests_on_two_lanes_equal_each_served_alone(
        sides, kw):
    params, cfg, _ = sides
    rng = np.random.RandomState(9)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((5, 9), (13, 4), (9, 7))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, **kw)
    got, order = srv.run(jobs)
    assert len(got) == 3
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _alone(params, cfg, prompt, n_new)
        solo = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new,
                           cfg)
        assert list(got[rid]) == [int(t) for t in np.asarray(solo)[0]]


def test_the_batchers_streams_follow_the_references_logits(sides):
    """Logits, not tokens: every served token's reference logit lies
    within 1e-3 of the reference's best at its position (float32; a
    served token is the program's first choice, so the gap is 0 or the
    distance between two logits rounding orders otherwise)."""
    params, cfg, weights = sides
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    jobs = [(list(_tokens(13, 17)), 20), (list(_tokens(14, 6)), 25)]
    got, order = srv.run(jobs)
    for (prompt, _), rid in zip(jobs, order):
        out = np.asarray(got[rid], np.int32)
        rows = _reference_logits(weights, out)[len(prompt) - 1: len(out) - 1]
        served = out[len(prompt):]
        gap = jnp.max(rows, axis=-1) - rows[jnp.arange(len(served)), served]
        assert float(jnp.max(gap)) < 1e-3


def test_an_admission_in_chunks_equals_the_one_call(sides, chunks_of_8):
    """29 tokens go in as 8 + 8 + 8 and a rest of 5 in a bucket of 8:
    the last row's logits are the reference's, the rows behind equal the
    one bucket's, and the stream is the same."""
    params, cfg, weights = sides
    toks = list(_tokens(14, 29))
    assert serving.prefill_widths(cfg, 29) == [8, 8, 8, 8]
    # the same tokens without the streams would go in one call
    assert serving.prefill_widths(
        dataclasses.replace(cfg, hc_mult=None), 29) == [32]
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    logits, row = srv._prefill_rows(srv._fresh_row(), toks, 0)
    want = _reference_logits(weights, np.asarray(toks, np.int32))
    np.testing.assert_allclose(logits[0], want[-1], atol=1e-4)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :29] = toks
    _, whole = tf._jitted_prefill_chunk_row(cfg)(
        params, srv._fresh_row(), jnp.asarray(padded), jnp.int32(0),
        jnp.int32(28))
    for got, one in zip(row, whole):
        for name in ("c", "kr"):
            np.testing.assert_allclose(got[name][0, :29], one[name][0, :29],
                                       atol=1e-5)
    got, order = srv.run([(toks, 9)])
    solo = tf.generate(params, jnp.asarray([toks], jnp.int32), 9, cfg)
    assert list(got[order[0]]) == [int(t) for t in np.asarray(solo)[0]]


def test_an_admissions_chunks_are_sized_by_the_stream_it_carries():
    """2^25 stream elements a call: 2,048 tokens of four streams of
    3,584 where one stream would give 8,192; a configuration without
    the field gets the widths it got."""
    cfg = serve_xing4.program_config(REAL)
    assert serving.prefill_widths(cfg, 8192) == [2048] * 4
    assert serving.prefill_widths(cfg, 3450) == [2048, 2048]
    assert serving.prefill_widths(cfg, 2300) == [2048, 256]
    assert serving.prefill_widths(cfg, 1024) == [1024]
    assert serving.prefill_widths(
        dataclasses.replace(cfg, hc_mult=None), 8192) == [8192]
    for d_model, longest in ((2048, 1536), (2560, 2048), (2304, 8192)):
        older = tf.TransformerConfig(d_model=d_model, max_len=11264)
        assert serving.prefill_widths(older, longest) \
            == [serving._bucket(longest)]


# ------------------------------------------------- scopes and counters ---

def test_the_frames_device_operations_carry_their_scopes(sides):
    params, cfg, _ = sides
    text = jax.jit(lambda p, c, t: tf.decode_step(
        p, c, t, jnp.int32(3), cfg)).lower(
            params, tf.init_cache(cfg, 2),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for scope in ("mx.hc.weights", "mx.hc.pre", "mx.hc.post"):
        assert scope in text
    plain = dataclasses.replace(cfg, hc_mult=None)
    text = jax.jit(lambda p, c, t: tf.decode_step(
        p, c, t, jnp.int32(3), plain)).lower(
            tf.init_params(plain, 0), tf.init_cache(plain, 2),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    assert "mx.hc." not in text


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["two-in-flight", "depth1"])
def test_admissions_and_rounds_count_their_rows(sides, telemetry, loop,
                                                chunks_of_8):
    """An admission of 13 tokens is a chunk of 8 and a rest of 5 in its
    bucket of 8: 13 real tokens, 16 rows; one of 5 is one bucket of 8.
    Every row passes 2 sub-layers x 3 layers of frames, and so does every
    lane of a decode round, with a request or not."""
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=2, **loop)
    srv.admit(list(_tokens(15, 13)), 6)
    assert obs.counter("serving.prefill_tokens").value == 13
    assert obs.counter("serving.prefill_rows").value == 16
    assert obs.counter("hc.rows").value == 6 * 16
    srv.admit(list(_tokens(16, 5)), 6)
    assert obs.counter("serving.prefill_tokens").value == 18
    assert obs.counter("serving.prefill_rows").value == 24
    before = obs.counter("hc.rows").value
    assert before == 6 * 24
    srv.step()
    rounds = obs.counter("serving.dispatches").value
    assert rounds >= 1
    assert obs.counter("hc.rows").value == before + rounds * 6 * 2
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    frozen = {k: obs.counter(k).value for k in (
        "hc.rows", "serving.prefill_tokens", "serving.prefill_rows")}
    srv.step()
    other = ContinuousBatcher(params, cfg, max_batch=2, **loop)
    other.admit(list(_tokens(17, 4)), 3)
    other.step()
    assert frozen == {k: obs.counter(k).value for k in frozen}


def test_a_model_without_streams_counts_no_frame_rows(telemetry):
    cfg = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=1, d_ff=64, max_len=32)
    srv = ContinuousBatcher(tf.init_params(cfg, 0), cfg, max_batch=2)
    srv.admit([1, 2, 3], 4)
    srv.step()
    assert "hc.rows" not in obs.counters()
    assert obs.counter("serving.prefill_tokens").value == 3
    assert obs.counter("serving.prefill_rows").value == 8
