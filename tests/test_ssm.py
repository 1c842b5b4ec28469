"""The Mamba mixer (models/ssm.py) and the hybrid stack's cache paths in
models/transformer.py, at tiny sizes on the CPU: the sequence form against
the step form, the chunked scan against a sequential one, a bucket's
padding leaving no trace in the state, and a prefill continued from a
cached state."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import ssm
from mxnet_tpu.models import transformer as tf

KINDS = ("mamba", "attention", "mamba")


def _cfg(**kw):
    base = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=1,
                n_layers=3, layer_kinds=KINDS, d_ff=64, ffn="gated_silu",
                positions="none", max_len=64, ssm_state=8, ssm_dt_rank=4)
    base.update(kw)
    return tf.TransformerConfig(**base)


def _prefill(params, toks, cfg):
    """Whole-prompt prefill into a fresh cache, jitted (one program
    compiles faster here than its operations one by one)."""
    return jax.jit(lambda p, t: tf.prefill(
        p, tf.init_cache(cfg, t.shape[0]), t, cfg))(params, toks)


def _chunk(params, cache, toks, start, row, cfg):
    return jax.jit(lambda p, c, t, s, r: tf.prefill_chunk(
        p, c, t, s, cfg, logits_row=r))(
            params, cache, toks, jnp.int32(start), jnp.int32(row))


def _decode_fn(cfg):
    return jax.jit(lambda p, c, t, q: tf.decode_step(p, c, t, q, cfg))


def _layer(cfg=None, seed=3):
    cfg = cfg or _cfg()
    return tf.init_params(cfg, seed)["layers"][0], cfg


def _x(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


def _states_close(a, b, tol=2e-5):
    for la, lb in zip(a, b):
        for name in ("conv", "ssm"):
            if name in la:
                _close(la[name], lb[name], tol)


def test_sequence_form_equals_step_form_token_by_token():
    p, cfg = _layer()
    x = _x((2, 21, cfg.d_model))
    zero = tf._mamba_state(cfg, 2)
    y_seq, s_seq = ssm.mixer_seq(x, p, zero)
    state, ys = zero, []
    for t in range(x.shape[1]):
        y, state = ssm.mixer_step(x[:, t], p, state)
        ys.append(y)
    _close(y_seq, jnp.stack(ys, axis=1))
    _states_close([s_seq], [state])


@pytest.mark.parametrize("t", [5, ssm.SCAN_CHUNK, ssm.SCAN_CHUNK + 1,
                               3 * ssm.SCAN_CHUNK + 7])
def test_chunked_scan_equals_sequential_scan_across_a_chunk_edge(t):
    n, e = 8, 64
    rng = np.random.RandomState(t)
    delta = jnp.asarray(np.log1p(np.exp(rng.randn(2, t, e) - 2.0)),
                        jnp.float32)
    u = _x((2, t, e), 1)
    b, c = _x((2, t, n), 2), _x((2, t, n), 3)
    a = -jnp.tile(jnp.arange(1.0, n + 1)[:, None], (1, e))
    h0 = _x((2, n, e), 4)

    def step(h, xs):
        return ssm._advance(h, *xs, a)

    h_ref, y_ref = jax.lax.scan(
        step, h0, tuple(jnp.swapaxes(x, 0, 1) for x in (delta, u, b, c)))
    h, y = ssm._scan(h0, delta, u, b, c, a)
    _close(h, h_ref, 1e-5)
    _close(y, jnp.swapaxes(y_ref, 0, 1), 1e-5)


@pytest.mark.parametrize("t_p,width", [(13, 32), (3, 8)])
def test_padded_prompt_gives_the_unpadded_state_and_logits(t_p, width):
    cfg = _cfg()
    params = tf.init_params(cfg, 1)
    toks = jnp.asarray(np.random.RandomState(t_p).randint(1, 97, (1, t_p)),
                       jnp.int32)
    want, c_want = _prefill(params, toks, cfg)
    # pad tokens that are anything but inert if they were folded in
    padded = jnp.full((1, width), 96, jnp.int32).at[:, :t_p].set(toks)
    got, c_got = _chunk(params, tf.init_cache(cfg, 1), padded, 0, t_p - 1,
                        cfg)
    _close(got, want)
    _states_close(c_got, c_want)
    # and the next decode step agrees, through both kinds of state
    tok = jnp.argmax(want, -1).astype(jnp.int32)
    decode = _decode_fn(cfg)
    l_want, _ = decode(params, c_want, tok, jnp.int32(t_p))
    l_got, _ = decode(params, c_got, tok, jnp.int32(t_p))
    _close(l_got, l_want)


def test_unmasked_padding_would_move_the_state():
    """The control of the test above: without valid_len the padding is
    folded in, so the equality there is the mask's doing."""
    p, cfg = _layer()
    x = _x((1, 16, cfg.d_model))
    zero = tf._mamba_state(cfg, 1)
    _, exact = ssm.mixer_seq(x[:, :11], p, zero)
    _, masked = ssm.mixer_seq(x, p, zero, jnp.int32(11))
    _, folded = ssm.mixer_seq(x, p, zero)
    _states_close([masked], [exact])
    assert float(jnp.abs(folded["ssm"] - exact["ssm"]).max()) > 1e-3


@pytest.mark.parametrize("p_len", [1, 16])
def test_suffix_prefill_from_a_cached_state_equals_one_whole_prefill(p_len):
    cfg = _cfg()
    params = tf.init_params(cfg, 2)
    toks = jnp.asarray(np.random.RandomState(5).randint(1, 97, (1, 23)),
                       jnp.int32)
    want, c_want = _prefill(params, toks, cfg)
    # the prefix at its exact length, as cache_prefix does
    _, row = _chunk(params, tf.init_cache(cfg, 1), toks[:, :p_len], 0,
                    p_len - 1, cfg)
    # the suffix at its bucket, as admission does
    width = 32
    padded = jnp.zeros((1, width), jnp.int32) \
        .at[:, :23 - p_len].set(toks[:, p_len:])
    got, c_got = _chunk(params, row, padded, p_len, 22 - p_len, cfg)
    _close(got, want)
    _states_close(c_got, c_want)


def test_forward_prefill_and_decode_agree_through_the_cache():
    cfg = _cfg()
    params = tf.init_params(cfg, 1)
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 97, (2, 21)),
                       jnp.int32)
    full = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks)
    last, cache = _prefill(params, toks[:, :13], cfg)
    _close(last, full[:, 12])
    decode = _decode_fn(cfg)
    for t in range(13, 21):
        # ragged and scalar positions take the same recurrent step
        pos = jnp.full((2,), t, jnp.int32) if t % 2 else jnp.int32(t)
        logits, cache = decode(params, cache, toks[:, t], pos)
        _close(logits, full[:, t])


def _rows(layer, t, cfg):
    """A layer's state as float32, its K/V rows cut to the first t
    positions (an int8 cache dequantized: a code may flip on a rounding
    edge, its value may not move)."""
    if "ssm" in layer:
        return dict(layer)
    if cfg.kv_cache_int8:
        return {n: tf._kv_dequant(layer[n][:, :t], layer[n + "s"][:, :t],
                                  jnp.float32) for n in ("k", "v")}
    return {n: layer[n][:, :t] for n in ("k", "v")}


@pytest.mark.parametrize("kw", [
    dict(),
    dict(layer_kinds=None, positions=None),
    dict(layer_kinds=None, positions="rope", rope=True, n_kv_heads=2),
    dict(layer_kinds=None, positions=None, kv_cache_int8=True)],
    ids=["hybrid", "attention", "attention-rope-gqa", "attention-int8"])
def test_chunk_prefill_and_stepped_decode_leave_one_state(kw):
    """One layer body behind three doors: prefill_chunk over the whole
    prompt, prefill, and decode_step token by token leave the same
    state in every layer, of either kind, and the same last logits."""
    cfg = _cfg(**kw)
    params = tf.init_params(cfg, 5)
    t = 11
    toks = jnp.asarray(np.random.RandomState(2).randint(1, 97, (2, t)),
                       jnp.int32)
    want, c_want = _prefill(params, toks, cfg)
    got, c_chunk = _chunk(params, tf.init_cache(cfg, 2), toks, 0, t - 1,
                          cfg)
    decode, c_step = _decode_fn(cfg), tf.init_cache(cfg, 2)
    for i in range(t):
        pos = jnp.full((2,), i, jnp.int32) if i % 2 else jnp.int32(i)
        last, c_step = decode(params, c_step, toks[:, i], pos)
    # the quantizer's noise where the cache is int8: the contraction
    # reads codes, and the three doors round on different sides
    tol = 0.15 if cfg.kv_cache_int8 else 2e-5
    _close(got, want, tol)
    _close(last, want, tol)
    for l_want, l_chunk, l_step in zip(c_want, c_chunk, c_step):
        rows = _rows(l_want, t, cfg)
        for name in rows:
            scale = float(np.abs(np.asarray(rows[name], np.float32)).max())
            ltol = 2 * scale / 127 if cfg.kv_cache_int8 else 2e-5
            _close(_rows(l_chunk, t, cfg)[name], rows[name], ltol)
            _close(_rows(l_step, t, cfg)[name], rows[name], ltol)


def test_generate_and_beam_one_agree_on_a_hybrid_model():
    cfg = _cfg()
    params = tf.init_params(cfg, 4)
    prompt = jnp.asarray(np.random.RandomState(1).randint(1, 97, (2, 6)),
                         jnp.int32)
    out = tf.generate(params, prompt, 9, cfg)
    beams, _ = tf.beam_search(params, prompt, 9, cfg, beam=1)
    assert np.array_equal(np.asarray(out), np.asarray(beams[:, 0]))


def test_the_cache_follows_the_layer_kinds():
    cfg = _cfg(dtype=jnp.bfloat16)
    cache = tf.init_cache(cfg, 3)
    e = cfg.ssm_expand * cfg.d_model
    for kind, layer in zip(KINDS, cache):
        if kind == "mamba":
            assert layer["conv"].shape == (3, cfg.ssm_conv - 1, e)
            assert layer["conv"].dtype == jnp.bfloat16
            assert layer["ssm"].shape == (3, cfg.ssm_state, e)
            assert layer["ssm"].dtype == jnp.float32
        else:
            assert layer["k"].shape == (3, cfg.max_len, 1, 8)
    params = tf.init_params(cfg, 0)
    assert "pos" not in params and "pos" not in tf.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        tf.param_specs(cfg), is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec))


@pytest.mark.parametrize("kw", [
    dict(layer_kinds=("mamba",)), dict(layer_kinds=("mamba", "conv", "mamba")),
    dict(positions="rope"), dict(positions="sinusoid"),
    # gated SiLU experts are an architecture since PR 36: the one case
    # of this list that runs
    dict(ffn="gated_silu", n_experts=2, layer_kinds=None, runs=True),
    dict(ffn="swiglu")])
def test_a_configuration_that_states_no_architecture_is_refused(kw):
    runs = kw.pop("runs", False)
    cfg = _cfg(**kw)
    if runs:
        params = tf.init_params(cfg, 0)
        assert set(params["layers"][0]) >= {"gate", "w1", "w2", "w3"}
        logits = tf.forward(params, jnp.zeros((1, 4), jnp.int32), cfg)
        assert np.isfinite(np.asarray(logits)).all()
        return
    with pytest.raises(ValueError):
        params = tf.init_params(cfg, 0)
        tf.forward(params, jnp.zeros((1, 4), jnp.int32), cfg)


def test_the_default_configuration_is_the_attention_model_it_was():
    cfg = tf.TransformerConfig()
    assert tf._layer_kinds(cfg) == ("attention",) * cfg.n_layers
    assert tf._learned_pos(cfg) and not tf._recurrent(cfg)
    assert not tf._learned_pos(dataclasses.replace(cfg, rope=True))
    assert not tf._learned_pos(dataclasses.replace(cfg, positions="none"))
    assert set(tf.init_params(cfg)["layers"][0]) == {
        "ln1", "ln2", "wq", "wk", "wv", "wo", "w1", "w2"}
