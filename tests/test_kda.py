"""The KDA mixer (models/kda.py): gated delta-rule linear attention with a
matrix-valued state. The step form against the plain reference's
recurrence, the chunked sequence form against the step form, and the
state a padded prompt leaves. Float32 on the CPU throughout: the two
forms do the same sums in another order, so the tolerances are float32
rounding over a few hundred positions."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import kimi_linear as ref
from chipbench.reference.common import exact
from mxnet_tpu.models import kda

D, H, DK, K = 32, 4, 8, 4
EPS = 1e-5


def _params(seed, a_log=None, dt_bias=None):
    rng = np.random.RandomState(seed)

    def dense(*shape):
        return jnp.asarray(rng.randn(*shape) / np.sqrt(shape[0]), jnp.float32)

    dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H * DK))
    p = {"wqkv": dense(D, 3 * H * DK), "conv_w": dense(K, 3 * H * DK),
         "f_a": dense(D, DK), "f_b": dense(DK, H * DK),
         "dt_bias": jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), jnp.float32),
         "A_log": jnp.asarray(np.log(rng.uniform(1.0, 16.0, H)), jnp.float32),
         "b_proj": dense(D, H), "g_a": dense(D, DK), "g_b": dense(DK, H * DK),
         "o_norm": jnp.asarray(1.0 + 0.1 * rng.randn(DK), jnp.float32),
         "out_proj": dense(H * DK, D)}
    if a_log is not None:
        p["A_log"] = jnp.full((H,), a_log, jnp.float32)
    if dt_bias is not None:
        p["dt_bias"] = jnp.full((H * DK,), dt_bias, jnp.float32)
    return p


def _x(seed, t, batch=2):
    return jnp.asarray(np.random.RandomState(seed).randn(batch, t, D),
                       jnp.float32)


def _zero(batch):
    return kda.init_state(H, DK, K, batch, jnp.float32)


def _stepped(x, p, state):
    """The step form position by position: (out [B, T, D], state')."""
    outs = []
    for i in range(x.shape[1]):
        y, state = kda.mixer_step(x[:, i], p, state, EPS)
        outs.append(y)
    return jnp.stack(outs, axis=1), state


def test_the_step_form_is_the_references_recurrence():
    """One position of _advance after another against the plain
    reference's scan, on the same q, k, v, g, beta."""
    rng = np.random.RandomState(0)
    t = 37
    q, k = (jnp.asarray(rng.randn(t, H, DK), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.randn(t, H, DK), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.0, 2.0, (t, H, DK)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (t, H)), jnp.float32)
    want_s, want_o = ref.kda_recurrence(
        jnp.zeros((H, DK, DK), jnp.float32), q, k, v, g, beta)
    s = jnp.zeros((1, H, DK, DK), jnp.float32)
    for i in range(t):
        s, o = kda._advance(s, q[None, i], k[None, i], v[None, i],
                            g[None, i], beta[None, i])
        np.testing.assert_allclose(o[0], want_o[i], atol=1e-5)
    np.testing.assert_allclose(s[0], want_s, atol=1e-5)


def test_the_step_form_is_the_references_mixer():
    """The whole mixer (projections, convolutions, norms, gates, the
    recurrence, the output gate) against the reference's."""
    p, x = _params(1), _x(1, 29, batch=1)
    want = ref._kda(x[0], p, exact, EPS)
    got, _ = _stepped(x, p, _zero(1))
    np.testing.assert_allclose(got[0], want, atol=2e-5)


@pytest.mark.parametrize("t", [5, kda.CHUNK, kda.CHUNK + 1, 2 * kda.CHUNK,
                               2 * kda.CHUNK + 7])
def test_the_chunked_form_equals_the_step_form(t):
    """A prompt shorter than a chunk, chunk-aligned, and ragged: the WY
    form inside a chunk and the state carried between chunks give what
    one position after another gives, outputs and final state."""
    p, x = _params(2), _x(2, t)
    want, want_state = _stepped(x, p, _zero(2))
    got, state = jax.jit(lambda x, s: kda.mixer_seq(x, p, s, eps=EPS))(
        x, _zero(2))
    np.testing.assert_allclose(got, want, atol=3e-5)
    np.testing.assert_allclose(state["kda"], want_state["kda"], atol=3e-5)
    np.testing.assert_allclose(state["conv"], want_state["conv"], atol=1e-5)


def test_a_channel_that_decays_fast_does_not_overflow_a_chunk():
    """A = 16 and a step of ~5 a position: exp(-80) a position, exp(-5000)
    over a chunk. Every exponent of the chunk form is a difference that
    is never positive, so it stays finite and equal to the step form
    (dividing by the running decay would be 1 / 0)."""
    p = _params(3, a_log=np.log(16.0), dt_bias=5.0)
    x = _x(3, kda.CHUNK + 9)
    want, want_state = _stepped(x, p, _zero(2))
    got, state = kda.mixer_seq(x, p, _zero(2), eps=EPS)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=3e-5)
    np.testing.assert_allclose(state["kda"], want_state["kda"], atol=3e-5)


@pytest.mark.parametrize("t_p,width", [(13, 32), (3, 8), (kda.CHUNK, 128),
                                       (kda.CHUNK + 5, 128)])
def test_valid_len_stops_the_state_at_the_last_real_token(t_p, width):
    """A prompt padded to its bucket leaves exactly the state and the
    conv window of the unpadded prompt, whatever the padding holds."""
    p = _params(4)
    x = _x(4, width)
    _, want = kda.mixer_seq(x[:, :t_p], p, _zero(2), eps=EPS)
    out, got = jax.jit(lambda x, s, n: kda.mixer_seq(x, p, s, n, EPS))(
        x, _zero(2), jnp.int32(t_p))
    np.testing.assert_allclose(got["kda"], want["kda"], atol=3e-5)
    np.testing.assert_allclose(got["conv"], want["conv"], atol=1e-5)
    real, _ = kda.mixer_seq(x[:, :t_p], p, _zero(2), eps=EPS)
    np.testing.assert_allclose(out[:, :t_p], real, atol=3e-5)


def test_unmasked_padding_would_move_the_state():
    p, x = _params(4), _x(4, 32)
    _, want = kda.mixer_seq(x[:, :13], p, _zero(2), eps=EPS)
    _, folded = kda.mixer_seq(x, p, _zero(2), eps=EPS)
    assert float(jnp.max(jnp.abs(folded["kda"] - want["kda"]))) > 1e-3


@pytest.mark.parametrize("split", [1, 16, kda.CHUNK + 3])
def test_a_sequence_continues_from_a_carried_state(split):
    """A prefix, then the suffix from the prefix's state (a cached
    prefix, a chunked prompt) is one whole sequence."""
    p, x = _params(5), _x(5, kda.CHUNK + 20)
    want, want_state = kda.mixer_seq(x, p, _zero(2), eps=EPS)
    head, state = kda.mixer_seq(x[:, :split], p, _zero(2), eps=EPS)
    tail, state = kda.mixer_seq(x[:, split:], p, state, eps=EPS)
    np.testing.assert_allclose(jnp.concatenate([head, tail], axis=1), want,
                               atol=3e-5)
    np.testing.assert_allclose(state["kda"], want_state["kda"], atol=3e-5)
    np.testing.assert_allclose(state["conv"], want_state["conv"], atol=1e-5)


def test_the_state_is_a_float32_matrix_a_head_whatever_the_model_is():
    state = kda.init_state(H, DK, K, 3, jnp.bfloat16)
    assert state["kda"].shape == (3, H, DK, DK)
    assert state["kda"].dtype == jnp.float32
    assert state["conv"].shape == (3, K - 1, 3 * H * DK)
    assert state["conv"].dtype == jnp.bfloat16
