"""The expert layer (transformer._expert_ffn) and its share of a
deployment: a program that holds a contiguous range of the experts routes
over all of them and computes its own experts' part. At a small size on
the CPU, float32: the parts all the shares give add up to the uncut
reference's layer; a skewed load drops no token; and with every expert
held and k = E the layer is the dense dispatch it replaced."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import kimi_linear as ref
from chipbench.reference.common import exact
from mxnet_tpu.models import transformer as tf

D, F, E, K, SHARES = 32, 16, 16, 4, 4
CFG = tf.TransformerConfig(
    d_model=D, d_ff=64, ffn="gated_silu", n_experts=E, experts_per_token=K,
    expert_scoring="sigmoid", expert_scale=2.446, d_expert=F,
    n_shared_experts=1)


def _layer_params(seed):
    """The uncut layer: all E experts, the router, the shared expert."""
    rng = np.random.RandomState(seed)
    n = lambda *s: jnp.asarray(rng.randn(*s) / np.sqrt(s[-2]), jnp.float32)
    return {"gate": n(D, E),
            "gate_bias": jnp.asarray(rng.randn(E) * 0.02, jnp.float32),
            "w1": n(E, D, F), "w3": n(E, D, F), "w2": n(E, F, D),
            "ws1": n(D, F), "ws3": n(D, F), "ws2": n(F, D)}


def _share(p, first, held, shared):
    """What the chip holding experts [first, first + held) has of p."""
    out = {k: v[first:first + held] if k in ("w1", "w2", "w3") else v
           for k, v in p.items()}
    if not shared:
        out = {k: v for k, v in out.items() if not k.startswith("ws")}
    return out


def _x(seed, b=2, t=9):
    return jnp.asarray(np.random.RandomState(seed).randn(b, t, D),
                       jnp.float32)


def _reference(x, p):
    """The uncut reference: every expert by a loop, plus the shared."""
    rows = x.reshape(-1, D)
    routed = ref.experts_part(rows, p, exact, K, 2.446, 0)
    shared = ref._gated_mlp(rows, p["ws1"], p["ws3"], p["ws2"], exact)
    return (routed + shared).reshape(x.shape)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """4 shares of 4 experts each: their routed parts, with the shared
    expert (which every chip computes alike) counted once, equal the
    whole layer. 2e-5: float32, 4 partial sums against one."""
    p, x = _layer_params(0), _x(0)
    held = E // SHARES
    total = jnp.zeros_like(x)
    for i in range(SHARES):
        cfg = dataclasses.replace(CFG, experts_held=(i * held, held))
        part = tf._ffn(x, _share(p, i * held, held, shared=i == 0), cfg)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    np.testing.assert_allclose(total, _reference(x, p), atol=2e-5)


def test_one_share_is_the_references_share():
    """The reference, given the same share, leaves out the same part."""
    p, x = _layer_params(1), _x(1)
    cfg = dataclasses.replace(CFG, experts_held=(8, 4))
    share = _share(p, 8, 4, shared=True)
    rows = x.reshape(-1, D)
    want = ref.experts_part(rows, share, exact, K, 2.446, 8) \
        + ref._gated_mlp(rows, p["ws1"], p["ws3"], p["ws2"], exact)
    np.testing.assert_allclose(tf._ffn(x, share, cfg),
                               want.reshape(x.shape), atol=2e-5)


def test_a_load_skewed_onto_one_expert_drops_no_token():
    """The score-correction bias sends every token to experts 4..7 first
    and expert 5's score is the largest by far: every pick of the batch
    lands in this share, a quarter of them on one expert. No capacity,
    so every token's result is the reference's."""
    p, x = _layer_params(2), _x(2, b=3, t=21)
    bias = np.full((E,), -10.0, np.float32)
    bias[4:8] = 10.0
    gate = np.asarray(p["gate"]).copy()
    gate[:, 5] = 0.0
    p = dict(p, gate_bias=jnp.asarray(bias), gate=jnp.asarray(gate))
    cfg = dataclasses.replace(CFG, experts_held=(4, 4))
    loads = []
    got = tf._ffn(x, _share(p, 4, 4, shared=True), cfg, loads)
    assert [int(n) for n in loads[0]] == [63, 63, 63, 63]
    np.testing.assert_allclose(got, _reference(x, p), atol=2e-5)
    # and a share none of whose experts is picked adds only the shared
    cfg = dataclasses.replace(CFG, experts_held=(12, 4))
    loads = []
    got = tf._ffn(x, _share(p, 12, 4, shared=True), cfg, loads)
    assert int(jnp.sum(loads[0])) == 0
    rows = x.reshape(-1, D)
    np.testing.assert_allclose(got, ref._gated_mlp(
        rows, p["ws1"], p["ws3"], p["ws2"], exact).reshape(x.shape),
        atol=2e-5)


def test_the_routing_counts_are_the_loads():
    p, x = _layer_params(3), _x(3)
    cfg = dataclasses.replace(CFG, experts_held=(0, 8))
    loads = []
    tf._ffn(x, _share(p, 0, 8, shared=False), cfg, loads)
    tf._ffn(x, _share(p, 0, 8, shared=False), cfg, loads)
    load = np.asarray(loads[0])
    stats = dict(zip(tf.MOE_STATS,
                     (int(n) for n in tf.moe_stats(loads, 18, cfg))))
    assert stats == {
        "picks": 2 * 18 * K, "picks_here": 2 * int(load.sum()),
        "experts_touched": 2 * int((load > 0).sum()),
        "load_max": 2 * int(load.max()), "experts_held": 16, "layers": 2}
    # the picks are the reference's: its weights are non-zero there
    w = np.asarray(ref.route(x.reshape(-1, D), p, exact, K, 2.446))
    assert (w > 0).sum(axis=0)[:8].tolist() == load.tolist()


# --- the dense dispatch this layer replaced (transformer.py before PR 36),
# kept here as the reference of "softmax, k = E, every expert held"

def _parent_ffn(x, p):
    gates = jax.nn.softmax(jnp.einsum("btd,de->bte", x, p["gate"]), axis=-1)
    h = jax.nn.gelu(jnp.einsum("btd,edf->betf", x, p["w1"]))
    y = jnp.einsum("betf,efd->betd", h, p["w2"])
    return jnp.einsum("betd,bte->btd", y, gates)


@pytest.mark.parametrize("vocab,n_experts,batch", [(32, 2, 8), (32, 4, 8),
                                                   (16, 2, 4)])
def test_every_expert_held_and_k_equal_e_is_the_parents_dense_dispatch(
        vocab, n_experts, batch):
    """tests/test_parallel.py's own configurations and inputs: the layer
    alone, the whole forward, and the loss's gradients, against the
    parent's formula. 1e-5: float32, E partial sums in another order."""
    cfg = tf.TransformerConfig(vocab_size=vocab, d_model=32,
                               n_heads=4 if vocab == 32 else 2, n_layers=2,
                               d_ff=64, n_experts=n_experts, max_len=16)
    params = tf.init_params(cfg, seed=0)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, vocab, (batch, 16)), jnp.int32)
    x = params["embed"][tokens]
    p = params["layers"][0]
    np.testing.assert_allclose(tf._ffn(x, p, cfg), _parent_ffn(x, p),
                               atol=1e-5)

    def parent_loss(params):
        real = tf._expert_ffn
        tf._expert_ffn = lambda x, p, cfg, loads: _parent_ffn(x, p)
        try:
            return tf.loss_fn(params, tokens, cfg)
        finally:
            tf._expert_ffn = real

    want, want_g = jax.value_and_grad(parent_loss)(params)
    got, got_g = jax.value_and_grad(tf.loss_fn)(params, tokens, cfg)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("kw", [
    dict(experts_per_token=5), dict(experts_per_token=0, experts_held=(0, 0)),
    dict(experts_held=(3, 2)), dict(experts_held=(-1, 2)),
    dict(expert_scoring="top1")])
def test_a_routing_that_states_no_layer_is_refused(kw):
    cfg = tf.TransformerConfig(n_experts=4, **kw)
    with pytest.raises(ValueError, match="n_experts=4"):
        tf.init_params(cfg, 0)
