"""Optimizer tests — numeric parity vs simple numpy reference updates.

Mirrors tests/python/unittest/test_optimizer.py strategy: run each
optimizer a few steps on a small problem and check descent/behavior.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt


def quad_loss_grad(w):
    # f(w) = 0.5*||w - 3||^2 ; grad = (w - 3)
    return w.asnumpy() - 3.0


ALL_OPTS = ["sgd", "nag", "signum", "ftml", "dcasgd", "lbsgd", "sgld",
            "adam", "adagrad", "adadelta", "rmsprop", "ftrl", "adamax",
            "nadam"]


@pytest.mark.parametrize("name", ALL_OPTS)
def test_optimizer_descends(name):
    np.random.seed(0)
    o = opt.create(name, learning_rate=0.1)
    w = mx.nd.array(np.zeros((4, 3), dtype=np.float32))
    state = o.create_state(0, w)
    start = float(np.abs(quad_loss_grad(w)).mean())
    for _ in range(60):
        g = mx.nd.array(quad_loss_grad(w))
        o.update(0, w, g, state)
    end = float(np.abs(quad_loss_grad(w)).mean())
    assert end < start, "%s did not descend: %f -> %f" % (name, start, end)


def test_sgd_matches_numpy():
    o = opt.create("sgd", learning_rate=0.5, momentum=0.9)
    w = mx.nd.array(np.ones((3,), dtype=np.float32))
    state = o.create_state(0, w)
    w_np = np.ones(3, dtype=np.float32)
    mom_np = np.zeros(3, dtype=np.float32)
    for _ in range(5):
        g_np = 2 * w_np
        g = mx.nd.array(g_np)
        o.update(0, w, g, state)
        mom_np = 0.9 * mom_np - 0.5 * g_np
        w_np = w_np + mom_np
        np.testing.assert_allclose(w.asnumpy(), w_np, rtol=1e-5)


def test_adam_matches_numpy():
    o = opt.create("adam", learning_rate=0.01)
    w = mx.nd.array(np.ones((3,), dtype=np.float32))
    state = o.create_state(0, w)
    w_np = np.ones(3, dtype=np.float32)
    m = np.zeros(3, dtype=np.float32)
    v = np.zeros(3, dtype=np.float32)
    b1, b2, eps = 0.9, 0.999, 1e-8
    for t in range(1, 6):
        g_np = 2 * w_np
        g = mx.nd.array(g_np)
        o.update(0, w, g, state)
        lr = 0.01 * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = b1 * m + (1 - b1) * g_np
        v = b2 * v + (1 - b2) * g_np ** 2
        w_np = w_np - lr * m / (np.sqrt(v) + eps)
        np.testing.assert_allclose(w.asnumpy(), w_np, rtol=1e-5)


def test_clip_and_rescale():
    o = opt.create("sgd", learning_rate=1.0, rescale_grad=0.5,
                   clip_gradient=0.1)
    w = mx.nd.array(np.zeros((2,), dtype=np.float32))
    g = mx.nd.array(np.array([10.0, -10.0], dtype=np.float32))
    o.update(0, w, g, None)
    np.testing.assert_allclose(w.asnumpy(), [-0.1, 0.1], rtol=1e-6)


def test_lr_scheduler_factor():
    from mxnet_tpu.lr_scheduler import FactorScheduler, MultiFactorScheduler, \
        PolyScheduler, CosineScheduler
    s = FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(1) == 1.0
    assert abs(s(11) - 0.5) < 1e-9
    m = MultiFactorScheduler(step=[5, 15], factor=0.1, base_lr=1.0)
    assert m(3) == 1.0
    assert abs(m(7) - 0.1) < 1e-9
    p = PolyScheduler(max_update=100, base_lr=1.0)
    assert p(0) == 1.0 and p(100) < 1e-6
    c = CosineScheduler(max_update=100, base_lr=1.0)
    assert abs(c(0) - 1.0) < 1e-9 and c(100) < 1e-6


def test_warmup():
    from mxnet_tpu.lr_scheduler import FactorScheduler
    s = FactorScheduler(step=1000, factor=1.0, base_lr=1.0, warmup_steps=10,
                        warmup_begin_lr=0.0)
    assert s(0) == 0.0
    assert abs(s(5) - 0.5) < 1e-9
    assert s(10) == 1.0


def test_updater_and_states_roundtrip(tmp_path):
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    upd = opt.get_updater(o)
    w = mx.nd.array(np.ones((3,), dtype=np.float32))
    g = mx.nd.array(np.full((3,), 0.5, dtype=np.float32))
    upd(0, g, w)
    upd(0, g, w)
    states = upd.get_states()
    upd2 = opt.get_updater(opt.create("sgd", learning_rate=0.1, momentum=0.9))
    upd2.set_states(states)
    assert 0 in upd2.states


def test_multi_precision():
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9,
                   multi_precision=True)
    w = mx.nd.array(np.ones((4,), dtype=np.float32)).astype("bfloat16")
    state = o.create_state_multi_precision(0, w)
    g = mx.nd.array(np.full((4,), 0.5, dtype=np.float32)).astype("bfloat16")
    o.update_multi_precision(0, w, g, state)
    assert str(w.dtype) == "bfloat16"
    master = state[0]
    assert str(master.dtype) == "float32"


# ------------------------------------------- multi-tensor (fused) update ---
# A list call of the Updater runs every fusable parameter in one program;
# one call a parameter is the reference it has to agree with.

_FUSED_SHAPES = [(3,), (1,), (4, 5), (7, 1), (2, 3, 4), (16,), (5, 5),
                 (1, 9), (2, 2, 2, 2), (11,), (6, 3), (8, 8)]
_FUSED_RULES = {
    "sgd": ("sgd", {}),
    "sgd_momentum": ("sgd", {"momentum": 0.9}),
    "nag": ("nag", {"momentum": 0.9}),
    "adam": ("adam", {}),
}


class _Mults(object):
    def __init__(self, lr_mult, wd_mult):
        self.lr_mult, self.wd_mult = lr_mult, wd_mult


def _fused_case(rule, bf16, knobs):
    """(updater, weights, gradient maker) of one case, seeded."""
    from mxnet_tpu.lr_scheduler import FactorScheduler
    name, hyper = _FUSED_RULES[rule]
    hyper = dict(hyper, learning_rate=0.05, multi_precision=bf16,
                 rescale_grad=0.5)
    if knobs:
        hyper.update(
            clip_gradient=0.3, wd=0.01,
            lr_scheduler=FactorScheduler(step=2, factor=0.5, base_lr=0.05),
            param_dict={i: _Mults(1.0 + 0.25 * (i % 3), 0.5 * (i % 2))
                        for i in range(0, len(_FUSED_SHAPES), 2)})
    rng = np.random.RandomState(7)
    weights = [mx.nd.array(rng.randn(*s).astype(np.float32))
               for s in _FUSED_SHAPES]
    if bf16:
        weights = [w.astype("bfloat16") for w in weights]

    def grads(step):
        r = np.random.RandomState(100 + step)
        out = [mx.nd.array(r.randn(*s).astype(np.float32))
               for s in _FUSED_SHAPES]
        return [g.astype("bfloat16") for g in out] if bf16 else out

    return opt.get_updater(opt.create(name, **hyper)), weights, grads


def _structure(state):
    if isinstance(state, tuple):
        return tuple(_structure(s) for s in state)
    return type(state)


def _assert_same_update(fused, one, w_fused, w_one, bf16):
    for a, b in zip(w_fused, w_one):
        assert a.dtype == b.dtype and a.shape == b.shape
        if bf16:    # the stored weights: exactly
            np.testing.assert_array_equal(
                a.asnumpy().astype(np.float32),
                b.asnumpy().astype(np.float32))
        else:
            np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                       rtol=1e-6, atol=1e-7)
    assert set(fused.states) == set(one.states)
    for i in fused.states:
        assert _structure(fused.states[i]) == _structure(one.states[i])
        for a, b in zip(opt._state_leaves(fused.states[i]),
                        opt._state_leaves(one.states[i])):
            assert a.dtype == b.dtype == np.float32
            np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                       rtol=1e-6, atol=1e-7)
    assert fused.optimizer._index_update_count \
        == one.optimizer._index_update_count
    assert fused.optimizer.num_update == one.optimizer.num_update


@pytest.mark.parametrize("knobs", [False, True], ids=["plain", "knobs"])
@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "bf16_mp"])
@pytest.mark.parametrize("rule", sorted(_FUSED_RULES))
def test_fused_update_matches_per_parameter(rule, bf16, knobs):
    fused, w_fused, grads = _fused_case(rule, bf16, knobs)
    one, w_one, _ = _fused_case(rule, bf16, knobs)
    indices = list(range(len(_FUSED_SHAPES)))
    assert opt._fused_kernel(fused.optimizer) is not None

    def not_fused(*args):
        raise AssertionError("the list call fell back to one parameter")

    fused.optimizer.update_multi_precision = not_fused

    def step(t, fused, one):
        fused(indices, grads(t), w_fused)
        for i, g, w in zip(indices, grads(t), w_one):
            one(i, g, w)

    for t in range(3):
        step(t, fused, one)
        _assert_same_update(fused, one, w_fused, w_one, bf16)
    if bf16:
        assert all(isinstance(s, tuple) and str(s[0].dtype) == "float32"
                   for s in fused.states.values())
    # the states of one path load into the other, and the next step agrees
    swapped_fused = opt.get_updater(fused.optimizer)
    swapped_fused.set_states(one.get_states())
    swapped_one = opt.get_updater(one.optimizer)
    swapped_one.set_states(fused.get_states())
    step(3, swapped_fused, swapped_one)
    _assert_same_update(swapped_fused, swapped_one, w_fused, w_one, bf16)
    assert fused.optimizer.num_update == 4


def test_fused_update_leaves_single_triple_alone():
    """One triple (Module, KVStore) runs the optimizer's own update."""
    calls = []

    class Spy(opt.SGD):
        def update_multi_precision(self, index, weight, grad, state):
            calls.append(index)
            super().update_multi_precision(index, weight, grad, state)

    upd = opt.get_updater(Spy(learning_rate=0.1, momentum=0.9))
    w = mx.nd.array(np.ones((3,), dtype=np.float32))
    g = mx.nd.array(np.full((3,), 0.5, dtype=np.float32))
    upd(4, g, w)
    assert calls == [4]
    np.testing.assert_allclose(w.asnumpy(), np.full(3, 0.95), rtol=1e-6)
