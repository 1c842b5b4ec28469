"""Gluon Block/HybridBlock/Parameter/Trainer/loss tests.

Modeled on the reference suite tests/python/unittest/test_gluon.py (2821
LoC): parameter lifecycle, deferred init, hybridize consistency, trainer
steps, losses vs hand-computed numpy references.
"""

import glob
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn


def test_parameter_basic():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier")
    assert p.data().shape == (10, 10)
    assert p.grad().shape == (10, 10)
    assert len(p.list_data()) == 1


def test_parameter_invalid_access():
    p = gluon.Parameter("weight", shape=(10, 10))
    with pytest.raises(RuntimeError):
        p.data()


def test_paramdict_get_and_share():
    shared = gluon.ParameterDict("net_")
    d1 = gluon.ParameterDict("net_", shared=shared)
    shared.get("w", shape=(3,))
    p = d1.get("w")
    assert p is shared["net_w"]


def test_constant_parameter():
    const = np.arange(6.0).reshape(2, 3)

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.c = self.params.get_constant("const", const)

        def hybrid_forward(self, F, x, c):
            return x + c

    net = Net()
    net.initialize()
    x = mx.nd.zeros((2, 3))
    out = net(x)
    assert np.allclose(out.asnumpy(), const)
    assert net.c.grad_req == "null"


def test_dense_deferred_init():
    net = nn.Dense(8)
    net.initialize()
    assert net.weight.shape == (8, 0)
    x = mx.nd.ones((4, 5))
    y = net(x)
    assert net.weight.shape == (8, 5)
    assert y.shape == (4, 8)


def test_dense_forward_numpy_parity():
    net = nn.Dense(3, use_bias=True, in_units=4)
    net.initialize()
    x = mx.nd.array(np.random.randn(2, 4))
    w = net.weight.data().asnumpy()
    b = net.bias.data().asnumpy()
    expected = x.asnumpy() @ w.T + b
    assert np.allclose(net(x).asnumpy(), expected, atol=1e-5)


def test_sequential_and_slicing():
    net = nn.Sequential()
    net.add(nn.Dense(4), nn.Dense(3), nn.Dense(2))
    assert len(net) == 3
    assert isinstance(net[0], nn.Dense)
    net.initialize()
    y = net(mx.nd.ones((1, 5)))
    assert y.shape == (1, 2)


def test_hybrid_consistency_mlp():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.random.randn(3, 7))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert np.allclose(eager, hybrid, atol=1e-5)


def test_hybrid_grad_consistency_cnn():
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(4, 3, padding=1),
                    nn.BatchNorm(),
                    nn.Activation("relu"),
                    nn.MaxPool2D(2),
                    nn.Flatten(),
                    nn.Dense(3))
        return net

    net = build()
    net.initialize()
    x = mx.nd.array(np.random.randn(2, 3, 8, 8))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    g_eager = net[0].weight.grad().asnumpy().copy()

    net.hybridize()
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    g_hybrid = net[0].weight.grad().asnumpy()
    assert np.allclose(g_eager, g_hybrid, atol=1e-4)


def test_batchnorm_running_stats_update():
    net = nn.BatchNorm(in_channels=3)
    net.initialize()
    x = mx.nd.array(np.random.randn(4, 3, 5, 5) * 3 + 1)
    before = net.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    after = net.running_mean.data().asnumpy()
    assert not np.allclose(before, after)
    # inference uses running stats: output differs from training output
    y_train_mean = None
    with autograd.record():
        y_train_mean = net(x).asnumpy()
    y_infer = net(x).asnumpy()
    assert not np.allclose(y_train_mean, y_infer)


def test_conv_transpose_shapes():
    net = nn.Conv2DTranspose(8, 3, strides=2, padding=1, output_padding=1,
                             in_channels=4)
    net.initialize()
    y = net(mx.nd.ones((2, 4, 7, 7)))
    assert y.shape == (2, 8, 14, 14)


def test_pool_layers():
    x = mx.nd.array(np.random.randn(2, 3, 8, 8))
    assert nn.MaxPool2D(2)(x).shape == (2, 3, 4, 4)
    assert nn.AvgPool2D(2)(x).shape == (2, 3, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (2, 3, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (2, 3, 1, 1)
    # avg pool numeric check
    y = nn.AvgPool2D(2)(x).asnumpy()
    ref = x.asnumpy().reshape(2, 3, 4, 2, 4, 2).mean(axis=(3, 5))
    assert np.allclose(y, ref, atol=1e-6)


def test_maxpool_grad_through_hybrid():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.MaxPool2D(2), nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.randn(1, 1, 4, 4))
    x.attach_grad()
    with autograd.record():
        y = net(x).sum()
    y.backward()
    # gradient flows only to window maxima
    gx = x.grad.asnumpy()
    assert (gx != 0).sum() > 0


def test_embedding():
    net = nn.Embedding(10, 4)
    net.initialize()
    idx = mx.nd.array(np.array([[1, 2], [3, 4]]), dtype="int32")
    out = net(idx)
    assert out.shape == (2, 2, 4)
    w = net.weight.data().asnumpy()
    assert np.allclose(out.asnumpy()[0, 0], w[1], atol=1e-6)


def test_layernorm_groupnorm_instancenorm():
    x = mx.nd.array(np.random.randn(2, 6, 4))
    ln = nn.LayerNorm(in_channels=4)
    ln.initialize()
    y = ln(x).asnumpy()
    assert np.allclose(y.mean(axis=-1), 0, atol=1e-4)
    gn = nn.GroupNorm(num_groups=3, in_channels=6)
    gn.initialize()
    assert gn(x).shape == x.shape
    inorm = nn.InstanceNorm(in_channels=6)
    inorm.initialize()
    assert inorm(x).shape == x.shape


def test_activations_layers():
    x = mx.nd.array(np.array([-2.0, -0.5, 0.5, 2.0]))
    assert np.allclose(nn.Activation("relu")(x).asnumpy(),
                       np.maximum(x.asnumpy(), 0))
    lrelu = nn.LeakyReLU(0.1)
    y = lrelu(x).asnumpy()
    assert np.allclose(y, np.where(x.asnumpy() > 0, x.asnumpy(),
                                   0.1 * x.asnumpy()), atol=1e-6)
    for blk in [nn.ELU(), nn.SELU(), nn.Swish(), nn.GELU(),
                nn.PReLU()]:
        blk.initialize()
        assert blk(x).shape == x.shape


def test_trainer_sgd_step():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.array([[1.0, 2.0]])
    w_before = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(1)
    w_after = net.weight.data().asnumpy()
    assert not np.allclose(w_before, w_after)
    assert trainer.learning_rate == pytest.approx(0.1)
    trainer.set_learning_rate(0.01)
    assert trainer.learning_rate == pytest.approx(0.01)


def test_trainer_convergence_linear_regression():
    np.random.seed(0)
    true_w = np.array([[2.0, -3.4]])
    true_b = 4.2
    X = np.random.randn(200, 2).astype(np.float32)
    Y = X @ true_w.T + true_b

    net = nn.Dense(1, in_units=2)
    net.initialize(init=mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    l2 = gluon.loss.L2Loss()
    for epoch in range(60):
        with autograd.record():
            loss = l2(net(mx.nd.array(X)), mx.nd.array(Y))
        loss.backward()
        trainer.step(X.shape[0])
    assert np.allclose(net.weight.data().asnumpy(), true_w, atol=0.1)
    assert abs(float(net.bias.data().asnumpy()[0]) - true_b) < 0.1


def test_losses_numeric():
    pred = mx.nd.array(np.array([[1.0, 2.0], [0.5, -0.5]]))
    label = mx.nd.array(np.array([[0.5, 1.0], [1.0, 0.0]]))

    l2 = gluon.loss.L2Loss()(pred, label).asnumpy()
    ref = 0.5 * ((pred.asnumpy() - label.asnumpy()) ** 2).mean(axis=1)
    assert np.allclose(l2, ref, atol=1e-6)

    l1 = gluon.loss.L1Loss()(pred, label).asnumpy()
    ref = np.abs(pred.asnumpy() - label.asnumpy()).mean(axis=1)
    assert np.allclose(l1, ref, atol=1e-6)

    huber = gluon.loss.HuberLoss(rho=1.0)(pred, label).asnumpy()
    d = np.abs(pred.asnumpy() - label.asnumpy())
    ref = np.where(d > 1, d - 0.5, 0.5 * d * d).mean(axis=1)
    assert np.allclose(huber, ref, atol=1e-6)


def test_softmax_ce_loss():
    pred = mx.nd.array(np.random.randn(4, 5))
    label = mx.nd.array(np.array([0, 1, 2, 3]))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label).asnumpy()
    p = pred.asnumpy()
    logp = p - p.max(axis=1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(axis=1, keepdims=True))
    ref = -logp[np.arange(4), label.asnumpy().astype(int)]
    assert np.allclose(loss, ref, atol=1e-5)


def test_sigmoid_bce_loss():
    pred = mx.nd.array(np.random.randn(3, 4))
    label = mx.nd.array((np.random.rand(3, 4) > 0.5).astype(np.float32))
    loss = gluon.loss.SigmoidBCELoss()(pred, label).asnumpy()
    x, z = pred.asnumpy(), label.asnumpy()
    ref = (np.maximum(x, 0) - x * z + np.log1p(np.exp(-np.abs(x)))).mean(axis=1)
    assert np.allclose(loss, ref, atol=1e-5)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    fname = str(tmp_path / "p.params")
    net.save_parameters(fname)
    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_parameters(fname)
    x = mx.nd.array(np.random.randn(2, 3))
    assert np.allclose(net(x).asnumpy(), net2(x).asnumpy(), atol=1e-6)


def test_export_symbolblock_import(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(5, activation="relu", in_units=4), nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.randn(2, 4))
    ref = net(x).asnumpy()
    path = str(tmp_path / "model")
    net.export(path)
    sb = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                   path + "-0000.params")
    assert np.allclose(sb(x).asnumpy(), ref, atol=1e-5)


def test_name_scope_prefixes():
    net = nn.HybridSequential(prefix="model_")
    with net.name_scope():
        d = nn.Dense(2)
    assert d.prefix.startswith("model_")
    p_names = list(net.collect_params().keys()) + \
        list(d.collect_params().keys())
    assert all(n.startswith("model_") for n in p_names)


def test_block_grad_req_setattr():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    net.collect_params().setattr("grad_req", "null")
    with autograd.record():
        loss = net(mx.nd.ones((1, 2))).sum()
    loss.backward()
    assert net.weight.grad_req == "null"


def test_lambda_blocks():
    lam = nn.Lambda(lambda x: x * 2)
    out = lam(mx.nd.ones((2, 2)))
    assert np.allclose(out.asnumpy(), 2.0)
    hlam = nn.HybridLambda(lambda F, x: F.relu(x))
    out = hlam(mx.nd.array(np.array([-1.0, 1.0])))
    assert np.allclose(out.asnumpy(), [0.0, 1.0])


def test_hybrid_multi_output():
    class Net(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return F.relu(x), F.sigmoid(x)

    net = Net()
    net.initialize()
    x = mx.nd.array(np.random.randn(2, 3))
    a, b = net(x)
    net.hybridize()
    a2, b2 = net(x)
    assert np.allclose(a.asnumpy(), a2.asnumpy(), atol=1e-6)
    assert np.allclose(b.asnumpy(), b2.asnumpy(), atol=1e-6)


def test_dropout_hybrid_randomness():
    net = nn.Dropout(0.5)
    net.hybridize()
    x = mx.nd.ones((100,))
    with autograd.record():
        y1 = net(x).asnumpy()
        y2 = net(x).asnumpy()
    # training-mode dropout: masks differ between calls even when compiled
    assert not np.allclose(y1, y2)
    # inference: identity
    y3 = net(x).asnumpy()
    assert np.allclose(y3, 1.0)


def test_clip_global_norm():
    arrays = [mx.nd.array(np.ones((2, 2)) * 3),
              mx.nd.array(np.ones((3,)) * 4)]
    total = gluon.utils.clip_global_norm(arrays, 1.0)
    new_norm = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert new_norm < 1.01
    assert total > 1.0


def test_split_and_load():
    data = mx.nd.array(np.arange(12).reshape(6, 2))
    parts = gluon.utils.split_data(data, 3)
    assert len(parts) == 3 and parts[0].shape == (2, 2)
    loaded = gluon.utils.split_and_load(data, [mx.cpu()])
    assert len(loaded) == 1


# ------------------------------------- the trainer's multi-tensor update ---
# Trainer hands the Updater every parameter in one call; what can be fused
# runs as one program, counted by `optimizer.fused_params` /
# `optimizer.fallback_params` and spanned by `optimizer.fused`.

@pytest.fixture
def telemetry(monkeypatch):
    from mxnet_tpu.observability import core
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.reset()
    core.set_enabled(True)
    yield core
    core.set_enabled(None)
    core.reset()


def _mlp(optimizer="sgd", hyper=None, kvstore=None, dtype="float32",
         **trainer_args):
    """A small hybridized block with six parameters, a trainer and a
    function that runs forward + backward (on the first `heads` heads).
    `kvstore="default"` leaves the argument to the Trainer's default."""
    np.random.seed(3)
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(6, activation="relu", in_units=4),
            nn.Dense(5, activation="relu", in_units=6),
            nn.Dense(3, in_units=5))
    net.initialize(mx.init.Xavier())
    net.cast(dtype)
    net.hybridize()
    if kvstore != "default":
        trainer_args["kvstore"] = kvstore
    trainer = gluon.Trainer(
        net.collect_params(), optimizer,
        hyper if hyper is not None else {"learning_rate": 0.1,
                                         "momentum": 0.9},
        **trainer_args)
    x = mx.nd.array(np.random.randn(8, 4).astype(np.float32), dtype=dtype)

    def backward():
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()

    return net, trainer, backward


def _opt_counts(core):
    c = core.counters()
    return tuple(int(c[k].total) if k in c else None
                 for k in ("optimizer.fused_params",
                           "optimizer.fallback_params"))


def _weights(net):
    return [p.data().asnumpy().copy()
            for p in net.collect_params().values()]


def test_trainer_update_is_one_fused_program(telemetry):
    net, trainer, backward = _mlp(kvstore=mx.kvstore.create("device"))
    backward()
    trainer.step(8)
    assert _opt_counts(telemetry) == (6, 0)
    spans = [r for r in telemetry.records() if r[0] == "X"]
    fused = [r for r in spans if r[1] == "optimizer.fused"]
    update = [r for r in spans if r[1] == "update"]
    assert len(fused) == 1 and len(update) == 1
    (_, _, _, f0, fdur, ftid, fargs), (_, _, _, u0, udur, utid, _) = \
        fused[0], update[0]
    assert ftid == utid and u0 <= f0 and f0 + fdur <= u0 + udur
    assert fargs["params"] == 6


def test_trainer_update_counts_nothing_when_telemetry_is_off(monkeypatch):
    from mxnet_tpu.observability import core
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    _, trainer, backward = _mlp()
    backward()
    trainer.step(8)
    assert core.counters() == {} and core.records() == []


def test_trainer_update_row_sparse_gradient_falls_back(telemetry):
    from mxnet_tpu import sparse
    net, trainer, backward = _mlp()
    ref_net, ref_trainer, ref_backward = _mlp()
    first = list(net.collect_params().values())[0]
    ref_first = list(ref_net.collect_params().values())[0]
    for _ in range(2):
        backward()
        ref_backward()
        dense = first.grad().asnumpy().copy()
        dense[1::2] = 0.0       # rows 0, 2, 4 carry the gradient
        first._grad = sparse.row_sparse_array(dense)
        ref_first.grad()._data = mx.nd.array(dense)._data
        trainer.step(8)
        ref_trainer.step(8)
    assert _opt_counts(telemetry) == (10 + 12, 2)
    # lazy update: rows the sparse gradient does not name keep their
    # momentum's pull out of the weight; rows it names agree with dense
    got, want = first.data().asnumpy(), ref_first.data().asnumpy()
    np.testing.assert_allclose(got[0::2], want[0::2], rtol=1e-6)
    for a, b in zip(_weights(net)[1:], _weights(ref_net)[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_trainer_update_overridden_optimizer_falls_back(telemetry):
    seen = []

    class Mine(mx.optimizer.SGD):
        def update(self, index, weight, grad, state):
            seen.append(index)
            super().update(index, weight, grad, state)

    hyper = dict(learning_rate=0.1, momentum=0.9)
    net, trainer, backward = _mlp(Mine(**hyper), {})
    ref_net, ref_trainer, ref_backward = _mlp("sgd", hyper)
    for _ in range(2):
        backward()
        trainer.step(8)
        ref_backward()
        ref_trainer.step(8)
    assert seen == list(range(6)) * 2
    assert _opt_counts(telemetry) == (12, 12)   # ref fused, Mine not
    for a, b in zip(_weights(net), _weights(ref_net)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_trainer_update_skips_stale_parameter(telemetry):
    net, trainer, _ = _mlp()
    extra = nn.Dense(2, in_units=3)
    extra.initialize()
    params = net.collect_params()
    params.update(extra.collect_params())
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                            kvstore=None)
    x = mx.nd.array(np.random.randn(8, 4).astype(np.float32))
    before = _weights(net), _weights(extra)
    with autograd.record():
        loss = (net(x) ** 2).mean()      # `extra` takes no part
    loss.backward()
    with pytest.raises(UserWarning):
        trainer.step(8)
    # the refused step updated nothing and consumed no gradient
    for a, b in zip(_weights(net), before[0]):
        np.testing.assert_array_equal(a, b)
    trainer.step(8, ignore_stale_grad=True)
    assert _opt_counts(telemetry) == (6, 0)
    for a, b in zip(_weights(extra), before[1]):
        np.testing.assert_array_equal(a, b)
    assert all((a != b).any() for a, b in zip(_weights(net), before[0]))


def test_trainer_update_never_recompiles_for_a_hyperparameter():
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.lr_scheduler import FactorScheduler
    _, trainer, backward = _mlp(hyper={
        "learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
        "lr_scheduler": FactorScheduler(step=2, factor=0.7, base_lr=0.1)})
    program = opt._fused_program(opt.SGD._kernel, False)
    sizes = []
    for step in range(10):
        backward()
        trainer.step(1 + step)                # a new rescale_grad
        trainer._optimizer.wd *= 1.5
        trainer._optimizer.momentum *= 0.99
        sizes.append(program._cache_size())
    # the scheduler moved the rate too
    assert trainer.learning_rate < 0.1 * 0.7 ** 3
    assert sizes[-1] == sizes[0], sizes


# ------------------------------------------------ the trainer's store ---
# The reference's rule (model.py _create_kvstore): a string spec with no
# "dist" in it, on one gradient copy a parameter, is no store at all. The
# allreduce phase still opens every step and counts that it had nothing to
# do; whoever asks for what only a store does keeps one.

def _spans(core, name):
    return [r for r in core.records() if r[0] == "X" and r[1] == name]


@pytest.mark.parametrize("spec", ["default", "device", "local"])
def test_trainer_string_spec_on_one_worker_builds_no_store(telemetry, spec):
    _, trainer, backward = _mlp(kvstore=spec)
    for _ in range(3):
        backward()
        trainer.step(8)
    assert trainer._kvstore is None
    assert trainer._update_on_kvstore is False
    assert not [n for n in telemetry.counters() if n.startswith("kvstore.")]
    assert not [r for r in telemetry.records()
                if r[1].startswith("kvstore.")]
    assert len(_spans(telemetry, "allreduce")) == 3
    assert telemetry.counters()["trainer.allreduce_noop"].total == 3
    assert _opt_counts(telemetry) == (18, 0)


def test_trainer_default_spec_step_boundaries_take_no_store(
        telemetry, monkeypatch, tmp_path):
    """Everything in step() after the allreduce is handed the store; the
    default spec now hands each of them None."""
    from mxnet_tpu.observability import integrity
    monkeypatch.setenv("MXNET_INTEGRITY", "1")
    monkeypatch.setenv("MXNET_INTEGRITY_EVERY", "1")
    monkeypatch.setenv("MXNET_OBS_SKEW_EVERY", "1")
    integrity._reset_for_tests()
    net, trainer, backward = _mlp(kvstore="default")
    try:
        for _ in range(2):
            backward()
            trainer.step(8)
        assert integrity._state["steps"] == 2
        assert integrity.stats["detected"] == 0
    finally:
        integrity._reset_for_tests()
    assert trainer._kvstore is None
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    saved = _bits(_state_leaves(trainer))
    backward()
    trainer.step(8)
    assert _bits(_state_leaves(trainer)) != saved
    trainer.load_states(fname)
    assert _bits(_state_leaves(trainer)) == saved


@pytest.mark.parametrize("case,args", [
    ("instance", {}),
    ("dist", {"kvstore": "dist_tpu_sync"}),
    ("compression", {"kvstore": "device",
                     "compression_params": {"type": "2bit",
                                            "threshold": 0.5}}),
    ("update_on_kvstore", {"kvstore": "device",
                           "update_on_kvstore": True}),
])
def test_trainer_keeps_the_store_only_a_store_can_serve(telemetry, case,
                                                         args):
    if case == "instance":
        args = {"kvstore": mx.kvstore.create("device")}
    net, trainer, backward = _mlp(**args)
    before = _weights(net)
    for _ in range(3):
        backward()
        trainer.step(8)
    kv = trainer._kvstore
    assert isinstance(kv, mx.kvstore.KVStore)
    assert kv.dispatch_stats["buckets"] > 0
    assert kv.dispatch_stats["keys"] == 18
    assert kv.gradient_compression.active == (case == "compression")
    assert trainer._update_on_kvstore == (case == "update_on_kvstore")
    assert telemetry.counters()["kvstore.buckets"].total \
        == kv.dispatch_stats["buckets"]
    assert "trainer.allreduce_noop" not in telemetry.counters()
    assert len(_spans(telemetry, "allreduce")) == 3
    assert all((a != b).any() for a, b in zip(_weights(net), before))


def _bits(arrays):
    return [np.asarray(a).tobytes() for a in arrays]


def _state_leaves(trainer):
    """Every optimizer state array (momenta; with multi_precision the
    float32 masters beside them), in parameter order."""
    states = trainer._updaters[0].states
    return [np.asarray(a._data) for i in sorted(states)
            for a in (states[i] if isinstance(states[i], tuple)
                      else (states[i],))]


@pytest.mark.parametrize("dtype,hyper", [
    ("float32", {"learning_rate": 0.1, "momentum": 0.9}),
    ("float16", {"learning_rate": 0.1, "momentum": 0.9,
                 "multi_precision": True}),
])
def test_trainer_store_or_none_gives_the_same_bits(dtype, hyper):
    runs = []
    for spec in (None, "device", mx.kvstore.create("device")):
        net, trainer, backward = _mlp(hyper=dict(hyper), kvstore=spec,
                                      dtype=dtype)
        for _ in range(3):
            backward()
            trainer.step(8)
        assert (trainer._kvstore is None) == (not isinstance(
            spec, mx.kvstore.KVStore))
        leaves = _state_leaves(trainer)
        # momenta for six parameters, and their masters in float16
        assert len(leaves) == (12 if dtype == "float16" else 6)
        runs.append((_bits(_weights(net)), _bits(leaves)))
    assert runs[0] == runs[1] == runs[2]


def test_trainer_states_saved_beside_a_store_load_under_the_default(
        tmp_path):
    """What a run before the rule wrote (a store for the gradients, the
    update and its states in the Updater) is what the default spec reads."""
    fname = str(tmp_path / "trainer.states")
    net, trainer, backward = _mlp(kvstore=mx.kvstore.create("device"))
    for _ in range(2):
        backward()
        trainer.step(8)
    assert trainer._kvstore is not None and not trainer._update_on_kvstore
    trainer.save_states(fname)
    new_net, new_trainer, new_backward = _mlp(kvstore="default")
    for mine, theirs in zip(new_net.collect_params().values(),
                            net.collect_params().values()):
        mine.set_data(theirs.data())
    new_trainer.load_states(fname)
    assert new_trainer._kvstore is None
    assert _bits(_state_leaves(new_trainer)) == _bits(_state_leaves(trainer))
    for step, back in ((trainer, backward), (new_trainer, new_backward)):
        back()
        step.step(8)
    assert _bits(_weights(new_net)) == _bits(_weights(net))
    assert _bits(_state_leaves(new_trainer)) == _bits(_state_leaves(trainer))


@pytest.mark.parametrize("spec", [None, "device", "local", "nccl",
                                  "dist_tpu_sync", "instance"])
def test_module_and_trainer_resolve_a_spec_alike(spec):
    if spec == "instance":
        spec = mx.kvstore.create("local")
    _, trainer, backward = _mlp(kvstore=spec)
    backward()
    trainer.step(8)
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc",
                              num_hidden=2), name="softmax")
    mod = mx.mod.Module(out, data_names=["data"],
                        label_names=["softmax_label"])
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(kvstore=spec)
    if isinstance(spec, mx.kvstore.KVStore):
        assert trainer._kvstore is spec and mod._kvstore is spec
    else:
        assert type(trainer._kvstore) is type(mod._kvstore)
        assert (trainer._kvstore is None) == (
            spec is None or "dist" not in spec)


# ------------------------------------- one program a tape node (ISSUE 31) ---
# Auxiliary states (BatchNorm running statistics) leave a compiled block as
# has_aux outputs, not as differentiated ones, so its backward is ONE program
# however many batch norms it has; and the sweep applies every pullback that
# came out of a jitted forward inside one program, seeds filled there.

def _bn_net(n_bn, hybrid=True, **flags):
    np.random.seed(5)
    mx.random.seed(5)
    net = nn.HybridSequential()
    for i in range(n_bn):
        net.add(nn.Conv2D(4, 3, padding=1, use_bias=False,
                          in_channels=4 if i else 3),
                nn.BatchNorm(in_channels=4), nn.Activation("relu"))
    net.add(nn.GlobalAvgPool2D(), nn.Dense(3, in_units=4))
    net.initialize(mx.init.Xavier())
    if hybrid:
        net.hybridize(**flags)
    return net


def _bn_batch():
    return (mx.nd.array(np.random.RandomState(0).randn(4, 3, 8, 8)),
            mx.nd.array(np.array([0, 1, 2, 1])))


def _bn_loss(net, heads=None):
    """Record forward + loss; the loss (a scalar, or the per-sample vector
    when the caller brings its own head gradient)."""
    x, y = _bn_batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
        return loss if heads else loss.mean()


def _grads(net):
    return [p.grad().asnumpy() for p in net.collect_params().values()
            if p.grad_req != "null"]


def _stats(net):
    return [p.data().asnumpy() for n, p in net.collect_params().items()
            if "running" in n]


def _cached_inputs(net):
    """(the block's CachedOp, its arguments by name as they stand, its
    auxiliary states, the names a recorded call differentiates)."""
    x, _ = _bn_batch()
    with autograd.pause():
        net(x)                               # builds the CachedOp
    op = net._cached_op
    held = {n: p.data()._data for n, p in net.collect_params().items()}
    held["data"] = x._data
    inputs = {n: held.get(n, held.get(net.prefix + n))
              for n in op._input_names}
    assert all(v is not None for v in inputs.values())
    return (op, {n: inputs[n] for n in op._arg_names},
            {n: inputs[n] for n in op._aux_names},
            tuple(n for n in op._arg_names if n != "data"))


def _jitted_calls(tmp_path, fn):
    """Names of the jitted calls `fn()` makes, from a `jax.profiler` host
    trace (independent of the program's own counters). jax writes each call
    as two `PjitFunction(<name>)` events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.backward"):
            fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, calls = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "test.backward":
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                elif e.name.startswith("PjitFunction("):
                    calls.append((e.name[len("PjitFunction("):-1],
                                  e.start_ns))
    assert len(spans) == 1
    names = sorted(n for n, t in calls if spans[0][0] <= t <= spans[0][1])
    assert len(names) % 2 == 0
    return names[::2]


# the two sides of ISSUE 49's default: a backward that recomputes the cheap
# activations from the MXU results (unset), and one that reads every
# intermediate the forward kept
SAVED = [{}, {"backward_do_mirror": False}]
SAVED_IDS = ["mxu-results", "everything"]


@pytest.mark.parametrize("flags", SAVED, ids=SAVED_IDS)
@pytest.mark.parametrize("heads", [False, True],
                         ids=["default-head", "given-head"])
def test_hybridized_backward_does_not_grow_with_its_batch_norms(
        tmp_path, heads, flags):
    calls = {}
    autograd._tape().clear()
    for n_bn in (1, 8):
        net = _bn_net(n_bn, **flags)
        head = mx.nd.ones((4,)) if heads else None
        for _ in range(2):
            _bn_loss(net, heads).backward(head)
        loss = _bn_loss(net, heads)
        nodes = len(autograd._tape())
        calls[n_bn] = _jitted_calls(tmp_path / str(n_bn),
                                    lambda: loss.backward(head))
    assert calls[1] == calls[8]
    # one program a tape node and none between them: the block and the
    # loss's own few operations (the mean too, where taken)
    assert nodes == (5 if heads else 6)
    assert calls[8] == ["_apply_vjp"] * nodes


@pytest.mark.parametrize("n_bn", [1, 3])
def test_hybridized_gradients_equal_the_eager_tapes(n_bn):
    hybrid, eager = _bn_net(n_bn), _bn_net(n_bn, hybrid=False)
    _bn_loss(hybrid).backward()
    _bn_loss(eager).backward()
    for a, b in zip(_grads(hybrid), _grads(eager)):
        assert np.abs(a).sum() > 0
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    for a, b in zip(_stats(hybrid), _stats(eager)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# what the parent (bb24960) wrote into the four statistics of _bn_net(2)
# after one recorded forward, on this seed
_PARENT_STATS = [
    [0.0030160362366586924, -0.0075761908665299416,
     -0.006369703449308872, 0.019041553139686584],
    [0.9934601783752441, 0.9843229651451111,
     0.9920638203620911, 0.9936774373054504],
    [-0.06526137888431549, -0.016486667096614838,
     0.015546808950603008, -0.06275202333927155],
    [0.939578652381897, 0.9296086430549622,
     0.9289587736129761, 0.9496206045150757]]


def test_running_statistics_are_written_back_as_before():
    """The statistics' updates are still computed and written back: to the
    bit what the forward gives when they are differentiated outputs (the
    parent's form, built here from the same plan), and the parent's own
    recorded numbers."""
    from mxnet_tpu.executor import build_graph_fn
    net = _bn_net(2)
    op, args, aux, diff = _cached_inputs(net)
    _bn_loss(net).backward()
    after = _stats(net)
    for got, want in zip(after, _PARENT_STATS):
        np.testing.assert_allclose(got, want, rtol=2e-6)

    graph_fn = build_graph_fn(op.symbol, is_train=True)

    def fwd_res(diff_list, rest, aux, rng_key):
        def pure(d):
            full = dict(rest)
            full.update(zip(diff, d))
            outs, aux_up = graph_fn(full, aux, rng_key)
            return tuple(outs), aux_up
        return jax.vjp(pure, diff_list)
    (_, aux_up), _ = jax.jit(fwd_res)(
        [args[n] for n in diff], args, aux, jax.random.PRNGKey(0))
    assert len(aux_up) == 4
    for name, got in zip(op._aux_names, after):
        np.testing.assert_array_equal(got, np.asarray(aux_up[name]))


@pytest.mark.parametrize("flags", SAVED, ids=SAVED_IDS)
def test_grad_req_add_accumulates_over_two_hybridized_backwards(flags):
    net, once = _bn_net(2, **flags), _bn_net(2, **flags)
    _bn_loss(once).backward()
    for p in net.collect_params().values():
        if p.grad_req != "null":
            p.grad_req = "add"
    for p in net.collect_params().values():
        p.zero_grad()
    # the second forward centres on the statistics the first one moved:
    # the same gradient, rounded otherwise
    for _ in range(2):
        _bn_loss(net).backward()
    for a, b in zip(_grads(net), _grads(once)):
        np.testing.assert_allclose(a, 2 * b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("flags", SAVED, ids=SAVED_IDS)
def test_retained_hybridized_graph_sweeps_twice_alike(flags):
    net = _bn_net(2, **flags)
    loss = _bn_loss(net)
    loss.backward(retain_graph=True)
    first = _grads(net)
    loss.backward()
    for a, b in zip(first, _grads(net)):
        np.testing.assert_array_equal(a, b)
    assert autograd._tape() == []


@pytest.mark.parametrize("n_bn", [1, 8])
@pytest.mark.parametrize("case", ["unset", "flag", "full"])
def test_backward_do_mirror_gives_the_same_gradients(monkeypatch, case,
                                                     n_bn):
    """Whatever the backward recomputes, the gradients are the
    save-everything path's (up to the order of a float sum) and the
    running statistics, which the forward alone writes, are its bits."""
    if case == "full":
        monkeypatch.setenv("MXNET_MIRROR_POLICY", "full")
    flags = {"backward_do_mirror": True} if case == "flag" else {}
    plain = _bn_net(n_bn, backward_do_mirror=False)
    mirror = _bn_net(n_bn, **flags)
    _bn_loss(plain).backward()
    _bn_loss(mirror).backward()
    for a, b in zip(_grads(plain), _grads(mirror)):
        assert np.abs(a).sum() > 0
        # tests/test_mirror.py test_hybridize_mirror_flag_grads_match's
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-5)
    for a, b in zip(_stats(plain), _stats(mirror)):
        np.testing.assert_array_equal(a, b)


# ------------------- what a recorded call hands its backward (ISSUE 49) ---
# By default the closure that crosses from the forward program to the
# backward holds the MXU operations' results and the reductions' (a batch
# norm's statistics) (executor.fwd_res_fn): no ReLU mask, no normalised
# activation, and none of the forward's own inputs, which are bound again
# on the host (executor.SavedForBackward).

def _handed_over(net):
    """What a recorded call of `net` returns for its backward, as
    `jax.eval_shape` gives it (nothing runs): (the leaves the forward
    program returns, all leaves of the closure the backward gets)."""
    op, args, aux, diff = _cached_inputs(net)
    call = ([args[n] for n in diff], args, aux, jax.random.PRNGKey(0))
    _, saved = jax.eval_shape(op._get_fn(True, diff), *call)
    return saved.saved, jax.tree.leaves(saved.bind(call))


def _nbytes(leaves):
    return sum(int(np.prod(v.shape)) * v.dtype.itemsize for v in leaves)


@pytest.mark.parametrize("n_bn", [1, 8])
def test_a_recorded_call_hands_over_mxu_results_and_statistics(n_bn):
    saved, closure = _handed_over(_bn_net(n_bn))
    kept, kept_closure = _handed_over(_bn_net(n_bn,
                                              backward_do_mirror=False))
    conv = ((4, 4, 8, 8), np.dtype("float32"))
    # the convolutions' results, and no other activation: the classifier's
    # result is the block's output, which no pullback reads
    assert [(v.shape, v.dtype) for v in saved
            if v.ndim == 4] == [conv] * n_bn
    # beside them the reductions' results: a mean and a variance a batch
    # norm, a vector a channel, and the pooled features
    assert sorted(v.shape for v in saved if v.ndim != 4) \
        == [(4,)] * (2 * n_bn) + [(4, 4)]
    assert all(v.dtype == np.float32 for v in saved)
    # save-everything keeps a mask a ReLU and the normalised activations
    assert sum(v.dtype == np.bool_ for v in kept) == n_bn
    assert len(kept) > 3 * n_bn and _nbytes(saved) < _nbytes(kept) / 2
    # the rest of either closure are the forward's own inputs, which the
    # program does not return: the batch, the classifier's weight, every
    # convolution's but the first's (nothing differentiates its input)
    # and, for the recomputation, each batch norm's scale, shift and the
    # running mean it centres on
    assert len(closure) - len(saved) == 4 * n_bn + 1
    held = len(closure) - len(saved)
    assert len(kept_closure) - len(kept) <= held


def test_the_mirror_knobs_choose_what_is_handed_over(monkeypatch):
    def handed(**flags):
        return _nbytes(_handed_over(_bn_net(3, **flags))[0])
    unset, everything = handed(), handed(backward_do_mirror=False)
    assert handed(backward_do_mirror=True) == unset < everything
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "0")
    assert handed() == everything
    assert handed(backward_do_mirror=True) == unset       # the flag wins
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    assert handed() == unset
    monkeypatch.setenv("MXNET_MIRROR_POLICY", "full")
    assert handed() == 0 < unset


def _cachedop_counts(core):
    c = core.counters()
    return tuple(int(c[k].total) if k in c else None
                 for k in ("cachedop.recorded_calls",
                           "cachedop.saved_buffers",
                           "cachedop.saved_bytes"))


@pytest.mark.parametrize("flags", SAVED, ids=SAVED_IDS)
def test_a_recorded_call_counts_what_it_hands_over(telemetry, flags):
    net = _bn_net(2, **flags)
    saved, _ = _handed_over(net)
    assert _cachedop_counts(telemetry) == (None, None, None)
    x, _ = _bn_batch()
    net(x)                                   # not recorded: not counted
    assert _cachedop_counts(telemetry) == (None, None, None)
    for _ in range(2):
        _bn_loss(net).backward()
    assert _cachedop_counts(telemetry) == (2, 2 * len(saved),
                                           2 * _nbytes(saved))
    if not flags:           # 2 results, 4 statistics, the pooled features
        assert len(saved) == 7
        assert _nbytes(saved) == (2 * 4 * 4 * 8 * 8 + 4 * 4 + 4 * 4) * 4


def test_the_recorded_calls_counters_do_not_exist_with_telemetry_off(
        monkeypatch):
    from mxnet_tpu.observability import core
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    _bn_loss(_bn_net(1)).backward()
    assert not [n for n in core.counters() if n.startswith("cachedop.")]


class _TwoHeads(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.fc = nn.Dense(3, in_units=4)
            self.bn = nn.BatchNorm(in_channels=3)

    def hybrid_forward(self, F, x):
        h = self.bn(self.fc(x))
        return h * 2, F.tanh(h)


@pytest.mark.parametrize("used", [0, 1])
def test_an_unused_output_of_a_hybridized_block_still_differentiates(used):
    """A multi-output block with one output unused: its cotangent is None
    and is filled inside the backward program."""
    def run(hybrid):
        np.random.seed(7)
        mx.random.seed(7)
        net = _TwoHeads()
        net.initialize(mx.init.Xavier())
        if hybrid:
            net.hybridize()
        rs = np.random.RandomState(1)
        x, w = mx.nd.array(rs.randn(5, 4)), mx.nd.array(rs.randn(5, 3))
        with autograd.record():
            loss = (net(x)[used] * w).sum()
        loss.backward()
        return _grads(net)
    for a, b in zip(run(True), run(False)):
        assert np.abs(a).sum() > 0
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_autograd_grad_through_a_cached_op_returns_what_backward_writes():
    # two nets: a training batch norm centres on the running mean, so a
    # second forward of one net rounds otherwise
    net, twin = _bn_net(2), _bn_net(2)
    _bn_loss(twin).backward()
    written = _grads(twin)
    params = [p.data() for p in net.collect_params().values()
              if p.grad_req != "null"]
    returned = autograd.grad(_bn_loss(net), params)
    for a, b in zip(written, returned):
        np.testing.assert_array_equal(a, b.asnumpy())


class _Split(autograd.Function):
    """Two outputs of a bare pullback; the test uses one."""

    def forward(self, x):
        return x * 2, x * 3

    def backward(self, da, db):
        return da * 2 + db * 3


@pytest.mark.parametrize("case,nodes,seeds", [
    ("hybrid-given-head", 5, 0),
    ("hybrid-default-head", 6, 1),
    ("eager-given-head", None, 0),
    ("function-unused-output", 3, 2),
])
def test_the_sweep_counts_its_pullbacks_and_eager_seeds(telemetry, case,
                                                        nodes, seeds):
    autograd._tape().clear()
    if case == "function-unused-output":
        x = mx.nd.array(np.arange(3.0))
        x.attach_grad()
        with autograd.record():
            loss = (_Split()(x)[0] * 1.0).sum()
        tape, head = len(autograd._tape()), None
        assert tape == nodes
    else:
        given = case.endswith("given-head")
        loss = _bn_loss(_bn_net(2, hybrid=case.startswith("hybrid")), given)
        tape = len(autograd._tape())
        head = mx.nd.ones((4,)) if given else None
        assert nodes is None or tape == nodes
    assert "autograd.pullbacks" not in telemetry.counters()
    loss.backward(head)
    counters = telemetry.counters()
    assert counters["autograd.pullbacks"].total == tape
    assert counters["autograd.eager_seeds"].total == seeds


def test_the_sweeps_counters_do_not_exist_with_telemetry_off(monkeypatch):
    from mxnet_tpu.observability import core
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    _bn_loss(_bn_net(1)).backward()
    assert not [n for n in core.counters() if n.startswith("autograd.")]
