"""The plain ResNet-50 reference lays the network out as the Gluon model
zoo does: same parameter names and shapes, and in float32 the same logits
from the same weights."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import resnet50_v1 as ref

CFG = {"classes": 10, "image_size": 64}


@pytest.fixture(scope="module")
def net_and_weights():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import vision
    ctx = mx.cpu(0)
    net = vision.resnet50_v1(classes=CFG["classes"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    weights = ref.init_weights(CFG, 11)
    for name, p in net.collect_params().items():
        p.set_data(nd.NDArray(
            weights[name[len(net.prefix):]].astype(jnp.float32), ctx))
    return net, weights, ctx


def test_names_and_trained_flags_match_gluon(net_and_weights):
    net, weights, _ = net_and_weights
    params = net.collect_params()
    specs = ref.leaf_specs(CFG)
    assert [s[0] for s in specs] == [k[len(net.prefix):] for k in params]
    assert [s[3] for s in specs] == [p.grad_req != "null"
                                     for p in params.values()]
    assert sum(1 for s in specs if s[3]) == 161


def test_float32_logits_match_gluon(net_and_weights):
    from mxnet_tpu import autograd, nd
    net, weights, ctx = net_and_weights
    x, _ = ref.make_batch(CFG, 16, 11)
    with autograd.record():
        got = np.asarray(net(nd.NDArray(x.astype(jnp.float32), ctx))._data)
    want = np.asarray(ref.forward(weights, x, CFG))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 1e-2 * np.abs(want).max()
    shapes = {k[len(net.prefix):]: tuple(p.shape)
              for k, p in net.collect_params().items()}
    assert shapes == {k: tuple(v.shape) for k, v in weights.items()}
