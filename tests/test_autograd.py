"""Autograd tests: finite-difference gradient checks + scope semantics.

Reference strategy: tests/python/unittest/test_autograd.py and
check_numeric_gradient in python/mxnet/test_utils.py.
"""

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd


def assert_close(a, b, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


def numeric_grad(f, x, eps=1e-3):
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        i = it.multi_index
        orig = x[i]
        x[i] = orig + eps
        fp = f(x)
        x[i] = orig - eps
        fm = f(x)
        x[i] = orig
        g[i] = (fp - fm) / (2 * eps)
        it.iternext()
    return g


def test_simple_grad():
    x = nd.array(np.random.rand(3, 4))
    x.attach_grad()
    with autograd.record():
        y = (x * x + 2 * x).sum()
    y.backward()
    assert_close(x.grad.asnumpy(), 2 * x.asnumpy() + 2)


def test_chain_grad():
    xv = np.random.rand(4).astype(np.float32) + 0.5
    x = nd.array(xv)
    x.attach_grad()
    with autograd.record():
        y = nd.exp(nd.log(x) * 2.0).sum()  # = sum(x^2)
    y.backward()
    assert_close(x.grad.asnumpy(), 2 * xv, rtol=1e-3)


def test_finite_difference_matmul():
    xv = np.random.rand(3, 5).astype(np.float32)
    wv = np.random.rand(4, 5).astype(np.float32)
    x, w = nd.array(xv), nd.array(wv)
    w.attach_grad()
    with autograd.record():
        y = nd.FullyConnected(x, w, no_bias=True, num_hidden=4)
        loss = (y * y).sum()
    loss.backward()

    def f(wnp):
        return float(((xv @ wnp.T) ** 2).sum())
    ng = numeric_grad(f, wv.copy())
    assert_close(w.grad.asnumpy(), ng, rtol=1e-2, atol=1e-2)


def test_conv_grad_finite_difference():
    xv = np.random.rand(1, 2, 5, 5).astype(np.float32)
    wv = np.random.rand(3, 2, 3, 3).astype(np.float32)
    x, w = nd.array(xv), nd.array(wv)
    w.attach_grad()
    with autograd.record():
        y = nd.Convolution(x, w, kernel=(3, 3), num_filter=3, no_bias=True)
        loss = y.sum()
    loss.backward()

    import jax.numpy as jnp
    from jax import lax

    def f(wnp):
        out = lax.conv_general_dilated(
            jnp.asarray(xv), jnp.asarray(wnp), (1, 1), [(0, 0), (0, 0)],
            dimension_numbers=lax.conv_dimension_numbers(
                xv.shape, wnp.shape, ("NCHW", "OIHW", "NCHW")))
        return float(out.sum())
    ng = numeric_grad(f, wv.copy(), eps=1e-2)
    assert_close(w.grad.asnumpy(), ng, rtol=1e-2, atol=1e-1)


def test_head_gradient():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
    y.backward(nd.array([1.0, 10.0, 100.0]))
    assert_close(x.grad.asnumpy(), [2.0, 20.0, 200.0])


def test_grad_add_req():
    x = nd.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_close(x.grad.asnumpy(), 3 * 2 * x.asnumpy())


def test_detach_blocks_grad():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        z = y.detach() * x
    z.backward()
    assert_close(x.grad.asnumpy(), [6.0])  # only through second factor


def test_blockgrad_op():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = nd.BlockGrad(x * 3) * x
    y.backward()
    assert_close(x.grad.asnumpy(), [6.0])


def test_scopes():
    assert not autograd.is_recording()
    assert not autograd.is_training()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
        with autograd.pause():
            assert not autograd.is_recording()
        with autograd.predict_mode():
            assert not autograd.is_training()
    with autograd.train_mode():
        assert autograd.is_training()
        assert not autograd.is_recording()


def test_autograd_grad_fn():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x ** 3).sum()
    (g,) = autograd.grad([y], [x])
    assert_close(g.asnumpy(), 3 * x.asnumpy() ** 2)


def test_multi_output_op_grad():
    x = nd.array(np.random.rand(2, 6).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        parts = nd.split(x, num_outputs=3, axis=1)
        loss = (parts[0] * 1 + parts[1] * 2 + parts[2] * 3).sum()
    loss.backward()
    expect = np.concatenate([np.full((2, 2), i, np.float32) for i in (1, 2, 3)],
                            axis=1)
    assert_close(x.grad.asnumpy(), expect)


def test_shared_input_grad():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x  # same array used twice as op input
    y.backward()
    assert_close(x.grad.asnumpy(), [4.0])


def test_softmax_output_gradient():
    data = nd.array(np.random.rand(4, 3).astype(np.float32))
    label = nd.array([0.0, 1.0, 2.0, 0.0])
    data.attach_grad()
    with autograd.record():
        out = nd.SoftmaxOutput(data, label)
    out.backward()
    p = out.asnumpy()
    onehot = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
    assert_close(data.grad.asnumpy(), p - onehot, rtol=1e-4)


def test_custom_function():
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = nd.sigmoid(x)
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = nd.array(np.random.rand(5).astype(np.float32))
    x.attach_grad()
    f = Sigmoid()
    with autograd.record():
        y = f(x)
    y.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_close(x.grad.asnumpy(), s * (1 - s), rtol=1e-4)


def test_dropout_train_vs_predict():
    x = nd.ones((100, 100))
    with autograd.record(train_mode=True):
        y = nd.Dropout(x, p=0.5)
    frac = float((y.asnumpy() == 0).mean())
    assert 0.3 < frac < 0.7
    with autograd.predict_mode():
        y2 = nd.Dropout(x, p=0.5)
    assert float(y2.asnumpy().std()) == 0.0


def test_rnn_op_grad():
    seq, batch, inp, hid = 3, 2, 4, 5
    from mxnet_tpu.ops.nn import rnn_param_size
    psize = rnn_param_size("lstm", 1, inp, hid)
    params = nd.array(np.random.rand(psize).astype(np.float32) * 0.1)
    params.attach_grad()
    x = nd.array(np.random.rand(seq, batch, inp).astype(np.float32))
    h0 = nd.zeros((1, batch, hid))
    c0 = nd.zeros((1, batch, hid))
    with autograd.record():
        out = nd.RNN(x, params, h0, c0, state_size=hid, num_layers=1,
                     mode="lstm", state_outputs=True)
        loss = out[0].sum() if isinstance(out, list) else out.sum()
    loss.backward()
    assert params.grad.asnumpy().std() > 0


def test_astype_preserves_tape():
    """astype inside record() must route through Cast so mixed-precision
    chains (bf16 logits -> fp32 loss) stay differentiable."""
    import numpy as np
    x = mx.nd.array(np.ones((3,), np.float32))
    x.attach_grad()
    with mx.autograd.record():
        y = (x * 2).astype("float16")
        loss = mx.nd.sum(y.astype("float32") * 3)
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 6.0)


def test_backward_releases_saved_buffers_unless_retained():
    """backward() frees the graph's saved activations (the reference's
    retain_graph=False contract): an output the caller still holds must
    not pin the pullback's residuals into the next iteration."""
    x = mx.nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    node = y._ag_node[0]
    y.backward(retain_graph=True)
    assert node.vjp_fn is not None
    y.backward()
    assert node.vjp_fn is None and node.inputs == ()
    np.testing.assert_allclose(x.grad.asnumpy(), [2.0, 4.0, 6.0])
