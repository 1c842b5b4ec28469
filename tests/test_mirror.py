"""Gradient mirroring / activation recompute (VERDICT r2 item 4).

Reference: MXNET_BACKWARD_DO_MIRROR (src/nnvm/gradient.cc:285, executor
switch src/executor/graph_executor.cc:351-357) — trade recompute FLOPs
for backward memory. TPU mapping: jax.checkpoint around the traced graph
(executor.apply_mirror) and per-layer remat on the transformer.

Residual memory is measured directly: the executor's saved vjp closure
is a pytree of residual arrays, so summing leaf bytes gives the saved-
activation footprint on any backend."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


def _residual_bytes(executor):
    vjp, _ = executor._saved_vjp
    return sum(x.nbytes for x in jax.tree.leaves(vjp)
               if hasattr(x, "nbytes"))


def _deep_sym(n_layers=8, hidden=64):
    x = mx.sym.Variable("data")
    for i in range(n_layers):
        x = mx.sym.FullyConnected(x, num_hidden=hidden, name="fc%d" % i)
        x = mx.sym.Activation(x, act_type="tanh", name="act%d" % i)
    return mx.sym.sum(x, name="out")


def _bind_forward_backward(sym, env):
    rng = np.random.RandomState(0)
    args = {n: mx.nd.array(rng.randn(*s) * 0.1) for n, s in zip(
        sym.list_arguments(),
        sym.infer_shape(data=(16, 64))[0])}
    grads = {n: mx.nd.zeros(a.shape) for n, a in args.items()}
    for k, v in env.items():
        import os
        os.environ[k] = v
    try:
        ex = sym.bind(mx.cpu(), args, args_grad=grads)
        ex.forward(is_train=True)
        ex.backward()
    finally:
        import os
        for k in env:
            os.environ.pop(k, None)
    return ex, grads


def test_executor_mirror_shrinks_residuals_and_matches_grads():
    sym = _deep_sym()
    # the save-everything side is asked for: unset is the dots policy
    ex_base, g_base = _bind_forward_backward(
        sym, {"MXNET_BACKWARD_DO_MIRROR": "0"})
    ex_full, g_full = _bind_forward_backward(
        sym, {"MXNET_BACKWARD_DO_MIRROR": "1", "MXNET_MIRROR_POLICY": "full"})
    ex_dots, g_dots = _bind_forward_backward(
        sym, {"MXNET_BACKWARD_DO_MIRROR": "1", "MXNET_MIRROR_POLICY": "dots"})

    ex_unset, g_unset = _bind_forward_backward(sym, {})

    b_base = _residual_bytes(ex_base)
    b_full = _residual_bytes(ex_full)
    b_dots = _residual_bytes(ex_dots)
    # an Executor bound with nothing set latches the dots policy
    assert _residual_bytes(ex_unset) == b_dots
    for n in g_dots:
        np.testing.assert_array_equal(g_unset[n].asnumpy(),
                                      g_dots[n].asnumpy())
    # full mirroring keeps only inputs; dots keeps MXU outputs too;
    # both must be strictly smaller than the unmirrored residual set
    assert b_full < b_base, (b_full, b_base)
    assert b_dots < b_base, (b_dots, b_base)
    assert b_full <= b_dots

    for n in g_base:
        np.testing.assert_allclose(g_base[n].asnumpy(),
                                   g_full[n].asnumpy(), rtol=2e-5,
                                   atol=2e-6)
        np.testing.assert_allclose(g_base[n].asnumpy(),
                                   g_dots[n].asnumpy(), rtol=2e-5,
                                   atol=2e-6)


def test_invalid_mirror_policy_raises():
    import os
    sym = _deep_sym(2)
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    os.environ["MXNET_MIRROR_POLICY"] = "bogus"
    try:
        with pytest.raises(mx.MXNetError):
            _bind_forward_backward(sym, {})
    finally:
        os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
        os.environ.pop("MXNET_MIRROR_POLICY", None)


def _gluon_grads(mirror):
    mx.random.seed(0)
    rng = np.random.RandomState(1)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        for _ in range(3):
            net.add(gluon.nn.Dense(32, activation="relu"))
            net.add(gluon.nn.BatchNorm())
        net.add(gluon.nn.Dropout(0.3))
        net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    # None: nothing said, the default
    net.hybridize(**({} if mirror is None
                     else {"backward_do_mirror": mirror}))
    x = mx.nd.array(rng.randn(8, 16))
    params = net.collect_params()
    with autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    return {k: p.grad().asnumpy() for k, p in params.items()
            if p.grad_req != "null"}


@pytest.mark.parametrize("mirror", [True, None], ids=["flag", "unset"])
def test_hybridize_mirror_flag_grads_match(mirror):
    """hybridize(backward_do_mirror=True), and since PR 49 hybridize()
    alone, route CachedOp through remat; gradients (incl. through
    BatchNorm aux stats and Dropout rng: the backward draws the mask
    again from the same key) must be identical to the unmirrored
    trace, hybridize(backward_do_mirror=False)."""
    base = _gluon_grads(False)
    mirrored = _gluon_grads(mirror)
    assert len(base) == len(mirrored) and base
    # parameter names carry distinct auto name-scope prefixes
    # (hybridsequential0_ vs hybridsequential1_); compare by sorted order
    for kb, km in zip(sorted(base), sorted(mirrored)):
        assert kb.split("_", 1)[1] == km.split("_", 1)[1], (kb, km)
        # remat reorders float accumulation (activations are recomputed
        # in backward), so equality is up to reassociation noise
        np.testing.assert_allclose(base[kb], mirrored[km], rtol=2e-3,
                                   atol=1e-5)


class _CountedSquare(mx.operator.CustomOp):
    entered = {"forward": 0, "backward": 0}

    def forward(self, is_train, req, in_data, out_data, aux):
        self.entered["forward"] += 1
        self.assign(out_data[0], req[0], in_data[0] * in_data[0])

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.entered["backward"] += 1
        self.assign(in_grad[0], req[0], 2 * in_data[0] * out_grad[0])


@mx.operator.register("mirror_counted_square")
class _CountedSquareProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return _CountedSquare()


class _AroundCustom(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.first = gluon.nn.Dense(8, in_units=6)
            self.last = gluon.nn.Dense(4, in_units=8)

    def hybrid_forward(self, F, x):
        h = F.Activation(self.first(x), act_type="tanh")
        h = F.Custom(h, op_type="mirror_counted_square")
        return self.last(F.Activation(h, act_type="tanh"))


@pytest.mark.parametrize("env", [
    {}, {"MXNET_MIRROR_POLICY": "full"}, {"MXNET_BACKWARD_DO_MIRROR": "0"}],
    ids=["unset", "full", "off"])
def test_a_custom_operators_python_forward_is_entered_once_a_step(
        monkeypatch, env):
    """What a host callback returned is saved under either policy: a
    backward that recomputes the activations around a Custom operator
    does not call its Python forward again."""
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    mx.random.seed(0)
    net = _AroundCustom()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(4, 6))
    _CountedSquare.entered.update(forward=0, backward=0)
    grads = []
    for _ in range(2):
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        grads.append(net.first.weight.grad().asnumpy().copy())
    assert _CountedSquare.entered == {"forward": 2, "backward": 2}
    assert np.abs(grads[0]).sum() > 0
    np.testing.assert_array_equal(grads[0], grads[1])


def test_transformer_remat_layers_matches_and_shrinks_memory():
    """cfg.remat_layers: same loss/grads, smaller compiled temp memory
    (when the backend reports it)."""
    from mxnet_tpu.models import transformer as T

    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
               d_ff=128, max_len=64, use_ring_attention=False)
    base_cfg = T.TransformerConfig(**cfg)
    remat_cfg = T.TransformerConfig(remat_layers=True, **cfg)

    params = T.init_params(base_cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 64)), jnp.int32)

    g_base = jax.grad(T.loss_fn)(params, tokens, base_cfg)
    g_remat = jax.grad(T.loss_fn)(params, tokens, remat_cfg)
    for a, b in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)

    def residual_bytes(cfg_):
        # eager vjp stores the pullback residuals as concrete arrays —
        # a backend-independent measure of saved-activation memory
        _, vjp = jax.vjp(lambda p: T.loss_fn(p, tokens, cfg_), params)
        return sum(x.nbytes for x in jax.tree.leaves(vjp)
                   if hasattr(x, "nbytes"))

    b_base, b_remat = residual_bytes(base_cfg), residual_bytes(remat_cfg)
    assert b_remat < b_base, (b_remat, b_base)


def test_residual_compression_knobs_match_gradients():
    """MXNET_RELU_MASK_RESIDUAL and MXNET_BN_BF16_RESIDUAL change the
    SAVED-residual format, not the math: gradients must match the
    default path to (bf16-)reassociation tolerance."""
    import os
    import subprocess
    import sys

    script = r'''
import os, sys
sys.path.insert(0, %r)
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd
rng = np.random.RandomState(0)
x = mx.nd.array(rng.randn(4, 3, 8, 8).astype("float32"))
w = mx.nd.array(rng.randn(8, 3, 3, 3).astype("float32")); w.attach_grad()
g = mx.nd.ones((8,)); g.attach_grad()
b = mx.nd.zeros((8,)); b.attach_grad()
mm = mx.nd.zeros((8,)); mv = mx.nd.ones((8,))
with autograd.record():
    y = mx.nd.Convolution(x, w, no_bias=True, kernel=(3, 3), num_filter=8)
    z = mx.nd.BatchNorm(y, g, b, mm, mv, fix_gamma=False)
    r = mx.nd.Activation(z, act_type="relu")
    ((r * r).sum()).backward()
np.save(sys.argv[1], np.concatenate(
    [w.grad.asnumpy().ravel(), g.grad.asnumpy().ravel(),
     b.grad.asnumpy().ravel()]))
''' % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    import numpy as np
    import tempfile
    outs = {}
    with tempfile.TemporaryDirectory() as td:
        for name, env in (("base", {}),
                          ("compressed", {"MXNET_RELU_MASK_RESIDUAL": "1",
                                          "MXNET_BN_BF16_RESIDUAL": "1"})):
            out = os.path.join(td, name + ".npy")
            e = dict(os.environ)
            e.update(env)
            r = subprocess.run([sys.executable, "-c", script, out],
                               env=e, capture_output=True, timeout=300)
            assert r.returncode == 0, r.stderr[-1500:]
            outs[name] = np.load(out)
    # in fp32 the two formulations coincide exactly (the knobs change
    # the saved-residual FORMAT, visible only for bf16 activations —
    # benchmark/activation_residual_ab.py measures that); grads must
    # match tightly either way
    np.testing.assert_allclose(outs["compressed"], outs["base"],
                               rtol=1e-5, atol=1e-5)


def test_maxpool_index_residual_first_max_ties_and_grads():
    """Native reduce_window max pooling (default) and the opt-in
    index-residual path agree on tie-free data, and ties follow the
    reference's FIRST-max convention (mshadow pooling backward) instead
    of jnp.maximum's 0.5/0.5 split."""
    import os
    import subprocess
    import sys

    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    # tie-free random data: both paths agree
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(2, 3, 8, 8) + np.arange(64).reshape(8, 8)
                    * 1e-3)
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                          pool_type="max")
        (y * y).sum().backward()
    g_index = x.grad.asnumpy().copy()

    env = dict(os.environ)
    # opt-in index path in the subprocess (default is the native
    # reduce_window path the in-process leg above just used)
    env["MXNET_POOL_INDEX_RESIDUAL"] = "1"
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import os; os.environ['JAX_PLATFORMS']='cpu'\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import autograd\n"
        "rng = np.random.RandomState(0)\n"
        "x = mx.nd.array(rng.randn(2, 3, 8, 8)"
        " + np.arange(64).reshape(8, 8) * 1e-3)\n"
        "x.attach_grad()\n"
        "with autograd.record():\n"
        "    y = mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2),"
        " pool_type='max')\n"
        "    (y * y).sum().backward()\n"
        "np.save(sys.argv[1], x.grad.asnumpy())\n"
        % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "g.npy")
        r = subprocess.run([sys.executable, "-c", code, out], env=env,
                           capture_output=True, timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]
        g_tree = np.load(out)
    np.testing.assert_allclose(g_index, g_tree, rtol=1e-5, atol=1e-6)

    # ties: all-equal window routes the WHOLE cotangent to the first
    # position (reference convention)
    t = mx.nd.zeros((1, 1, 2, 2))
    t.attach_grad()
    with autograd.record():
        y = mx.nd.Pooling(t, kernel=(2, 2), stride=(2, 2),
                          pool_type="max")
        y.sum().backward()
    np.testing.assert_array_equal(
        t.grad.asnumpy()[0, 0], [[1.0, 0.0], [0.0, 0.0]])


def test_maxpool_index_residual_large_kernel():
    """Window index must not wrap for kernels with > 256 offsets
    (uint8 would route gradients to wrong positions). Forces the
    opt-in index path — the native reduce_window default keeps no
    index at all."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(1, 1, 20, 20))
    x.attach_grad()
    os.environ["MXNET_POOL_INDEX_RESIDUAL"] = "1"
    try:
        with autograd.record():
            # 17x17 kernel = 289 offsets > 256
            y = mx.nd.Pooling(x, kernel=(17, 17), stride=(1, 1),
                              pool_type="max")
            y.sum().backward()
    finally:
        del os.environ["MXNET_POOL_INDEX_RESIDUAL"]
    g = x.grad.asnumpy()[0, 0]
    xa = x.asnumpy()[0, 0]
    # each 17x17 window contributes 1.0 at its (first) argmax; verify
    # total mass and that every contribution landed on a window max
    assert g.sum() == y.size
    nz = np.argwhere(g > 0)
    for r, c in nz:
        # the touched position must be the max of at least one window
        # containing it
        found = False
        for wr in range(max(0, r - 16), min(4, r + 1)):
            for wc in range(max(0, c - 16), min(4, c + 1)):
                win = xa[wr:wr + 17, wc:wc + 17]
                if xa[r, c] == win.max():
                    found = True
                    break
            if found:
                break
        assert found, (r, c)


def test_int8_conv_residual_dx_exact_dw_close():
    """MXNET_INT8_RESIDUAL=1 (opt-in, lossy): the conv input-gradient
    stays EXACT (it reads only the weights), the weight gradient is
    computed from the int8-reconstructed activation with a small
    relative error, and the saved residual really is int8."""
    import os
    import subprocess
    import sys

    script = r'''
import os, sys
sys.path.insert(0, %r)
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax
import jax.numpy as jnp
from mxnet_tpu import ops
conv = ops.get("Convolution").fn
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(4, 3, 10, 10).astype("float32"))
w = jnp.asarray(rng.randn(8, 3, 3, 3).astype("float32"))

def f(x, w):
    return (conv(x, w, no_bias=True, kernel=(3, 3), num_filter=8) ** 2).sum()

(dx, dw) = jax.grad(f, argnums=(0, 1))(x, w)
res = jax.vjp(lambda a: conv(a, w, no_bias=True, kernel=(3, 3),
                             num_filter=8), x)[1]
dtypes = sorted({str(l.dtype) for l in jax.tree.leaves(res)})
np.savez(sys.argv[1], dx=np.asarray(dx), dw=np.asarray(dw),
         dtypes=np.array(dtypes))
''' % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    import numpy as np
    import tempfile
    outs = {}
    with tempfile.TemporaryDirectory() as td:
        for name, env in (("base", {}),
                          ("int8", {"MXNET_INT8_RESIDUAL": "1"})):
            out = os.path.join(td, name + ".npz")
            e = dict(os.environ)
            e.update(env)
            r = subprocess.run([sys.executable, "-c", script, out],
                               env=e, capture_output=True, timeout=300)
            assert r.returncode == 0, r.stderr[-1500:]
            outs[name] = np.load(out)
    np.testing.assert_allclose(outs["int8"]["dx"], outs["base"]["dx"],
                               rtol=1e-6, atol=1e-6)
    ref = outs["base"]["dw"]
    err = np.abs(outs["int8"]["dw"] - ref).max() / np.abs(ref).max()
    assert err < 2e-2, err          # int8 reconstruction error bound
    assert err > 0                  # and it IS the lossy path
    assert "int8" in list(outs["int8"]["dtypes"])
    assert "int8" not in list(outs["base"]["dtypes"])


def test_residual_knob_toggle_retraces_cached_op(monkeypatch):
    """In-process env toggles of the residual-format knobs must retrace
    the CachedOp compiled fn, not reuse the stale program (the
    MXNET_BACKWARD_DO_MIRROR cache-aliasing class)."""
    import os
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    monkeypatch.delenv("MXNET_INT8_RESIDUAL", raising=False)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.Dense(2))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).rand(2, 3, 8, 8)
                    .astype("float32"))
    with autograd.record():
        net(x).sum().backward()
    cached = net._cached_op
    n_before = len(cached._fns)
    monkeypatch.setenv("MXNET_INT8_RESIDUAL", "1")
    with autograd.record():
        net(x).sum().backward()
    assert len(cached._fns) > n_before
