"""ResNet-50 training through the entry points a Gluon user writes (copied
from chip_smoke.py's phase A(a), stripped to a step): `initialize`, `cast`,
`hybridize`, `autograd.record`, `backward`, `Trainer.step`; bf16 compute,
fp32 master weights (`multi_precision`), one seeded batch resident on the
device. The weights are the benchmark's own (set with `set_data` before the
first forward), so the reference starts from the same numbers."""

import jax
import jax.numpy as jnp

from ..reference import resnet50_v1 as ref
from ..reference.common import OPERANDS


class Session(object):
    def __init__(self, config, traffic, seed):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon, nd
        from mxnet_tpu.gluon.model_zoo import vision
        self.config, self.seed = config, seed
        self.batch = self.items_per_step = traffic["batch"]
        opt = config["optimizer"]
        self.lr = opt["learning_rate"]
        ctx = mx.cpu(0) if jax.default_backend() == "cpu" else mx.tpu(0)
        self._autograd = autograd
        net = vision.resnet50_v1(classes=config["classes"])
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.cast("bfloat16")
        net.hybridize()
        weights = ref.init_weights(config, seed)
        self._names = {}
        for name, p in net.collect_params().items():
            short = name[len(net.prefix):]
            self._names[name] = short
            p.set_data(nd.NDArray(weights[short], ctx))
        self.net = net
        self.trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": self.lr, "momentum": opt["momentum"],
             "multi_precision": True}, kvstore="device")
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        x, y = ref.make_batch(config, self.batch, seed)
        self.x = nd.NDArray(x, ctx)
        self.y = nd.NDArray(y.astype(jnp.float32), ctx)
        self._norms = jax.jit(lambda xs: [
            jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for v in xs])

    # the window's own call
    def step(self):
        with self._autograd.record():
            self._samples = self.loss_fn(
                self.net(self.x).astype("float32"), self.y)
            loss = self._samples.mean()
        loss.backward()
        self.trainer.step(1)
        return loss

    def fetch(self, loss):
        return float(loss.asnumpy())

    def barrier(self):
        last = list(self.net.collect_params().values())[-1]
        jax.block_until_ready(last.data()._data)

    # what the comparison reads of the first steps
    def _states(self):
        """[(short name, momentum, master-or-weight)] of trained params,
        from the optimizer's own state."""
        states = self.trainer._updaters[0].states
        out = []
        for i, p in enumerate(self.trainer._params):
            if p.grad_req == "null":
                continue
            s = states[i]
            master, mom = s if isinstance(s, tuple) else (p.data(), s)
            out.append((self._names[p.name], mom._data, master._data))
        return out

    def sample_losses(self):
        """The last step's loss, image by image (the array `.mean()` was
        taken of)."""
        return [float(v) for v in self._samples.asnumpy()]

    def first_grad_norms(self):
        """After ONE step the momentum is -lr x the gradient the optimizer
        got (momentum * 0 - lr * g)."""
        st = self._states()
        norms = jax.device_get(self._norms([m for _, m, _ in st]))
        return {n: float(v) / self.lr for (n, _, _), v in zip(st, norms)}

    def delta_norms(self):
        start = ref.init_weights(self.config, self.seed)
        st = self._states()
        norms = jax.device_get(self._norms(
            [w.astype(jnp.float32) - start[n].astype(jnp.float32)
             for n, _, w in st]))
        return {n: float(v) for (n, _, _), v in zip(st, norms)}

    def release(self):
        self.net = self.trainer = self.x = self.y = None

    def reference(self, operand="float32", steps=3):
        return ref.train_reference(
            self.config, self.seed, self.batch, steps, self.lr,
            self.config["optimizer"]["momentum"], q=OPERANDS[operand])


def build(config, traffic, seed):
    return Session(config, traffic, seed)
