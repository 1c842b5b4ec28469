"""LM training through `models.transformer.make_train_step` as it stands
(copied from chip_smoke.py's phase B(a), stripped to a step): bf16
parameters, fp32 momentum, one program a step, one seeded batch resident
on the device."""

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import cerebras_gpt as ref
from ..reference.common import OPERANDS, leaf_norms
from .lm_common import program_config, program_params


class Session(object):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models import transformer as tf
        self.config, self.seed = config, seed
        self.lr = config["optimizer"]["learning_rate"]
        rows, seq = traffic["batch"], traffic["seq"]
        self.items_per_step = rows * seq
        self.tokens_np = np.random.RandomState(
            int(seed) % (2 ** 32)).randint(
                1, config["vocab_size"], (rows, seq)).astype(np.int32)
        self.tokens = jnp.asarray(self.tokens_np)
        self.params = program_params(config, seed)
        self.mom = tf.init_momentum(self.params)
        self._step = tf.make_train_step(program_config(config), lr=self.lr)
        self._norms = jax.jit(leaf_norms)
        self._delta = jax.jit(lambda a, b: leaf_norms(
            {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
             for k in a}))

    # the window's own call
    def step(self):
        self.params, self.mom, loss = self._step(self.params, self.mom,
                                                 self.tokens)
        return loss

    def fetch(self, loss):
        return float(loss)

    def barrier(self):
        jax.block_until_ready(self.params["ln_f"])

    # what the comparison reads of the first steps
    def first_grad_norms(self):
        """After ONE step the momentum is the gradient the optimizer got
        (0.9 * 0 + g)."""
        return _floats(self._norms(ref.flatten_tree(self.mom)))

    def delta_norms(self):
        start = ref.init_weights(self.config, self.seed)
        return _floats(self._delta(ref.flatten_tree(self.params), start))

    def release(self):
        self.params = self.mom = None

    def reference(self, operand="float32", steps=3):
        return ref.train_reference(
            self.config, self.seed, jnp.asarray(self.tokens_np), steps,
            self.lr, self.config["optimizer"]["momentum"],
            q=OPERANDS[operand])


def _floats(tree):
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def build(config, traffic, seed):
    return Session(config, traffic, seed)
