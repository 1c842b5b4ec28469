"""The Jamba hybrid through `models.serving.ContinuousBatcher(params, cfg,
max_batch=<clients>)` with every other argument at its default:
`serve_lm.py`'s session (admit / step / progress / counters / warm /
release) over the Jamba reference and the configuration file's keys
mapped onto the program's `TransformerConfig`."""

import jax.numpy as jnp

from ..reference import jamba as ref
from ..reference.common import OPERANDS
from . import serve_lm


def program_config(config):
    from mxnet_tpu.models import transformer as tf
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=config["num_hidden_layers"],
        layer_kinds=ref.layer_kinds(config),
        d_ff=config["intermediate_size"], ffn="gated_silu",
        positions="none", max_len=config["max_len"], dtype=jnp.bfloat16,
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        ssm_expand=config["mamba_expand"],
        ssm_dt_rank=config["mamba_dt_rank"])


class Session(serve_lm.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        # the configuration first: a program that cannot state this
        # architecture stops here, before 6 GB of weights are made
        cfg = program_config(config)
        self.srv = ContinuousBatcher(
            ref.as_tree(ref.init_weights(config, seed), config), cfg,
            max_batch=traffic["clients"])

    def reference(self, streams, operand=None):
        """streams: [(prompt_len, prompt + served tokens)]."""
        return ref.served_gaps(
            self.config, self.seed, streams,
            q_control=OPERANDS[operand] if operand else None)


def build(config, traffic, seed):
    return Session(config, traffic, seed)
