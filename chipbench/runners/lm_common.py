"""What the two LM runners share: the configuration file's keys mapped onto
the program's `TransformerConfig`, and the program's parameter tree built
from the benchmark's own seeded weights."""

import jax.numpy as jnp

from ..reference import cerebras_gpt as ref


def program_config(config):
    from mxnet_tpu.models import transformer as tf
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=jnp.bfloat16, rope=False)


def program_params(config, seed):
    return ref.as_tree(ref.init_weights(config, seed), config)
