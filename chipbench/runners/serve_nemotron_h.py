"""The first pipeline stage of NVIDIA-Nemotron-3-Nano-30B-A3B through
`models.serving.ContinuousBatcher(params, cfg, max_batch=<clients>)` with
every other argument at its default: `serve_kimi_k2.py`'s session (admit /
step / progress / counters / warm by prefill width / release) over the
Nemotron-H reference, and the configuration file's keys mapped onto the
program's `TransformerConfig`."""

import jax.numpy as jnp

from ..reference import nemotron_h as ref
from ..reference.common import OPERANDS
from . import serve_kimi_k2

# a block's kind in the program by its letter in the published pattern
KINDS = {"M": "mamba2", "E": "ffn", "*": "attention"}


def program_config(config):
    """A program whose configuration cannot say that a block holds one
    sub-layer, or has no Mamba-2 sizes, cannot state this architecture:
    the constructor raises on the unknown field, before any weight is
    made."""
    from mxnet_tpu.models import transformer as tf
    k, scale, offset = ref.routing_of(config)
    heads, head_dim, states, groups, taps = ref.mamba_sizes(config)
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        layer_kinds=tuple(KINDS[c] for c in ref.layer_plan(config)),
        mixer_ffn=False, ffn=config["mlp_hidden_act"],
        positions="none", max_len=config["max_len"], dtype=jnp.bfloat16,
        tied_head=config["tie_word_embeddings"],
        norm_eps=config["layer_norm_epsilon"],
        ssd_heads=heads, ssd_head_dim=head_dim, ssd_state=states,
        ssd_groups=groups, ssd_conv=taps, ssd_chunk=config["chunk_size"],
        n_experts=ref.routed_experts(config), experts_per_token=k,
        expert_scoring="sigmoid", expert_scale=scale,
        experts_held=(offset, config["n_routed_experts"]),
        d_expert=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"]
        // config["moe_intermediate_size"])


def program_sides(config, seed, weights=None):
    """(params, cfg) as the program serves them: the reference's seeded
    weights (or `weights`, the same flat dict) arranged into its tree,
    with the routed experts' width of 1,856 padded by zero hidden units
    to 1,920 = 15 x 128 by the program's own `pad_expert_width` (the
    same function: the configuration file's `departures`). The
    configuration first: a program that cannot state this architecture
    stops there, before 8 GB of weights are made."""
    from mxnet_tpu.models import transformer as tf
    cfg = program_config(config)
    # no name keeps the unpadded stacks: the helper drops each as it goes
    return tf.pad_expert_width(ref.as_tree(
        ref.init_weights(config, seed) if weights is None else weights,
        config), cfg)


class Session(serve_kimi_k2.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        self.srv = ContinuousBatcher(*program_sides(config, seed),
                                     max_batch=traffic["clients"])

    def reference(self, streams, operand=None):
        """streams: [(prompt_len, prompt + served tokens)]."""
        return ref.served_gaps(
            self.config, self.seed, streams,
            q_control=OPERANDS[operand] if operand else None)


def build(config, traffic, seed):
    return Session(config, traffic, seed)
