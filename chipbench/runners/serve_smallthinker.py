"""The first pipeline stage of SmallThinker-21BA3B through
`models.serving.ContinuousBatcher(params, cfg, max_batch=<clients>)` with
every other argument at its default: `serve_kimi_k2.py`'s session (admit /
step / progress / counters / warm by prefill width / release) over the
SmallThinker reference, and the configuration file's keys mapped onto the
program's `TransformerConfig`."""

import jax.numpy as jnp

from ..reference import smallthinker as ref
from ..reference.common import OPERANDS
from . import serve_kimi_k2


def program_config(config):
    """A program whose configuration has no window layers, no head width
    of its own, no rotation by layer or no router before the mixer cannot
    state this architecture: the constructor raises on the unknown field,
    before any weight is made."""
    from mxnet_tpu.models import transformer as tf
    plan = ref.layer_plan(config)
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attn_head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        layer_kinds=tuple("attention" if window is None else "window"
                          for _, window in plan),
        attn_window=config["sliding_window_size"],
        positions="rope", rope=True, rope_base=float(config["rope_theta"]),
        rope_layers=tuple(rotates for rotates, _ in plan),
        max_len=config["max_len"], dtype=jnp.bfloat16,
        tied_head=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], ffn="gated_relu",
        n_experts=config["moe_num_primary_experts"],
        experts_per_token=ref.routing_of(config),
        expert_scoring="softmax_topk", router_input="layer",
        d_expert=config["moe_ffn_hidden_size"])


class Session(serve_kimi_k2.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        # the configuration first: a program that cannot state this
        # architecture stops here, before 8 GB of weights are made
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
