"""The Kimi-Linear share through `models.serving.ContinuousBatcher(params,
cfg, max_batch=<clients>)` with every other argument at its default:
`serve_lm.py`'s session (admit / step / progress / counters / warm /
release) over the Kimi-Linear reference and the configuration file's keys
mapped onto the program's `TransformerConfig`."""

import jax.numpy as jnp

from ..reference import kimi_linear as ref
from ..reference.common import OPERANDS
from . import serve_lm


def program_config(config):
    from mxnet_tpu.models import transformer as tf
    lin = config["linear_attn_config"]
    k, scale, offset = ref.routing_of(config)
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        layer_kinds=ref.layer_kinds(config),
        d_ff=config["intermediate_size"], ffn="gated_silu",
        positions="none", max_len=config["max_len"], dtype=jnp.bfloat16,
        tied_head=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        n_experts=ref.routed_experts(config), experts_per_token=k,
        expert_scoring="sigmoid", expert_scale=scale,
        experts_held=(offset, config["num_experts"]),
        d_expert=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        first_dense_layers=config["first_k_dense_replace"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        mla_rank=config["kv_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_v_dim=config["v_head_dim"])


class Session(serve_lm.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        # the configuration first: a program that cannot state this
        # architecture stops here, before 7.5 GB of weights are made
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
