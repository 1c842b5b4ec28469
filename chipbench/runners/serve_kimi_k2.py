"""The Kimi-K2 share through `models.serving.ContinuousBatcher(params, cfg,
max_batch=<clients>)` with every other argument at its default:
`serve_lm.py`'s session (admit / step / progress / counters / release)
over the Kimi-K2 reference and the configuration file's keys mapped onto
the program's `TransformerConfig`."""

import jax.numpy as jnp
import numpy as np

from ..reference import kimi_k2 as ref
from ..reference.common import OPERANDS
from . import serve_lm


def program_config(config):
    from mxnet_tpu.models import transformer as tf
    k, scale, offset = ref.routing_of(config)
    ys = config["rope_scaling"]
    if ys["type"] != "yarn":
        raise ValueError("rope_scaling.type %r: yarn" % (ys["type"],))
    return tf.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        layer_kinds=("mla",) * config["num_hidden_layers"],
        d_ff=config["intermediate_size"], ffn="gated_silu",
        positions="rope", rope=True, rope_base=float(config["rope_theta"]),
        rope_scaling=tf.YarnScaling(
            factor=ys["factor"],
            original_max_len=ys["original_max_position_embeddings"],
            beta_fast=ys["beta_fast"], beta_slow=ys["beta_slow"],
            mscale=ys["mscale"], mscale_all_dim=ys["mscale_all_dim"]),
        max_len=config["max_len"], dtype=jnp.bfloat16,
        tied_head=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        n_experts=ref.routed_experts(config), experts_per_token=k,
        expert_scoring="sigmoid", expert_scale=scale,
        experts_held=(offset, config["n_routed_experts"]),
        d_expert=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        first_dense_layers=config["first_k_dense_replace"],
        mla_rank=config["kv_lora_rank"], mla_q_rank=config["q_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_v_dim=config["v_head_dim"])


class Session(serve_lm.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        # the configuration first: a program that cannot state this
        # architecture stops here, before 7 GB of weights are made
        cfg = program_config(config)
        self.srv = ContinuousBatcher(
            ref.as_tree(ref.init_weights(config, seed), config), cfg,
            max_batch=traffic["clients"])

    def warm(self, prompt_lengths):
        """Compile (or load) every program the traffic's lengths use. An
        admission's programs are one a WIDTH (whole chunks, then the
        rest at its power of two), so one two-token request for each
        width some prompt is prefilled in, the longest such prompt: a
        prefill of 4k-16k tokens for each of 64 lengths would be set-up
        spent on shapes that are already there."""
        from mxnet_tpu.models.serving import prefill_widths
        seen = set()
        for n in sorted(set(prompt_lengths), reverse=True):
            widths = set(prefill_widths(self.srv.cfg, n))
            if widths <= seen:
                continue
            seen |= widths
            self.srv.admit(np.ones((n,), np.int32), 2)
            while self.srv.active_count:
                self.srv.step()

    def reference(self, streams, operand=None):
        """streams: [(prompt_len, prompt + served tokens)]."""
        return ref.served_gaps(
            self.config, self.seed, streams,
            q_control=OPERANDS[operand] if operand else None)


def build(config, traffic, seed):
    return Session(config, traffic, seed)
