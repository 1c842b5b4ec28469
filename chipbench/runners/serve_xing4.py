"""One pipeline stage of Xing4.0-29B-A4B through
`models.serving.ContinuousBatcher(params, cfg, max_batch=<clients>)` with
every other argument at its default: `serve_kimi_k2.py`'s session (admit /
step / progress / counters / warm by prefill width / release) over the
Xing4 reference, and the configuration file's keys mapped onto the
program's `TransformerConfig`: the Kimi-K2 mapping at this model's numbers,
with every routed expert held, and the four-stream frame's keys."""

import dataclasses

import jax.numpy as jnp

from ..reference import xing4 as ref
from ..reference.common import OPERANDS
from . import serve_kimi_k2


def program_config(config):
    """A program whose configuration has no `hc_mult` cannot state this
    architecture: `dataclasses.replace` raises on the unknown field,
    before any weight is made."""
    return dataclasses.replace(
        serve_kimi_k2.program_config(config),
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp_min=float(config["mhc_h_res_clamp_min"]),
        hc_clamp_max=float(config["mhc_h_res_clamp_max"]))


def program_params(weights, config):
    """The reference's seeded arrays in the program's tree: a frame's
    three projections side by side in one leaf (columns: read, write,
    mix) and its biases behind them in the same order."""
    tree = ref.as_tree(weights, config)
    for layer in tree["layers"]:
        for f in ref.FRAMES:
            phi, b = ([layer.pop("%s_%s_%s" % (f, kind, k))
                       for k in ("pre", "post", "res")]
                      for kind in ("phi", "b"))
            layer[f + "_phi"] = jnp.concatenate(phi, axis=1)
            layer[f + "_b"] = jnp.concatenate([x.reshape(-1) for x in b])
    return tree


class Session(serve_kimi_k2.Session):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        # the configuration first: a program that cannot state this
        # architecture stops here, before 8 GB of weights are made
        cfg = program_config(config)
        self.srv = ContinuousBatcher(
            program_params(ref.init_weights(config, seed), config), cfg,
            max_batch=traffic["clients"])

    def reference(self, streams, operand=None):
        """streams: [(prompt_len, prompt + served tokens)]."""
        return ref.served_gaps(
            self.config, self.seed, streams,
            q_control=OPERANDS[operand] if operand else None)


def build(config, traffic, seed):
    return Session(config, traffic, seed)
