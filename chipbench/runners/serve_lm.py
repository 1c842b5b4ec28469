"""LM serving through `models.serving.ContinuousBatcher(params, cfg,
max_batch=<clients>)` with every other argument at its default (copied
from chip_smoke.py's phase B(b), stripped to admit/step). The adapter adds
nothing to the program: `progress()` reads how many tokens each live
request has, the way `ContinuousBatcher.stream()` itself does."""

import numpy as np

from ..reference import cerebras_gpt as ref
from ..reference.common import OPERANDS
from .lm_common import program_config, program_params


class Session(object):
    def __init__(self, config, traffic, seed):
        from mxnet_tpu.models.serving import ContinuousBatcher
        self.config, self.seed = config, seed
        self.srv = ContinuousBatcher(program_params(config, seed),
                                     program_config(config),
                                     max_batch=traffic["clients"])

    def admit(self, prompt, n_new):
        return self.srv.admit(prompt, n_new)

    def step(self):
        return self.srv.step()

    def progress(self):
        return {r.rid: r.emitted for r in self.srv._slots if r is not None}

    def counters(self):
        return {"dispatches": self.srv.dispatch_count}

    def warm(self, prompt_lengths):
        """Compile (or load) every program the traffic's lengths use: one
        two-token request per distinct prompt length, run to its end."""
        for n in sorted(set(prompt_lengths)):
            self.srv.admit(np.ones((n,), np.int32), 2)
            while self.srv.active_count:
                self.srv.step()

    def release(self):
        self.srv = None

    def reference(self, streams, operand=None):
        """streams: [(prompt_len, prompt + served tokens)]."""
        return ref.served_gaps(
            self.config, self.seed, streams,
            q_control=OPERANDS[operand] if operand else None)


def build(config, traffic, seed):
    return Session(config, traffic, seed)
