"""Model FLOP/s utilization, in %: model FLOPs per token x the tokens per
second of this run's untraced window / (chips x published peak). FLOPs come
from the configuration's shapes by `chipbench.flops`, never from XLA."""

from .. import flops, peaks


def read(ctx, args):
    rate = (ctx.get("clock") or {}).get(args["rate_key"])
    if rate is None:
        return None
    per_token = flops.lm_train_flops_per_token(ctx["config"],
                                               ctx["traffic"]["seq"])
    peak = peaks.peaks(ctx["device"]["kind"]).bf16_flops
    return 100.0 * per_token * rate / (ctx["chips"] * peak)
