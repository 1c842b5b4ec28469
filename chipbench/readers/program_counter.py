"""A ratio of the program's own counters over the traced sub-window, from
`mxnet_tpu.observability.core.counters()`. The counters this reads are
added to only while the program's spans record; a benchmark run sets no
`MXNET_OBS`, so that is while the run's one profiler session is live, and
a counter's value is the traced window's (as `program_span.py`'s totals
are).

args: {"num": [names], "den": [names]} and optionally "scale":

    scale * product of the values of `num` / product of the values of `den`

None when the program has no such counter (a parent commit from before
them, or a window in which none was added to) and when a divisor is 0.
A count is a count on any platform, so a CPU run reports it too.
"""

import math


def _values():
    try:
        from mxnet_tpu.observability import core
        return {name: c.value for name, c in core.counters().items()}
    except (ImportError, AttributeError):
        return None


def read(ctx, args):
    if not ctx.get("trace"):
        return None
    values = _values()
    names = list(args["num"]) + list(args["den"])
    if not values or any(n not in values for n in names):
        return None
    den = math.prod(values[n] for n in args["den"])
    if not den:
        return None
    return args.get("scale", 1.0) \
        * math.prod(values[n] for n in args["num"]) / den
