"""The longest single call of the program's own spans over the traced
sub-window, from `mxnet_tpu.observability.core.span_totals()`, which keeps
a `max_ns` beside every span's count and total: one stall of a hundred
milliseconds among a thousand rounds of ten moves a mean by a hundredth and
this number tenfold.

args: {"spans": [names]}

    the largest `max_ns` among `spans`, in ms

None without a trace, when the program has no span totals or none of
`spans` among them (a parent commit from before the span), and on the CPU
platform: a host time taken on a CPU is not a number of this benchmark. A
span the program seeds with zeros (`gc`: a window without a collection)
reads 0.0.
"""

from chipbench.readers import program_span


def read(ctx, args):
    if not ctx.get("trace") or ctx["device"]["platform"] == "cpu":
        return None
    totals = program_span._totals() or {}
    longest = [totals[n]["max_ns"] for n in args["spans"] if n in totals]
    if not longest:
        return None
    return max(longest) / 1e6
