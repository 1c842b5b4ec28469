"""Host time of the program's own spans over the traced sub-window, from
`mxnet_tpu.observability.core.span_totals()`: the per-name count and total
of the spans that ran while the run's one profiler session was live.

args: {"spans": [names], "per": "step" | "call" | "window"} and optionally
"minus": [names] (their totals are taken off first) and "complement": true
(with "per": "step": the wall step less the spans' share of it).

    per "step"     total ms / traced steps
    per "call"     total ms / calls of `spans`
    per "window"   total, in % of the traced window

None when no span of `spans` fired, when the program has no span totals
(a parent commit from before them), and on the CPU platform: a host time
taken on a CPU is not a number of this benchmark.
"""


def _totals():
    try:
        from mxnet_tpu.observability import core
        return core.span_totals()
    except (ImportError, AttributeError):
        return None


def read(ctx, args):
    trace = ctx.get("trace")
    if not trace or ctx["device"]["platform"] == "cpu":
        return None
    totals = _totals()
    if not totals or not any(n in totals for n in args["spans"]):
        return None

    def of(names, key):
        return sum(totals[n][key] for n in names if n in totals)

    ms = (of(args["spans"], "total_ns")
          - of(args.get("minus", ()), "total_ns")) / 1e6
    per = args["per"]
    if per == "step":
        if not trace.get("steps"):
            return None
        if args.get("complement"):
            ms = 1e3 * trace["window_s"] - ms
        return ms / trace["steps"]
    if per == "call":
        return ms / of(args["spans"], "count")
    if per == "window":
        if not trace.get("window_s"):
            return None
        return 100.0 * ms / (1e3 * trace["window_s"])
    raise ValueError("program_span: unknown per %r" % (per,))
