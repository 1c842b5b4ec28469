"""One of the window's counts over another: args {"num", "den"} name keys
of the run's `counters` (program counters read before and after the
untraced window, and the driver's own counts)."""


def read(ctx, args):
    c = ctx.get("counters") or {}
    num, den = c.get(args["num"]), c.get(args["den"])
    if num is None or not den:
        return None
    return num / float(den)
