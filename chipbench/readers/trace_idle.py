"""Device idle share of the traced window, in %: 1 - busy union / window."""


def read(ctx, args):
    t = ctx.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
