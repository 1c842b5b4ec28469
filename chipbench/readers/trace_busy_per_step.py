"""Device busy time (union of op intervals) per traced step, in ms."""


def read(ctx, args):
    t = ctx.get("trace")
    if not t or not t.get("steps"):
        return None
    return 1e3 * t["busy_s"] / t["steps"]
