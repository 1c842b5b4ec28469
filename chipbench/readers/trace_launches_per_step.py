"""Device program launches in the traced steps / steps."""


def read(ctx, args):
    t = ctx.get("trace")
    if not t or not t.get("steps"):
        return None
    return t["launches"] / float(t["steps"])
