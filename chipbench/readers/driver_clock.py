"""A number the driver took by the host's clock over the untraced window:
args {"key"} names it in the run's `clock` dict."""


def read(ctx, args):
    return (ctx.get("clock") or {}).get(args["key"])
