"""A quantity the traffic kind or the harness measured itself
(``ctx.values[key]``), times ``scale``."""


def read(ctx, args):
    v = ctx.values.get(args["key"])
    if v is None:
        return None
    return float(v) * float(args.get("scale", 1.0))
