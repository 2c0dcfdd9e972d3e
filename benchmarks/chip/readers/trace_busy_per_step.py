"""Device-busy milliseconds per completed step: the union of the intervals
in which an op ran on the device inside the traced window, over the steps
the window completed."""


def read(ctx, args):
    red, steps = ctx.trace_reduced, ctx.values.get("steps")
    if not red or not steps:
        return None
    return 1000.0 * red["busy_s"] / steps
