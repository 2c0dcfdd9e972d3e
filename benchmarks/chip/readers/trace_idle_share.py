"""1 - busy union over the traced window, in percent."""


def read(ctx, args):
    red = ctx.trace_reduced
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
