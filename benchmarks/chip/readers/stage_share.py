"""The busiest of the named host stages, as a share of the window's wall
time, from the program's own stage timers (``utils.metrics``).  A stage that
runs on its own thread can be busy all the window; the largest share says
which stage bounds the feed."""


def read(ctx, args):
    stages = ctx.values.get("stages")
    wall = ctx.values.get("window_wall_s")
    if not stages or not wall:
        return None
    got = [stages[s] for s in args["stages"] if s in stages]
    if not got or max(got) <= 0:
        return None
    return 100.0 * max(got) / wall
