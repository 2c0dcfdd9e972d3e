"""The whole step's share of the chip's peak: the least time the chip could
take for one step's *needed* work (``flops.py``: touched rows only; the
larger of operations over peak FLOP/s and bytes over peak bandwidth) over
the wall time the window took per step.  Says which bound it was on an
earlier line.  None without a chip's peaks: never a number from a CPU."""

import flops


def read(ctx, args):
    work, steps = ctx.values.get("needed_work"), ctx.values.get("steps")
    wall = ctx.values.get("window_wall_s")
    if not work or not steps or not wall or ctx.peaks is None:
        return None
    name, shape = work
    f, b = getattr(flops, name)(*shape)
    least, bound = flops.least_seconds(f, b, ctx.peaks)
    ctx.say(f"[mfu] {name}{tuple(round(x) for x in shape)}: {f:.3g} flops, "
            f"{b:.3g} bytes -> least {least * 1e3:.4f} ms ({bound}-bound) "
            f"against {1e3 * wall / steps:.4f} ms a step")
    return 100.0 * least / (wall / steps)
