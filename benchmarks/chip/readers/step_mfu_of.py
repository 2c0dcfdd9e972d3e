"""The whole batch's share of the chip's peak for a document scorer whose
work is counted by the module ``args["work"]`` names (beside the harness;
its ``lm_forward(cfg, lengths)``): the least time the chip could take for
one forward's *needed* work (the configuration's shapes, the batch's
document lengths, the held experts a token meets; the larger of operations
over peak FLOP/s and bytes over peak bandwidth) over the wall time the
window took a batch.  None without a chip's peaks: never a number from a
CPU."""

import importlib

import flops


def read(ctx, args):
    work, steps = ctx.values.get("needed_work"), ctx.values.get("steps")
    wall = ctx.values.get("window_wall_s")
    if not work or not steps or not wall or ctx.peaks is None:
        return None
    _, lengths = work
    f, b = importlib.import_module(args["work"]).lm_forward(ctx.cfg, lengths)
    least, bound = flops.least_seconds(f, b, ctx.peaks)
    ctx.say(f"[mfu] {args['work']}: {len(lengths)} documents, "
            f"{sum(lengths)} tokens: {f:.4g} flops, {b:.4g} bytes -> least "
            f"{least * 1e3:.2f} ms ({bound}-bound) against "
            f"{1e3 * wall / steps:.2f} ms a batch")
    return 100.0 * least / (wall / steps)
