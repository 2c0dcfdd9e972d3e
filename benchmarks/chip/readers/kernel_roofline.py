"""One kernel's share of its roofline, from the device trace: the least
time the chip could take for the work the kernel's calls of one batch
*need* (``args["work"]``: ``module.function`` beside the harness, called
with the configuration and the batch's document lengths, ``(flops,
bytes)``; the larger of operations over peak FLOP/s and bytes over peak
bandwidth) over the device time those calls took.

The calls are the events of the ``XLA Ops`` line whose op is named
``args["kernel"]`` (``%<kernel>`` or ``%<kernel>.<n>``: a Pallas kernel's
``name``), a batch the events inside one run of the program
``args["module"]`` (``XLA Modules`` line) that lies whole inside the
window; the share is over the mean of those batches.  Needed work, so it
cannot pass 100.  Nothing to read gives nothing: no kept trace (a traffic
kind that keeps none), no chip's peaks, no whole run of the module, or no
event of that name (a program that lowers no such kernel)."""

import importlib
import re

import flops
import xplane


def kernel_seconds(trace: dict, kernel: str, module: str):
    """Device seconds of the kernel's events a whole run of ``module``
    inside the window, averaged over those runs and the chips; None where
    there is no such run or no such event."""
    named = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? ")
    marks = trace["host"].get("bench.window") or [(float("-inf"),
                                                   float("inf"))]
    w_lo, w_hi = min(a for a, _ in marks), max(b for _, b in marks)
    per_run = []
    for plane, runs in trace.get("modules", {}).items():
        calls = [(s, e) for n, s, e in trace["devices"].get(plane, ())
                 if named.match(n)]
        for name, lo, hi in runs:
            if name.split("(")[0] == module and w_lo <= lo and hi <= w_hi:
                per_run.append(xplane.total(
                    (s, e) for s, e in calls if lo <= s and e <= hi))
    if not per_run or not any(per_run):
        return None
    return sum(per_run) / len(per_run)


def read(ctx, args):
    trace, work = ctx.values.get("trace"), ctx.values.get("needed_work")
    if not trace or not work or ctx.peaks is None:
        return None
    took = kernel_seconds(trace, args["kernel"], args["module"])
    if not took:
        return None
    module, function = args["work"].rsplit(".", 1)
    f, b = getattr(importlib.import_module(module), function)(
        ctx.cfg, work[1])
    least, bound = flops.least_seconds(f, b, ctx.peaks)
    ctx.say(f"[roofline] {args['kernel']}: {f:.4g} flops, {b:.4g} bytes a "
            f"batch -> least {least * 1e3:.2f} ms ({bound}-bound) against "
            f"{took * 1e3:.2f} ms of device time a batch")
    return 100.0 * least / took
