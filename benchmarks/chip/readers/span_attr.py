"""The mean of a numeric attribute of the program's span records, over the
``spans`` that start in the window ``program_spans`` finds (the last
``steps`` batches handed to the caller), read in process after it.

``args``: ``spans`` (names), ``attr`` (the attribute, e.g. ``nthreads`` of
``parser.parse``: the team of that call).  Nothing when no such span in the
window carries the attribute as a number.
"""

import numbers


def mean_attr(records, names, attr, lo, hi):
    vals = [r["attrs"][attr] for r in records
            if r["name"] in names and lo <= r["mono_us"] * 1e-6 <= hi
            and isinstance(r.get("attrs", {}).get(attr), numbers.Real)]
    if not vals:
        return None
    return sum(vals) / len(vals)


def read(ctx, args):
    ps = ctx.manifest.module("readers", "program_spans")
    records = ps.span_records()
    win = ps.window(records, ctx.values.get("steps"), ctx.say)
    if win is None or win[1] <= win[0]:
        return None
    return mean_attr(records, args["spans"], args["attr"], *win)
