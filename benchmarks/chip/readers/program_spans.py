"""Shares of the window and counts, from the program's own span records
(``dmlc_core_tpu.telemetry.trace.recorder``), read in process after the
window.  Every record carries its start on ``time.monotonic`` (``mono_us``)
and its duration (``dur_us``), so intervals of different threads compare.

The harness hands a reader no bounds, so the window is found on the
program's clock: the text-fed kinds call ``DeviceLoader.next_batch`` exactly
once with a batch coming back (``got`` true) for every step they count, and
never after the window.  The last ``ctx.values["steps"]`` such records are
the window's; it runs from the first one's start to the last one's end.

``args["what"]``:

* ``share``    seconds of the ``spans`` inside the window (summed, so K
               overlapping workers can pass 100), over the window, %.
* ``starved``  seconds the consumer sat in ``waiting`` that no span of
               ``covered_by`` (on any thread) covers, over the window, %:
               the caller waited for the feed while the feed was not
               waiting for the chip.
* ``starts``   how many of the ``spans`` start inside the window.

A program that records no such spans (a parent commit from before they
existed), or a ring that no longer holds the whole window, gives nothing.
"""

import xplane

NEXT_BATCH = "device_loader.next_batch"


def interval(rec: dict):
    """(start_s, end_s) of one record on the program's monotonic clock."""
    start = rec["mono_us"] * 1e-6
    return start, start + rec["dur_us"] * 1e-6


def span_records() -> list:
    """The program's finished spans that carry a monotonic start."""
    from dmlc_core_tpu.telemetry import trace
    return [r for r in trace.recorder.snapshot()
            if r.get("kind") == "span" and "mono_us" in r]


def named(records, names) -> list:
    return [interval(r) for r in records if r["name"] in names]


def window(records, steps, say=lambda msg: None):
    """(lo, hi) from the last ``steps`` batches handed to the caller, or
    None when the ring does not hold that many."""
    got = [r for r in records
           if r["name"] == NEXT_BATCH and r.get("attrs", {}).get("got")]
    if not steps or len(got) < steps:
        say(f"[program_spans] the window made {steps} steps and the ring "
            f"holds {len(got)} {NEXT_BATCH} records with a batch: nothing "
            f"to read")
        return None
    got = sorted(got, key=lambda r: r["mono_us"])[-int(steps):]
    return interval(got[0])[0], interval(got[-1])[1]


def share(records, names, lo, hi) -> float:
    return 100.0 * xplane.total(xplane.clip(named(records, names), lo, hi)) \
        / (hi - lo)


def starved(records, waiting, covered_by, lo, hi) -> float:
    cover = xplane.Cover(xplane.clip(named(records, covered_by), lo, hi))
    waits = xplane.clip(named(records, [waiting]), lo, hi)
    alone = sum((b - a) - cover.covered((a, b)) for a, b in waits)
    return 100.0 * alone / (hi - lo)


def starts(records, names, lo, hi) -> int:
    return sum(lo <= a <= hi for a, _ in named(records, names))


def read(ctx, args):
    records = span_records()
    win = window(records, ctx.values.get("steps"), ctx.say)
    if win is None or win[1] <= win[0]:
        return None
    what = args["what"]
    if what == "share":
        return share(records, args["spans"], *win)
    if what == "starved":
        return starved(records, args["waiting"], args["covered_by"], *win)
    if what == "starts":
        return starts(records, args["spans"], *win)
    raise ValueError(f"program_spans: what={what!r}")
