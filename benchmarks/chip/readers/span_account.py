"""What the span records' second clock says about the feed's threads, over
the window ``program_spans`` finds (the last ``steps`` batches handed to the
caller), read in process after it.

A record carries ``dur_us`` (wall time) and ``cpu_us``: the CPU time of the
thread that ran the span, over the same extent.  ``dur_us - cpu_us`` is the
time that thread was not running — blocked on a lock, the GIL or the
device, or runnable with no core to run on.  ``cpu_us`` is that thread's
alone: what a native call's own workers burned (the parser's OpenMP team,
the runtime's transfer threads) is not in it.  Those show in
``proc_cpu_us``, the process's CPU clock (``time.process_time_ns``, every
thread) that each ``device_loader.next_batch`` record takes at its end.
Both clocks tick as the host's kernel accounts CPU time: by 10 ms on the
chip's machine (a record's ``cpu_us`` reads 0 or 10 000 there), so a single
span's ``cpu_us`` says nothing and every reading here is a sum over a window.

``args["what"]``:

* ``offcpu``       sum of ``dur_us - cpu_us`` of the ``spans`` that start in
                   the window, over the window, %: their time with the
                   thread not running.
* ``cpu_cores``    ``proc_cpu_us`` at the window's last hand-over less that
                   at its first, over the time between the two: the cores
                   the whole process kept busy.
* ``ratio_under``  mean ``dur_us`` of the ``spans`` that an ``under`` span
                   of another thread overlaps by half or more, over the mean
                   of the rest; nothing when either set has under 10.
* ``longest``      the longest of the ``spans`` that start in the window,
                   ms.

A program whose records carry no ``cpu_us`` (a parent commit from before
they did) gives nothing, whatever is asked.
"""

NEXT_BATCH = "device_loader.next_batch"


def starting_in(records, names, lo, hi) -> list:
    return [r for r in records
            if r["name"] in names and lo <= r["mono_us"] * 1e-6 <= hi]


def offcpu(records, names, lo, hi):
    spans = [r for r in starting_in(records, names, lo, hi) if "cpu_us" in r]
    if not spans:
        return None
    off = sum(max(0, r["dur_us"] - r["cpu_us"]) for r in spans)
    return 100.0 * off * 1e-6 / (hi - lo)


def cpu_cores(records, lo, hi):
    got = sorted((r for r in starting_in(records, [NEXT_BATCH], lo, hi)
                  if r.get("attrs", {}).get("got")
                  and "proc_cpu_us" in r["attrs"]),
                 key=lambda r: r["mono_us"])
    if len(got) < 2:
        return None
    first, last = got[0], got[-1]
    wall_us = (last["mono_us"] + last["dur_us"]) \
        - (first["mono_us"] + first["dur_us"])
    if wall_us <= 0:
        return None
    return (last["attrs"]["proc_cpu_us"]
            - first["attrs"]["proc_cpu_us"]) / wall_us


def ratio_under(records, names, under, lo, hi, least=10):
    spans = starting_in(records, names, lo, hi)
    cover = sorted((r["mono_us"], r["mono_us"] + r["dur_us"], r["tid"])
                   for r in records if r["name"] in under)
    inside, rest = [], []
    for r in spans:
        a, b = r["mono_us"], r["mono_us"] + r["dur_us"]
        overlap = sum(min(b, d) - max(a, c) for c, d, tid in cover
                      if tid != r["tid"] and c < b and d > a)
        (inside if 2 * overlap >= (b - a) else rest).append(r["dur_us"])
    if len(inside) < least or len(rest) < least:
        return None
    return (sum(inside) / len(inside)) / (sum(rest) / len(rest))


def longest(records, names, lo, hi):
    spans = starting_in(records, names, lo, hi)
    if not spans:
        return None
    return max(r["dur_us"] for r in spans) * 1e-3


def read(ctx, args):
    ps = ctx.manifest.module("readers", "program_spans")
    records = ps.span_records()
    win = ps.window(records, ctx.values.get("steps"), ctx.say)
    if win is None or win[1] <= win[0]:
        return None
    if not any("cpu_us" in r for r in records):
        return None
    what = args["what"]
    if what == "offcpu":
        return offcpu(records, args["spans"], *win)
    if what == "cpu_cores":
        return cpu_cores(records, *win)
    if what == "ratio_under":
        return ratio_under(records, args["spans"], args["under"], *win)
    if what == "longest":
        return longest(records, args["spans"], *win)
    raise ValueError(f"span_account: what={what!r}")
