"""From a ``jax.profiler`` trace to device busy time, per-op totals and the
host's share of every idle gap.  ``jax.profiler.ProfileData.from_file`` only.

What a v5e trace holds (read by hand, PR 26): one plane per chip named
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per executed
HLO op (nested ``while``/``conditional`` bodies appear both as the outer op
and as their inner ops, so busy time is a *union* of intervals, never a
sum), and ``/host:CPU`` with one line per thread, on the same clock, whose
``TraceAnnotation`` events carry the names given by the code.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def short_op(name: str, width: int = 96) -> str:
    """``%fusion.10 = (f32[16777216,32]{0,1:T(8,128)}, ...) fusion(...)`` is
    the whole HLO line; keep the op's name and the head of what it makes."""
    head, sep, rest = name.partition(" = ")
    return (head + " " + rest)[:width] if sep else name[:width]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of ``[lo, hi)`` that a disjoint ``busy`` leaves."""
    out, at = [], lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


class Cover:
    """Sorted disjoint ``spans`` with prefix sums: how much of ``[a, b)``
    they cover, in O(log n) — a serving trace has thousands of gaps and
    thousands of host spans."""

    def __init__(self, spans: Sequence[Interval]):
        self.spans = union(spans)
        self.starts = [a for a, _ in self.spans]
        self.before = [0.0]
        for a, b in self.spans:
            self.before.append(self.before[-1] + (b - a))

    def upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        a, b = self.spans[i - 1]
        return self.before[i - 1] + (min(x, b) - a)

    def covered(self, gap: Interval) -> float:
        return self.upto(gap[1]) - self.upto(gap[0])


def read(path: str) -> dict:
    """``{"devices": {plane: [(name, start_s, end_s), ...]},
    "modules": {plane: [(name, start_s, end_s), ...]},
    "host": {name: [(start_s, end_s), ...]}}`` from one ``.xplane.pb``.
    Device events are those of the ``XLA Ops`` line, modules those of the
    ``XLA Modules`` line (one event per executed program, named
    ``jit_<function>(<fingerprint>)``); host events are every named event
    on the host plane (``TraceAnnotation`` included)."""
    import jax.profiler as prof
    data = prof.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(
                    line.name)
                if into is None:
                    continue
                into.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9, e.end_ns * 1e-9))
    return {"devices": devices, "modules": modules, "host": host}


def reduce(trace: dict, window: Interval | None = None,
           host_labels: Sequence[str] = (), top: int = 10) -> dict:
    """Busy seconds (union, averaged over the chips), window seconds, the
    ops that took most device time and the idle gaps by what the host was
    doing in them.

    ``window`` is on the trace's clock; by default it runs from the first
    host ``bench.window`` annotation if there is one, else from the first
    device op to the last.  ``host_labels`` are the host event names that
    may label a gap, most specific first; a gap (or the part of one) that
    none covers is ``(host: unlabelled)``."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("trace holds no device plane with an 'XLA Ops' line")
    if window is None:
        marks = trace["host"].get("bench.window")
        if marks:
            window = (min(a for a, _ in marks), max(b for _, b in marks))
        else:
            window = (min(s for ev in devices.values() for _, s, _ in ev),
                      max(e for ev in devices.values() for _, _, e in ev))
    lo, hi = window
    busy_sum = 0.0
    covers = [(label, Cover(clip(trace["host"].get(label, ()), lo, hi)))
              for label in host_labels]
    op_seconds: Dict[str, float] = {}
    gap_seconds: Dict[str, float] = {}
    for events in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        busy = union((s, e) for _, s, e in inside)
        busy_sum += total(busy)
        for n, s, e in inside:
            n = short_op(n)
            op_seconds[n] = op_seconds.get(n, 0.0) + (e - s)
        for gap in gaps(busy, lo, hi):
            left = gap[1] - gap[0]
            for label, cover in covers:
                got = min(left, cover.covered(gap))
                if got > 0:
                    gap_seconds[label] = gap_seconds.get(label, 0.0) + got
                    left -= got
            if left > 1e-12:
                key = "(host: unlabelled)"
                gap_seconds[key] = gap_seconds.get(key, 0.0) + left
    module_seconds: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    for events in trace.get("modules", {}).values():
        for name, s, e in events:
            if e > lo and s < hi:
                name = name.split("(")[0]       # drop the fingerprint
                module_seconds[name] = (module_seconds.get(name, 0.0)
                                        + min(e, hi) - max(s, lo))
                module_calls[name] = module_calls.get(name, 0) + 1
    n = len(devices)
    rank = lambda d: [[k, v / n] for k, v in sorted(   # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_sum / n, "window_s": hi - lo, "chips": n,
            "device_ops": rank(op_seconds), "idle_gaps": rank(gap_seconds),
            "module_seconds": {k: v / n for k, v in module_seconds.items()},
            "module_calls": module_calls}
