"""``readers/program_spans.py`` on hand-made span records: the window rule,
nothing when the ring is short, the starved share with waits that overlap
on two threads, and a compile inside and outside the window."""

import os
import sys
import types

import pytest

from conftest import BENCH

sys.path.insert(0, os.path.join(BENCH, "readers"))
import program_spans as ps  # noqa: E402

NEXT = "device_loader.next_batch"
RING = "device_loader.ring_wait"
POOL = "device_loader.pool_wait"
PUT = "device_loader.put"
COMPILE = "xla.backend_compile"


def rec(name, start_s, dur_s, tid=1, **attrs):
    return {"kind": "span", "name": name, "mono_us": int(start_s * 1e6),
            "dur_us": int(dur_s * 1e6), "tid": tid, "attrs": attrs}


def steps(n, first=10.0, every=1.0, wait=0.5):
    """``n`` batches handed over: the caller waits ``wait`` s for each."""
    return [rec(NEXT, first + i * every, wait, got=True) for i in range(n)]


def test_window_is_the_last_steps_batches():
    warm = [rec(NEXT, 1.0, 0.2, got=True), rec(NEXT, 2.0, 0.2, got=True)]
    epoch_end = [rec(NEXT, 12.6, 0.1, got=False)]
    records = warm + steps(5) + epoch_end
    assert ps.window(records, 5) == pytest.approx((10.0, 14.5))
    # the end of an epoch hands nothing over and does not count as a step
    assert ps.window(records, 7) == pytest.approx((1.0, 14.5))


def test_short_ring_gives_nothing_and_says_so():
    said = []
    assert ps.window(steps(4), 5, said.append) is None
    assert "5 steps" in said[0] and "holds 4" in said[0]
    assert ps.window(steps(4), 0) is None
    # a program from before the spans existed: no record carries mono_us
    ctx = types.SimpleNamespace(values={"steps": 3}, say=said.append)
    old = [{"kind": "span", "name": NEXT, "ts_us": 1, "dur_us": 5,
            "attrs": {"got": True}}]
    from dmlc_core_tpu.telemetry import trace
    trace.recorder.clear()
    for r in old:
        trace.recorder.record(r)
    try:
        assert ps.read(ctx, {"what": "share", "spans": [PUT]}) is None
    finally:
        trace.recorder.clear()


def test_share_clips_spans_to_the_window():
    records = steps(4) + [
        rec(PUT, 9.9, 0.2),        # half before the window
        rec(PUT, 11.0, 0.3),
        rec(PUT, 13.4, 0.4),       # 0.1 inside (the window ends at 13.5)
        rec(RING, 11.3, 0.5),
    ]
    lo, hi = ps.window(records, 4)
    assert ps.share(records, [PUT], lo, hi) == pytest.approx(
        100 * (0.1 + 0.3 + 0.1) / 3.5)
    assert ps.share(records, [RING, POOL], lo, hi) == pytest.approx(
        100 * 0.5 / 3.5)


def test_starved_share_with_overlapping_waits_on_two_threads():
    # the caller waits 10.0-10.5, 11.0-11.5, 12.0-12.5 (window 10.0-12.5)
    records = steps(3) + [
        # two transfer workers wait on the chip over the same stretch: the
        # union covers 10.1-10.4 of the first wait once, not twice
        rec(POOL, 10.1, 0.2, tid=2), rec(POOL, 10.2, 0.2, tid=3),
        # a ring wait that covers the whole second wait and more
        rec(RING, 10.9, 0.8, tid=2),
        # nothing covers the third
    ]
    lo, hi = ps.window(records, 3)
    alone = (0.5 - 0.3) + 0.0 + 0.5
    assert ps.starved(records, NEXT, [RING, POOL], lo, hi) == pytest.approx(
        100 * alone / 2.5)
    # the end-of-epoch wait counts as the caller's wait too
    records.append(rec(NEXT, 11.6, 0.2, got=False))
    assert ps.starved(records, NEXT, [RING, POOL], lo, hi) == pytest.approx(
        100 * (alone + 0.1) / 2.5)


def test_compile_inside_and_outside_the_window():
    records = steps(3) + [rec(COMPILE, 3.0, 2.0),     # set-up
                          rec(COMPILE, 9.5, 1.0),     # starts before lo
                          rec(COMPILE, 11.2, 0.4),    # inside
                          rec(COMPILE, 30.0, 0.1)]    # the reference's
    lo, hi = ps.window(records, 3)
    assert ps.starts(records, [COMPILE], lo, hi) == 1
    assert ps.starts(steps(3), [COMPILE], lo, hi) == 0


def test_read_takes_the_programs_own_recorder():
    from dmlc_core_tpu.telemetry import trace
    said = []
    ctx = types.SimpleNamespace(values={"steps": 2}, say=said.append)
    trace.recorder.clear()
    try:
        for _ in range(2):
            with trace.span(NEXT) as s:
                with trace.span(RING):
                    pass
                s.attrs["got"] = True
        if "mono_us" not in trace.recorder.snapshot()[-1]:
            # laid over a program from before its spans carried the
            # monotonic start: nothing to read, and no error
            assert ps.read(ctx, {"what": "starts", "spans": [RING]}) is None
            return
        assert ps.read(ctx, {"what": "starts", "spans": [RING]}) == 2
        share = ps.read(ctx, {"what": "share", "spans": [RING]})
        assert 0 <= share <= 100
        assert ps.read(ctx, {"what": "starved", "waiting": NEXT,
                             "covered_by": [RING]}) >= 0
        with pytest.raises(ValueError):
            ps.read(ctx, {"what": "median"})
        ctx.values["steps"] = 3
        assert ps.read(ctx, {"what": "starts", "spans": [RING]}) is None
        assert said
    finally:
        trace.recorder.clear()
