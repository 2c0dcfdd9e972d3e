"""``readers/span_account.py`` on hand-made span records: each ``what`` on
numbers known beforehand, nothing from records without ``cpu_us`` (a parent
commit's), and once on the program's own recorder."""

import os
import sys
import types

import pytest

from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "readers"))
import manifest as manifest_mod  # noqa: E402
import span_account as sa  # noqa: E402

NEXT = "device_loader.next_batch"
PUT = "device_loader.put"
H2D = "device_loader.h2d"
PARSE = "parser.parse"


def rec(name, start_s, dur_s, cpu_s=None, tid=1, **attrs):
    r = {"kind": "span", "name": name, "mono_us": int(round(start_s * 1e6)),
         "dur_us": int(round(dur_s * 1e6)), "tid": tid, "attrs": attrs}
    if cpu_s is not None:
        r["cpu_us"] = int(round(cpu_s * 1e6))
    return r


def steps(n, first=10.0, every=1.0, wait=0.5, cores=2.5):
    """``n`` hand-overs; the process burns ``cores`` CPU seconds a second."""
    return [rec(NEXT, first + i * every, wait, cpu_s=0.0, got=True,
                proc_cpu_us=int((first + i * every + wait) * cores * 1e6))
            for i in range(n)]


def test_offcpu_is_wall_less_cpu_of_the_spans_that_start_in_the_window():
    records = steps(4) + [
        rec(PUT, 9.9, 0.2, cpu_s=0.0, tid=2),     # starts before the window
        rec(PUT, 11.0, 0.3, cpu_s=0.1, tid=2),    # 0.2 off the CPU
        rec(PUT, 12.0, 0.1, cpu_s=0.1, tid=2),    # ran throughout
        rec(PUT, 13.4, 0.4, cpu_s=0.0, tid=2),    # starts inside: all 0.4
        rec(H2D, 11.0, 1.0, cpu_s=0.0, tid=2),    # another name
    ]
    assert sa.offcpu(records, [PUT], 10.0, 13.5) == pytest.approx(
        100 * (0.2 + 0.0 + 0.4) / 3.5)
    # a clock that ran a microsecond ahead of the wall does not count back
    assert sa.offcpu([rec(PUT, 11.0, 0.1, cpu_s=0.1001)], [PUT], 10.0,
                     13.5) == 0.0
    assert sa.offcpu(records, ["device_loader.ring_wait"], 10.0, 13.5) is None


def test_cpu_cores_is_the_process_clock_between_two_hand_overs():
    records = steps(5, cores=2.5) + [rec(NEXT, 12.6, 0.1, cpu_s=0.0,
                                         got=False)]
    assert sa.cpu_cores(records, 10.0, 14.5) == pytest.approx(2.5)
    # from the first hand-over inside the window to the last
    assert sa.cpu_cores(records, 11.0, 13.5) == pytest.approx(2.5)
    assert sa.cpu_cores(steps(1), 10.0, 10.5) is None
    # records from before the field existed
    old = [rec(NEXT, 10.0 + i, 0.5, got=True) for i in range(3)]
    assert sa.cpu_cores(old, 10.0, 12.5) is None


def test_ratio_under_splits_the_spans_by_another_threads_overlap():
    puts, parses = [], []
    for i in range(12):         # slow puts: a parse covers 0.06 of 0.08
        puts.append(rec(PUT, 10.0 + i, 0.08, cpu_s=0.01, tid=2))
        parses.append(rec(PARSE, 10.02 + i, 0.2, cpu_s=0.2, tid=3))
    for i in range(12):         # quick ones: a parse covers 0.01 of 0.04
        puts.append(rec(PUT, 10.5 + i, 0.04, cpu_s=0.01, tid=2))
        parses.append(rec(PARSE, 10.53 + i, 0.1, cpu_s=0.1, tid=3))
    records = steps(13) + puts + parses
    assert sa.ratio_under(records, [PUT], [PARSE], 10.0, 22.5) == \
        pytest.approx(2.0)
    # a span's own thread does not overlap it
    own = [dict(r, tid=2) for r in parses]
    assert sa.ratio_under(steps(13) + puts + own, [PUT], [PARSE], 10.0,
                          22.5) is None
    # under ten on a side says nothing
    assert sa.ratio_under(records, [PUT], [PARSE], 10.0, 18.9) is None


def test_longest_takes_the_spans_that_start_in_the_window():
    records = steps(4) + [rec(H2D, 9.0, 5.0, cpu_s=0.0),      # before
                          rec(H2D, 11.0, 0.008, cpu_s=0.0),
                          rec(H2D, 12.0, 0.1234, cpu_s=0.0),
                          rec(H2D, 13.4, 0.5, cpu_s=0.0),     # ends after
                          rec(H2D, 14.0, 3.0, cpu_s=0.0)]     # after
    assert sa.longest(records, [H2D], 10.0, 13.5) == pytest.approx(500.0)
    assert sa.longest(records, [H2D], 10.0, 13.0) == pytest.approx(123.4)
    assert sa.longest(records, [PUT], 10.0, 13.5) is None


WHATS = {
    "offcpu": ({"spans": [PUT]}, 100 * 0.6 / 3.5),
    "cpu_cores": ({}, 2.5),
    "ratio_under": ({"spans": [PUT], "under": [PARSE]}, None),
    "longest": ({"spans": [H2D]}, 250.0),
}


def _ctx(n_steps):
    man = manifest_mod.Manifest(REPO, BENCH)
    return types.SimpleNamespace(manifest=man, values={"steps": n_steps},
                                 say=lambda msg: None)


@pytest.mark.parametrize("what", sorted(WHATS))
def test_read_on_the_programs_own_ring(what):
    """Through ``read``, with ``program_spans`` found by name: the known
    value from records with ``cpu_us``, nothing from the same records
    without it."""
    from dmlc_core_tpu.telemetry import trace
    args, want = WHATS[what]
    records = steps(4) + [rec(PUT, 11.0, 0.3, cpu_s=0.1, tid=2),
                          rec(PUT, 13.4, 0.4, cpu_s=0.0, tid=2),
                          rec(H2D, 11.0, 0.25, cpu_s=0.0, tid=2)]
    trace.recorder.clear()
    try:
        for r in records:
            trace.recorder.record(r)
        got = sa.read(_ctx(4), dict(args, what=what))
        assert got == (pytest.approx(want) if want is not None else None)
        # the window's rule is program_spans': a short ring gives nothing
        assert sa.read(_ctx(5), dict(args, what=what)) is None
        trace.recorder.clear()
        for r in records:
            r = dict(r)
            r.pop("cpu_us", None)
            trace.recorder.record(r)
        assert sa.read(_ctx(4), dict(args, what=what)) is None
    finally:
        trace.recorder.clear()
    with pytest.raises(ValueError):
        trace.recorder.record(rec(NEXT, 1.0, 0.1, cpu_s=0.0, got=True))
        try:
            sa.read(_ctx(1), {"what": "median"})
        finally:
            trace.recorder.clear()


def test_live_spans_carry_what_the_reader_needs():
    """The program's own records, made now: ``cpu_us`` on every span (or the
    program is a parent commit's and the reader gives nothing)."""
    import time

    from dmlc_core_tpu.telemetry import trace
    trace.recorder.clear()
    try:
        for _ in range(3):
            with trace.span(NEXT) as s:
                with trace.span(PUT):
                    time.sleep(0.002)
                s.attrs["got"] = True
                s.attrs["proc_cpu_us"] = time.process_time_ns() // 1000
        ctx = _ctx(3)
        live = "cpu_us" in trace.recorder.snapshot()[-1]
        off = sa.read(ctx, {"what": "offcpu", "spans": [PUT]})
        cores = sa.read(ctx, {"what": "cpu_cores"})
        if not live:
            assert off is None and cores is None
            return
        assert 0 < off <= 100 and 0 <= cores < 64
    finally:
        trace.recorder.clear()
