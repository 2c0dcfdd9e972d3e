"""``readers/span_attr.py``: the mean of a span attribute over the window —
on hand-made records (a team that changes, the parent's constant 13,
records without the attribute), and once through ``read`` on the
program's own recorder."""

import os
import sys
import types

import pytest

from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "readers"))
import manifest as manifest_mod  # noqa: E402
import span_attr  # noqa: E402

NEXT = "device_loader.next_batch"
PARSE = "parser.parse"


def rec(name, start_s, dur_s=0.01, **attrs):
    return {"kind": "span", "name": name, "mono_us": int(round(start_s * 1e6)),
            "dur_us": int(round(dur_s * 1e6)), "tid": 1, "attrs": attrs}


def steps(n, first=10.0):
    return [rec(NEXT, first + i, 0.5, got=True) for i in range(n)]


def test_mean_over_the_spans_that_start_in_the_window():
    records = steps(4) + [rec(PARSE, 9.5, nthreads=13),     # before
                          rec(PARSE, 10.2, nthreads=1),
                          rec(PARSE, 11.2, nthreads=2),
                          rec(PARSE, 12.2, nthreads=4),
                          rec(PARSE, 13.2, nthreads=1),
                          rec(PARSE, 14.0, nthreads=13),    # after
                          rec("parser.chunk", 11.0, nthreads=99)]
    assert span_attr.mean_attr(records, [PARSE], "nthreads", 10.0,
                               13.5) == pytest.approx(2.0)


def test_the_parents_constant_team_reads_as_itself():
    records = steps(4) + [rec(PARSE, 10.2 + 0.1 * i, nthreads=13)
                          for i in range(30)]
    assert span_attr.mean_attr(records, [PARSE], "nthreads", 10.0,
                               13.5) == 13.0


def test_nothing_without_the_attribute():
    records = steps(4) + [rec(PARSE, 10.2, bytes=100),
                          rec(PARSE, 11.2, nthreads="13")]
    assert span_attr.mean_attr(records, [PARSE], "nthreads", 10.0,
                               13.5) is None
    assert span_attr.mean_attr(records, ["nothing"], "nthreads", 10.0,
                               13.5) is None


def _ctx(n_steps):
    man = manifest_mod.Manifest(REPO, BENCH)
    return types.SimpleNamespace(manifest=man, values={"steps": n_steps},
                                 say=lambda msg: None)


def test_read_on_the_programs_own_ring():
    from dmlc_core_tpu.telemetry import trace
    args = {"spans": [PARSE], "attr": "nthreads"}
    trace.recorder.clear()
    try:
        for r in steps(4) + [rec(PARSE, 10.2, nthreads=1),
                             rec(PARSE, 11.2, nthreads=3)]:
            trace.recorder.record(r)
        assert span_attr.read(_ctx(4), args) == pytest.approx(2.0)
        # the window's rule is program_spans': a short ring gives nothing
        assert span_attr.read(_ctx(5), args) is None
    finally:
        trace.recorder.clear()


def test_the_metric_files_name_this_reader():
    man = manifest_mod.Manifest(REPO, BENCH)
    for cell, metric in (("dcn24_score_text", "feed.parse_team.score"),
                         ("fm24_train_text", "feed.parse_team.train")):
        spec = man.layer_metric(metric)
        assert spec == {"reader": "span_attr",
                        "args": {"spans": [PARSE], "attr": "nthreads"}}
        assert metric in [m["name"] for m in man.metrics_for(cell,
                                                             "per_layer")]
