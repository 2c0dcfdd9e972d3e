"""A threaded parser sizes its OpenMP team from the queue it fills
(``ThreadedParser``, ``data/parser.py``): a team of 1 while its consumer is
the slower, doubled up to the cap while the consumer waits on an empty
queue, halved while the queue stays full; pins fix it; the blocks do not
depend on it."""

import time

import numpy as np
import pytest

from dmlc_core_tpu import native
from dmlc_core_tpu.data import create_parser, py_parsers
from dmlc_core_tpu.data import parser as parser_mod
from dmlc_core_tpu.io import input_split
from dmlc_core_tpu.utils import ThreadedIter
from dmlc_core_tpu.utils.metrics import metrics

CAP = 8
ROW = b"1 3:0.5 7:1.25\n"


class Chunks:
    """An input split of ``n`` one-row chunks."""

    def __init__(self, n):
        self.n, self.i = n, 0

    def next_chunk(self):
        if self.i >= self.n:
            return None
        self.i += 1
        return ROW

    def before_first(self):
        self.i = 0

    def close(self):
        pass


def recording_kernel(teams, delay=0.0):
    """A format kernel that records each call's team."""
    def kernel(data, nthreads):
        teams.append(nthreads)
        if delay:
            time.sleep(delay)
        return py_parsers.parse_libsvm(data)
    return kernel


@pytest.fixture
def unpinned(monkeypatch):
    monkeypatch.delenv("DMLC_NUM_THREADS", raising=False)
    monkeypatch.delenv("OMP_NUM_THREADS", raising=False)
    monkeypatch.setattr(parser_mod, "_default_nthreads", lambda: CAP)


def threaded(n, teams, delay=0.0, nthreads=0):
    base = parser_mod.TextParser(Chunks(n), recording_kernel(teams, delay),
                                 nthreads)
    return parser_mod.ThreadedParser(base)


def take(p, n, pause=0.0):
    for _ in range(n):
        assert p.parse_next() is not None
        if pause:
            time.sleep(pause)


def test_a_slower_consumer_keeps_the_team_at_one(unpinned):
    teams = []
    p = threaded(30, teams)
    try:
        take(p, 30, pause=0.02)
        assert p.parse_next() is None
    finally:
        p.close()
    assert teams == [1] * 30


def test_a_consumer_that_always_waits_drives_the_team_to_the_cap(unpinned):
    changes0 = metrics.counter("parser.team_changes").value
    teams = []
    p = threaded(20, teams, delay=0.02)
    try:
        take(p, 20)
    finally:
        p.close()
    # the first queue-full of takes says nothing; the wait for the next
    # one does, and the team doubles before each chunk after it
    assert teams[:CAP + 1] == [1] * (CAP + 1)
    assert teams.index(CAP) <= CAP + 1 + 4
    assert max(teams) == CAP and teams[-1] == CAP
    assert metrics.counter("parser.team_changes").value - changes0 >= 3


def test_a_queue_that_stays_full_shrinks_the_team_back_to_one(unpinned):
    teams = []
    p = threaded(70, teams, delay=0.005)
    try:
        take(p, 16)                     # waits: 1 -> 8
        take(p, 54, pause=0.02)         # the queue fills and stays full
    finally:
        p.close()
    assert max(teams) == CAP
    top = teams.index(CAP)
    # halved once per queue's worth of chunks found full, down to 1
    down = teams[top:]
    assert down == sorted(down, reverse=True)
    assert down[-1] == 1


@pytest.mark.parametrize("pin", ["nthreads", "DMLC_NUM_THREADS",
                                 "OMP_NUM_THREADS"])
def test_a_pinned_team_is_the_pin_on_every_call(monkeypatch, pin):
    monkeypatch.delenv("DMLC_NUM_THREADS", raising=False)
    monkeypatch.delenv("OMP_NUM_THREADS", raising=False)
    nthreads = 0
    if pin == "nthreads":
        nthreads = 3
    else:
        monkeypatch.setenv(pin, "3")
    changes0 = metrics.counter("parser.team_changes").value
    teams = []
    p = threaded(16, teams, delay=0.01, nthreads=nthreads)
    try:
        assert not p.base.team_free
        take(p, 16)                     # a consumer that always waits
    finally:
        p.close()
    assert teams == [3] * 16
    assert metrics.counter("parser.team_changes").value == changes0


def test_an_empty_queue_after_before_first_does_not_grow_the_team(unpinned):
    teams = []
    p = threaded(12, teams, delay=0.005)
    try:
        for _ in range(2):
            take(p, CAP)                # a rewound pipeline fills at once
            time.sleep(0.2)             # then the consumer is the slower
            take(p, 12 - CAP)
            assert p.parse_next() is None
            p.before_first()
    finally:
        p.close()
    assert teams[:24] == [1] * 24


def test_the_queue_reports_starved_waits_and_full_streaks():
    made = {"n": 0}

    def produce(_cell):
        made["n"] += 1
        return [made["n"]]

    it = ThreadedIter(max_capacity=2)
    it.init(produce, lambda: None)
    try:
        deadline = time.monotonic() + 5
        while it.full_streak < 3 and time.monotonic() < deadline:
            it.recycle(it.next())       # each take makes room once
            time.sleep(0.01)
        assert it.full_streak >= 3 and it.starved == 0
    finally:
        it.destroy()

    slow = ThreadedIter(max_capacity=2)
    slow.init(lambda _cell: time.sleep(0.01) or [0], lambda: None)
    try:
        for _ in range(5):
            slow.next()
        # the first two takes are the queue's fill, the next three waited
        assert slow.starved == 3
        slow.before_first()
        slow.next()
        slow.next()
        assert slow.starved == 3
    finally:
        slow.destroy()


@pytest.mark.skipif(not native.available(), reason="needs the native parser")
def test_blocks_are_bit_identical_whatever_the_team(unpinned, monkeypatch,
                                                    tmp_path):
    from dmlc_core_tpu.telemetry import trace
    monkeypatch.setattr(input_split.InputSplitBase, "KBUFFER_SIZE", 96 << 10)
    rng = np.random.default_rng(7)
    path = tmp_path / "rows.libsvm"
    with open(path, "w") as f:
        for i in range(24000):
            ids = np.sort(rng.choice(1 << 20, size=20, replace=False))
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.random():.4f}" for j in ids) + "\n")
    uri = f"file://{path}"

    def blocks(p, epochs):
        out = []
        for _ in range(epochs):
            out.append([c.get_block() for c in p])
            p.before_first()
        p.close()
        return out

    want = blocks(create_parser(uri, 0, 1, "libsvm", nthreads=1,
                                threaded=False), 1)[0]
    assert len(want) > 3 * CAP
    trace.recorder.clear()
    try:
        got = blocks(create_parser(uri, 0, 1, "libsvm"), 2)
        teams = [r["attrs"]["nthreads"] for r in trace.recorder.snapshot()
                 if r["name"] == "parser.parse"]
    finally:
        trace.recorder.clear()
    assert len(set(teams)) > 1, teams   # the team changed mid-stream
    for epoch in got:
        assert len(epoch) == len(want)
        for a, b in zip(epoch, want):
            for name in ("offsets", "labels", "indices", "values"):
                x, y = getattr(a, name), getattr(b, name)
                assert x.dtype == y.dtype and x.tobytes() == y.tobytes()
            assert a.max_index == b.max_index
