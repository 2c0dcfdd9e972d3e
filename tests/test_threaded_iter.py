"""ThreadedIter semantics tests — mirrors reference
``test/unittest/unittest_threaditer.cc`` coverage: basic streaming, recycling,
BeforeFirst reset races, mid-stream destruction, producer error propagation."""

import threading
import time

import pytest

from dmlc_core_tpu.utils import DMLCError, ThreadedIter


def make_counter_iter(n, capacity=4, delay=0.0):
    state = {"i": 0}

    def next_fn(cell):
        if state["i"] >= n:
            return None
        if delay:
            time.sleep(delay)
        v = state["i"]
        state["i"] += 1
        # reuse the recycled cell when present (zero-alloc steady state)
        if cell is not None:
            cell[0] = v
            return cell
        return [v]

    def beforefirst():
        state["i"] = 0

    it = ThreadedIter(max_capacity=capacity)
    it.init(next_fn, beforefirst)
    return it


def test_basic_stream():
    with make_counter_iter(100) as it:
        got = [x[0] for x in it]
        assert got == list(range(100))
        assert it.next() is None  # stays ended


def test_recycling_reuses_cells():
    with make_counter_iter(50, capacity=2) as it:
        seen_ids = set()
        out = []
        while True:
            item = it.next()
            if item is None:
                break
            out.append(item[0])
            seen_ids.add(id(item))
            it.recycle(item)
        assert out == list(range(50))
        # with recycling and capacity 2 the number of distinct cells stays small
        assert len(seen_ids) <= 8


def test_before_first_restarts_epoch():
    with make_counter_iter(10) as it:
        first = [x[0] for x in it]
        it.before_first()
        second = [x[0] for x in it]
        assert first == second == list(range(10))


def test_before_first_mid_stream():
    # reference unittest_threaditer.cc exercises reset while producer active
    with make_counter_iter(1000, capacity=4) as it:
        for _ in range(5):
            assert it.next() is not None
        it.before_first()
        vals = [x[0] for x in it]
        assert vals == list(range(1000))


def test_destroy_mid_stream():
    it = make_counter_iter(10**9, capacity=2, delay=0.001)
    assert it.next() is not None
    it.destroy()  # must not hang with a full queue / busy producer
    # destroying twice is fine
    it.destroy()


def test_producer_exception_propagates():
    def next_fn(cell):
        raise ValueError("boom")

    it = ThreadedIter(max_capacity=2)
    it.init(next_fn)
    with pytest.raises(DMLCError, match="boom"):
        it.next()
    it.destroy()


def test_exception_then_reset_recovers():
    state = {"fail": True, "i": 0}

    def next_fn(cell):
        if state["fail"]:
            raise ValueError("first epoch fails")
        if state["i"] >= 3:
            return None
        state["i"] += 1
        return state["i"]

    def beforefirst():
        state["fail"] = False
        state["i"] = 0

    it = ThreadedIter(max_capacity=2)
    it.init(next_fn, beforefirst)
    with pytest.raises(DMLCError):
        it.next()
    it.before_first()
    assert [x for x in it] == [1, 2, 3]
    it.destroy()


def test_backpressure_bounded_queue():
    produced = []

    def next_fn(cell):
        produced.append(len(produced))
        return produced[-1]

    it = ThreadedIter(max_capacity=3)
    it.init(next_fn)
    time.sleep(0.2)  # let the producer run against a full queue
    assert len(produced) <= 5  # capacity + in-flight, never unbounded
    it.destroy()


def test_from_iterable_factory():
    it = ThreadedIter.from_iterable_factory(lambda: iter(range(7)), max_capacity=2)
    assert list(it) == list(range(7))
    it.before_first()
    assert list(it) == list(range(7))
    it.destroy()


# ---------------------------------------------------------------------------
# the hand-over accounts for itself: a real wait at the queue is a span

SLOT, ITEM = "unit.queue.wait_slot", "unit.queue.wait_item"


def _waits(name):
    from dmlc_core_tpu.telemetry import trace
    return [r for r in trace.recorder.snapshot() if r["name"] == name]


@pytest.fixture()
def clean_ring():
    from dmlc_core_tpu.telemetry import trace
    trace.recorder.clear()
    yield
    trace.recorder.clear()


def _named_iter(n, capacity, delay, wait_spans):
    state = {"i": 0}

    def next_fn(_cell):
        if state["i"] >= n:
            return None
        time.sleep(delay)
        state["i"] += 1
        return state["i"]

    it = ThreadedIter(max_capacity=capacity, wait_spans=wait_spans)
    it.init(next_fn)
    return it


def test_a_slow_producer_is_the_consumers_wait_item(clean_ring):
    from dmlc_core_tpu.utils.threaded_iter import WAIT_FLOOR_S
    it = _named_iter(8, capacity=4, delay=0.01, wait_spans=(SLOT, ITEM))
    assert list(it) == list(range(1, 9))
    it.destroy()
    waits = _waits(ITEM)
    # every item but those already queued was waited for, on this thread
    assert 6 <= len(waits) <= 9
    assert all(r["thread"] == "MainThread" for r in waits)
    assert all(r["dur_us"] >= WAIT_FLOOR_S * 1e6 for r in waits)
    assert sum(r["dur_us"] for r in waits) >= 0.05e6
    # a wait is off the CPU
    assert sum(r["cpu_us"] for r in waits) < 0.2 * sum(r["dur_us"]
                                                       for r in waits)
    # the queue never filled: the producer never waited for room
    assert not _waits(SLOT)


def test_a_slow_consumer_is_the_producers_wait_slot(clean_ring):
    it = _named_iter(8, capacity=2, delay=0.0, wait_spans=(SLOT, ITEM))
    got = []
    time.sleep(0.05)            # the producer fills the queue and waits
    for x in it:
        got.append(x)
        time.sleep(0.01)
    it.destroy()
    assert got == list(range(1, 9))
    waits = _waits(SLOT)
    assert 4 <= len(waits) <= 8
    # on the producer's thread, which is named after its queue
    assert {r["thread"] for r in waits} == {"unit.queue"}
    assert len({r["tid"] for r in waits}) == 1
    assert max(r["dur_us"] for r in waits) >= 0.04e6
    # the consumer found an item each time, but for the end of the stream
    assert len(_waits(ITEM)) <= 1


def test_a_queue_with_items_and_room_records_nothing(clean_ring):
    from dmlc_core_tpu.telemetry import trace
    it = _named_iter(4, capacity=8, delay=0.0, wait_spans=(SLOT, ITEM))
    time.sleep(0.1)             # everything is produced and queued
    trace.recorder.clear()
    assert [it.next() for _ in range(4)] == [1, 2, 3, 4]
    assert not len(trace.recorder)
    it.destroy()


@pytest.mark.parametrize("which", ["slot", "item", "neither"])
def test_an_owner_names_the_waits_it_wants(clean_ring, which):
    names = {"slot": (SLOT, None), "item": (None, ITEM),
             "neither": (None, None)}[which]
    it = _named_iter(6, capacity=1, delay=0.005, wait_spans=names)
    for _ in it:
        time.sleep(0.005)
    it.destroy()
    assert bool(_waits(SLOT)) == (which == "slot")
    assert bool(_waits(ITEM)) == (which == "item")


def test_the_module_imports_nothing_of_telemetry_at_load():
    import ast
    import inspect

    from dmlc_core_tpu.utils import threaded_iter
    tree = ast.parse(inspect.getsource(threaded_iter))
    for node in tree.body:      # module level only
        if isinstance(node, ast.ImportFrom):
            assert "telemetry" not in (node.module or ""), node.module
        if isinstance(node, ast.Import):
            assert all("telemetry" not in a.name for a in node.names)
    # and an iterator nobody named pulls it in at no time
    assert ThreadedIter()._span is None
