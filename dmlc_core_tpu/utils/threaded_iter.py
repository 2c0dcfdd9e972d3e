"""Threaded producer→consumer iterator — capability parity with reference
``include/dmlc/threadediter.h``.

The reference ``ThreadedIter<DType>`` (`threadediter.h:46`) runs a single
producer thread filling a bounded queue of heap cells, with a free-cell
recycling list so steady-state allocation is zero, a ``BeforeFirst`` reset
protocol (signals kProduce/kBeforeFirst/kDestroy `threadediter.h:198`,
producer loop :290-357), ``Next(DType**)`` :360 and ``Recycle`` :385.
Exceptions thrown by the producer are captured and re-thrown to the consumer
(`threadediter.h:95-135`).

This implementation keeps the exact contract (bounded queue, recycling,
mid-stream destruction, BeforeFirst reset, producer-exception propagation) on
Python threads.  It is the backbone of the ingest pipeline: chunk prefetch
(io.wrappers), parse prefetch (data.parser) and the device feed
(pipeline.device_loader) all wrap their producers in it, mirroring how the
reference composes `threaded_input_split.h:23` and `parser.h:71`.

A hand-over can account for itself: an owner that passes ``wait_spans``
gets a span for every real wait at its queue — the producer thread with an
item and no room (``<queue>.wait_slot``), the consuming thread with nothing
to take (``<queue>.wait_item``) — through ``telemetry.trace.span``, imported
when the first such iterator is built (this module imports nothing of
``telemetry`` at load: the dependency runs the other way).

The queue's occupancy is readable by its producer, for a producer that
paces itself by its consumer (``data.parser.ThreadedParser`` sizes its
parse team so): ``starved`` counts the consumer's waits on an empty queue,
``full_streak`` the items in a row before which the producer found it full.
"""

from __future__ import annotations

import threading
from typing import (Any, Callable, Generic, Iterator, List, Optional, Tuple,
                    TypeVar)

from .logging import DMLCError

__all__ = ["ThreadedIter"]

T = TypeVar("T")

#: a wait shorter than this leaves no record: one that returned at once
#: says nothing, and a queue that is never empty or full must cost nothing
WAIT_FLOOR_S = 50e-6


class ThreadedIter(Generic[T]):
    """Background producer with bounded queue and cell recycling.

    Parameters
    ----------
    max_capacity:
        Bound on queued items (reference ``set_max_capacity``; chunk wrapper
        uses 2 `threaded_input_split.h:33`, parser uses 8 `parser.h:75`).
    wait_spans:
        ``(wait_slot, wait_item)``: the span names under which the producer
        thread's waits for room and the consuming thread's waits for an
        item are recorded, each a full literal name
        (``"parser.prefetch.wait_slot"``); ``None`` in either place records
        nothing there (the device loader's last queue: its consumer's wait
        is ``device_loader.next_batch`` already).  A queue with an item, or
        with room, is not timed at all; a wait under ``WAIT_FLOOR_S``
        leaves no record.

    Attributes
    ----------
    starved:
        How many times the consumer found the queue empty and had to wait,
        not counting while it takes the first ``max_capacity`` items after
        :meth:`init` or :meth:`before_first`: the stages after a rewound
        queue are filling then, and an empty queue says nothing about the
        producer's rate.
    full_streak:
        How many items in a row the producer found the queue full before
        making; 0 once it finds room.  Written by the producer thread just
        before it calls ``next_fn``.
    """

    def __init__(self, max_capacity: int = 8,
                 wait_spans: Tuple[Optional[str], Optional[str]] = (None, None)):
        self.max_capacity = max(1, int(max_capacity))
        self._wait_slot, self._wait_item = wait_spans
        self._span = None
        if self._wait_slot or self._wait_item:
            from ..telemetry.trace import span
            self._span = span
        self._lock = threading.Condition()
        self._queue: List[T] = []
        self._free: List[T] = []
        self._produced_end = False
        self._consumed_end = False
        self._destroy = False
        self._reset_pending = False
        self._error: Optional[BaseException] = None
        self._taken = 0         # items the consumer took since the last reset
        self.starved = 0
        self.full_streak = 0
        self._thread: Optional[threading.Thread] = None
        self._next_fn: Optional[Callable[[Optional[T]], Optional[T]]] = None
        self._beforefirst_fn: Optional[Callable[[], None]] = None

    # -- setup (reference Init `threadediter.h:282`) --
    def init(self, next_fn: Callable[[Optional[T]], Optional[T]],
             beforefirst_fn: Optional[Callable[[], None]] = None) -> None:
        """Start the producer thread.

        ``next_fn(reuse_cell)`` must return the next item (it *may* reuse and
        return ``reuse_cell``, which is a previously recycled item, to avoid
        allocation) or ``None`` at end-of-stream.  ``beforefirst_fn()`` resets
        the underlying source to the beginning.
        """
        if self._thread is not None:
            raise DMLCError("ThreadedIter.init called twice")
        self._next_fn = next_fn
        self._beforefirst_fn = beforefirst_fn
        # a named queue names its producer thread (``parser.prefetch``), so
        # a record's ``thread`` says which stage of the feed it is
        named = self._wait_slot or self._wait_item
        self._thread = threading.Thread(
            target=self._producer_loop, daemon=True,
            name=named.rpartition(".")[0] if named else None)
        self._thread.start()

    @classmethod
    def from_iterable_factory(cls, factory: Callable[[], Iterator[T]],
                              max_capacity: int = 8) -> "ThreadedIter[T]":
        """Convenience: wrap a restartable iterable (factory called per epoch)."""
        it = cls(max_capacity=max_capacity)
        state = {"iter": factory()}

        def next_fn(_cell: Optional[T]) -> Optional[T]:
            try:
                return next(state["iter"])
            except StopIteration:
                return None

        def beforefirst_fn() -> None:
            state["iter"] = factory()

        it.init(next_fn, beforefirst_fn)
        return it

    # -- producer side --
    def _must_wait_slot(self) -> bool:
        return (not self._destroy and not self._reset_pending
                and (self._produced_end
                     or len(self._queue) >= self.max_capacity))

    def _must_wait_item(self) -> bool:
        return (not self._queue and not self._produced_end
                and not self._destroy)

    def _wait_while(self, must_wait: Callable[[], bool],
                    name: Optional[str]) -> None:
        """Wait on the (held) lock while ``must_wait()``; under a name, a
        wait that has to happen is a span, kept from ``WAIT_FLOOR_S`` up."""
        if name and must_wait():
            with self._span(name, floor_s=WAIT_FLOOR_S):
                while must_wait():
                    self._lock.wait()
        while must_wait():
            self._lock.wait()

    def _producer_loop(self) -> None:
        while True:
            with self._lock:
                # wait for: destroy | reset request | space to produce
                # (a producer parked at the end of its stream waits for a
                # reset, not for room: no span for that)
                full = (not self._produced_end
                        and len(self._queue) >= self.max_capacity)
                self._wait_while(
                    self._must_wait_slot,
                    None if self._produced_end else self._wait_slot)
                if self._destroy:
                    return
                if self._reset_pending:
                    # drain queue into free list, reset source, ack consumer
                    # (reference kBeforeFirst handling `threadediter.h:313-328`)
                    self._free.extend(self._queue)
                    self._queue.clear()
                    try:
                        if self._beforefirst_fn is not None:
                            self._beforefirst_fn()
                        self._produced_end = False
                        self._consumed_end = False
                        self._error = None
                    except BaseException as e:  # noqa: BLE001
                        self._error = e
                        self._produced_end = True
                    self._reset_pending = False
                    self._lock.notify_all()
                    continue
                self.full_streak = self.full_streak + 1 if full else 0
                cell = self._free.pop() if self._free else None
            # produce outside the lock (reference calls producer_->Next
            # without holding the mutex, `threadediter.h:330-340`)
            try:
                item = self._next_fn(cell)  # type: ignore[misc]
            except BaseException as e:  # noqa: BLE001
                with self._lock:
                    self._error = e
                    self._produced_end = True
                    self._lock.notify_all()
                continue
            with self._lock:
                if self._reset_pending or self._destroy:
                    # a reset raced with production: drop the item into free
                    if item is not None:
                        self._free.append(item)
                    continue
                if item is None:
                    if cell is not None:
                        self._free.append(cell)
                    self._produced_end = True
                else:
                    self._queue.append(item)
                self._lock.notify_all()

    # -- consumer side --
    def next(self) -> Optional[T]:
        """Pop the next item, or None at end (reference Next `threadediter.h:360-382`).

        Destroy-aware: a consumer blocked here returns None when
        :meth:`destroy` fires, so chained stages (a downstream producer
        thread consuming an upstream iter) unwind cleanly instead of
        deadlocking on a dead producer."""
        with self._lock:
            if self._consumed_end:
                return None
            if self._taken >= self.max_capacity and self._must_wait_item():
                self.starved += 1
            self._wait_while(self._must_wait_item, self._wait_item)
            if self._destroy and not self._queue:
                self._consumed_end = True
                return None
            if self._error is not None:
                err = self._error
                self._consumed_end = True
                raise DMLCError(f"ThreadedIter producer failed: {err!r}") from err
            if self._queue:
                item = self._queue.pop(0)
                self._taken += 1
                self._lock.notify_all()
                return item
            self._consumed_end = True
            return None

    def recycle(self, item: T) -> None:
        """Return a consumed cell for reuse (reference Recycle `threadediter.h:385-394`)."""
        with self._lock:
            self._free.append(item)
            self._lock.notify_all()

    def before_first(self) -> None:
        """Reset to the beginning; blocks until the producer acknowledges
        (reference BeforeFirst `threadediter.h:167-190`)."""
        with self._lock:
            if self._thread is None:
                raise DMLCError("ThreadedIter not initialized")
            self._reset_pending = True
            self._lock.notify_all()
            while self._reset_pending and not self._destroy:
                self._lock.wait()
            self._consumed_end = False
            self._taken = 0

    def __iter__(self) -> Iterator[T]:
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    # -- teardown (reference destructor sends kDestroy `threadediter.h:205-215`) --
    def destroy(self) -> None:
        with self._lock:
            self._destroy = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def __enter__(self) -> "ThreadedIter[T]":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.destroy()

    def __del__(self) -> None:
        try:
            self.destroy()
        except Exception:
            pass
