"""Trace context propagation + in-process span recording.

``utils.metrics`` answers *how much / how fast*; this module answers
*where did this request go*.  A :class:`TraceContext` is a pair of ids
(``trace_id`` for the whole request tree, ``span_id`` for the current
operation) carried in a ``contextvars.ContextVar`` so it follows the
logical call chain — including across ``with``-scoped helper layers —
without threading an argument through every signature.  Crossing a
thread or a wire is explicit: pack ``current()`` ids into the message
(the serving protocol carries them in the request header) and
:func:`activate` the reconstructed context on the other side.

Finished spans land in a process-global lock-protected ring buffer
(:class:`SpanRecorder`): bounded memory, newest-wins, cheap enough for
per-request recording.  Consumers are ``telemetry.chrome_trace``
(Perfetto export) and the ``/spans`` endpoint of
``telemetry.exposition``.

A span's record carries two durations: ``dur_us``, wall time on
``time.monotonic``, and ``cpu_us``, the CPU time of **the thread that ran
the span** over the same extent (``time.thread_time_ns``), so ``dur_us -
cpu_us`` is the time that thread was not running: blocked on a lock, the
GIL or a device, or runnable with no core.  ``cpu_us`` is the calling
thread only: what a native call's own workers (an OpenMP team, the
runtime's transfer threads) burned is not in it — that shows in the
process's CPU clock, which ``device_loader.next_batch`` records carry as
``proc_cpu_us``.  A span ended on another thread than the one that started
it, and one placed by :func:`record_completed`, has no ``cpu_us``.  Its
resolution is the kernel's thread clock's: nanoseconds on most hosts, 10 ms
where CPU time is accounted by the tick (the benchmark's chip machine reads
0 or 10 000) — there only sums over many spans mean anything.

Usage::

    with span("serving.client.predict", rows=4):        # scoped span
        ...                                             # children nest

    with span("device_loader.pack", stage=timer, stall=detector):
        ...     # one clock pair: the record, the stage total, the detector

    with span("queue.wait_item", floor_s=50e-6):
        ...     # a wait: no record when it returned at once

    s = start_span("serving.server.request", parent=ctx)  # manual span
    ...                                                   # (async paths)
    s.end(status="OK")

    add_event("retry", attempt=2)   # annotate the active span, if any
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import random
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Union

from ..utils.metrics import metrics, profiler_annotation
from ..utils.parameter import get_env

__all__ = [
    "TraceContext", "Span", "SpanRecorder", "recorder", "current",
    "current_trace_id", "new_trace_id", "start_span", "span",
    "record_completed", "activate",
    "add_event", "record_event", "open_spans", "format_id", "wire_ids",
    "from_wire", "set_sampler", "get_sampler",
]


class TraceContext(NamedTuple):
    """Wire-portable identity of an in-progress span: 64-bit non-zero
    ``trace_id`` shared by every span of one request tree, plus the
    ``span_id`` new children must name as their parent."""

    trace_id: int
    span_id: int


def format_id(v: int) -> str:
    """Canonical hex rendering (what logs/exports show)."""
    return f"{v & 0xFFFFFFFFFFFFFFFF:016x}"


# one RNG for id generation; os.urandom-seeded so forked workers diverge
_id_rng = random.Random(int.from_bytes(os.urandom(8), "little"))


def new_trace_id() -> int:
    """Random non-zero 63-bit id (zero is the wire's 'untraced' marker;
    bit 63 is reserved as the tail-sampling ``debug=1`` force-keep flag
    — see ``telemetry.sampling`` — so it is never minted by accident).
    One C call under the GIL: no lock of its own."""
    return _id_rng.getrandbits(63) or 1


class SpanRecorder:
    """Lock-protected ring buffer of finished span/event records.

    :meth:`snapshot` hands out plain JSON-ready dicts (see
    :func:`_render` for a span's schema), so exports never touch live
    objects.  A finished span is kept as the flat tuple :meth:`Span.end`
    made and rendered by whoever reads: the hex ids, the attribute
    coercion and the dict are not paid on the hot path.  Bounded by
    ``capacity`` (env ``DMLC_SPAN_BUFFER``; the default holds a 10 s
    window of the busiest benchmark cell, ~17 600 records): under
    sustained load old spans fall off the back — observability must never
    become the memory leak it exists to find.
    """

    def __init__(self, capacity: int = 32768) -> None:
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=max(1, int(capacity)))
        self._dropped = 0
        # the last rendering, good until the next record: a run's readers
        # take one snapshot each, back to back
        self._rendered: Optional[List[Dict[str, Any]]] = None

    def record(self, rec: Union[Dict[str, Any], tuple]) -> None:
        """Append a rendered record (a dict) or a span's flat tuple."""
        with self._lock:
            evicted = len(self._buf) == self._buf.maxlen
            if evicted:
                self._dropped += 1
            self._buf.append(rec)
            self._rendered = None
        if evicted:
            # eviction at maxlen used to be invisible — consumers of a
            # lossy /spans window must be able to see that it is lossy
            metrics.counter("telemetry.spans_dropped").add(1)

    def snapshot(self, since_mono_s: Optional[float] = None
                 ) -> List[Dict[str, Any]]:
        """The ring's records, oldest first, rendered.  ``since_mono_s``
        keeps only spans that ended at or after that ``time.monotonic``
        instant (records without a monotonic start are left out then)."""
        with self._lock:
            raw = list(self._buf)
            rendered = self._rendered
        pid = os.getpid()
        if since_mono_s is None:
            if rendered is None:
                rendered = [_render(r, pid) if type(r) is tuple else r
                            for r in raw]
                with self._lock:
                    if len(self._buf) == len(raw) and \
                            (not raw or self._buf[-1] is raw[-1]):
                        self._rendered = rendered
            return list(rendered)
        out = []
        for r in raw:
            if type(r) is tuple:
                if r[_MONO] + r[_DUR] >= since_mono_s:
                    out.append(_render(r, pid))
            elif "mono_us" in r and \
                    (r["mono_us"] + r.get("dur_us", 0)) * 1e-6 >= since_mono_s:
                out.append(r)
        return out

    @property
    def dropped(self) -> int:
        """Records evicted by the ring since construction/clear()."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._rendered = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


#: process-global recorder (the /spans endpoint and Chrome export read it)
recorder = SpanRecorder(capacity=get_env("DMLC_SPAN_BUFFER", 32768))

# Optional tail sampler (telemetry.sampling.TailSampler) interposed
# between span completion and the recorder.  None (the default) keeps
# the record-everything behaviour; ``sampling.install()`` swaps it in.
# This module stays import-light — it never imports sampling itself.
_sampler: Optional[Any] = None


def set_sampler(sampler: Optional[Any]) -> None:
    """Install (or with None, remove) the tail-sampling hook.  The
    sampler must expose ``on_start(trace_id)``, ``on_end(trace_id,
    rec)`` and ``on_event(trace_id_or_none, rec)``."""
    global _sampler
    _sampler = sampler


def get_sampler() -> Optional[Any]:
    return _sampler

# The active node of the logical call chain: a live Span in-process, or a
# bare TraceContext re-activated after crossing a thread/wire boundary.
_current: contextvars.ContextVar[Optional[Union["Span", TraceContext]]] = \
    contextvars.ContextVar("dmlc_trace", default=None)


def _ids_of(node: Union["Span", TraceContext, None]) -> Optional[TraceContext]:
    if node is None:
        return None
    if isinstance(node, TraceContext):
        return node
    return node.context


def current() -> Optional[TraceContext]:
    """The active trace context (ids only), or None when untraced."""
    return _ids_of(_current.get())


def current_trace_id() -> Optional[str]:
    """Hex trace id of the active context (log-correlation helper)."""
    ctx = current()
    return format_id(ctx.trace_id) if ctx is not None else None


def wire_ids() -> "tuple[int, int]":
    """``(trace_id, span_id)`` of the active context for wire injection;
    ``(0, 0)`` when untraced — zero is the wire's 'untraced' marker, so
    senders can pack unconditionally (the serving header convention,
    shared by the data-service JSON RPCs)."""
    ctx = current()
    return (ctx.trace_id, ctx.span_id) if ctx is not None else (0, 0)


def from_wire(trace_id: Any, span_id: Any) -> Optional[TraceContext]:
    """Reconstruct a remote parent from wire ids.  A zero, absent, or
    malformed trace id means the request is untraced → ``None`` (safe to
    hand straight to :func:`activate` / ``start_span(parent=...)``)."""
    try:
        tid, sid = int(trace_id or 0), int(span_id or 0)
    except (TypeError, ValueError):
        return None
    if tid == 0:
        return None
    return TraceContext(tid, sid)


class Span:
    """One timed operation.  Created via :func:`start_span` / :func:`span`;
    finished exactly once with :meth:`end` (idempotent — async completion
    paths may race a cleanup path)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "events", "dur_s", "cpu_s", "_t0_wall", "_t0_mono",
                 "_t0_cpu", "_floor_s", "_tid", "_thread", "_ended")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: Optional[int], attrs: Dict[str, Any]) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.events: List[Dict[str, Any]] = []
        #: seconds from start to :meth:`end`, on ``time.monotonic``
        self.dur_s = 0.0
        #: CPU seconds of the starting thread over the same extent; None
        #: until the end, and for a span ended on another thread
        self.cpu_s: Optional[float] = None
        #: a span shorter than this leaves no record (:func:`span`)
        self._floor_s = 0.0
        t = threading.current_thread()
        self._tid = t.ident or 0
        self._thread = t.name
        self._ended = False
        self._t0_wall = time.time()
        self._t0_mono = time.monotonic()
        self._t0_cpu: Optional[int] = time.thread_time_ns()

    @property
    def context(self) -> TraceContext:
        """What children (local or remote) name as their parent."""
        return TraceContext(self.trace_id, self.span_id)

    def event(self, name: str, **attrs: Any) -> None:
        """Attach a point-in-time annotation (retry, breaker trip, ...)."""
        self.events.append({
            "name": name,
            "ts_us": int(time.time() * 1e6),
            "attrs": _jsonable(attrs),
        })

    def end(self, **attrs: Any) -> None:
        """Finish the span and push its record into the ring buffer: a
        flat tuple, rendered by whoever reads the ring."""
        if self._ended:
            return
        self._ended = True
        cpu_ns = None
        if self._t0_cpu is not None and threading.get_ident() == self._tid:
            cpu_ns = time.thread_time_ns() - self._t0_cpu
            self.cpu_s = cpu_ns * 1e-9
        self.dur_s = max(0.0, time.monotonic() - self._t0_mono)
        if attrs:
            self.attrs.update(attrs)
        if self.dur_s < self._floor_s:
            return
        rec = (self.name, self.trace_id, self.span_id, self.parent_id,
               self._t0_wall, self._t0_mono, self.dur_s, cpu_ns, self._tid,
               self._thread, self.attrs, self.events)
        s = _sampler
        if s is not None:
            s.on_end(self.trace_id, _render(rec, os.getpid()))
        else:
            recorder.record(rec)


# places in a finished span's flat tuple that readers of the raw ring use
_MONO, _DUR = 5, 6


def _render(rec: tuple, pid: int) -> Dict[str, Any]:
    """The JSON-ready record of one finished span."""
    (name, trace_id, span_id, parent_id, t0_wall, t0_mono, dur_s, cpu_ns,
     tid, thread, attrs, events) = rec
    out = {
        "kind": "span",
        "name": name,
        "trace_id": format_id(trace_id),
        "span_id": format_id(span_id),
        "parent_id": format_id(parent_id) if parent_id else None,
        "ts_us": int(t0_wall * 1e6),
        # the start on the clock ``dur_us`` was taken from: in-process
        # arithmetic between records uses this, never the wall clock
        "mono_us": int(t0_mono * 1e6),
        "dur_us": int(dur_s * 1e6),
        "pid": pid,
        "tid": tid,
        "thread": thread,
        "attrs": _jsonable(attrs),
        "events": events,
    }
    if cpu_ns is not None:
        # CPU time of the span's own thread: ``dur_us - cpu_us`` is the
        # time it was not running
        out["cpu_us"] = cpu_ns // 1000
    return out


_SCALARS = (str, int, float, bool, type(None))


def _jsonable(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Attrs must survive json.dumps — coerce exotic values to str."""
    out: Dict[str, Any] = {}
    for k, v in attrs.items():
        if type(v) in _SCALARS or (
                type(v) is tuple and all(type(x) in _SCALARS for x in v)):
            out[k] = v
        else:
            try:
                json.dumps(v)
                out[k] = v
            except (TypeError, ValueError):
                out[k] = str(v)
    return out


def start_span(name: str, parent: Optional[TraceContext] = None,
               **attrs: Any) -> Span:
    """Create a span WITHOUT activating it (async server paths hold the
    object and ``end()`` it from a completion callback).  ``parent``
    defaults to the ambient context; with neither, the span roots a new
    trace."""
    if parent is None:
        parent = current()
    if parent is None:
        trace_id, parent_id = new_trace_id(), None
    else:
        trace_id, parent_id = parent.trace_id, parent.span_id
    s = _sampler
    if s is not None:
        s.on_start(trace_id)
    # (the record keeps no reference to a caller's live object)
    return Span(name, trace_id, new_trace_id(), parent_id,
                _jsonable(attrs) if attrs else attrs)


# The innermost unfinished scoped span of each thread, by thread ident: what
# a ``stall.capture`` says the other threads were in.  Plain dict writes
# under the GIL; an entry goes when its thread's outermost span ends.
_open: Dict[int, Span] = {}


def open_spans() -> List[Span]:
    """The innermost unfinished :func:`span` of every thread that has one."""
    return list(_open.values())


class span:
    """Scoped span: child of the ambient context (or of ``parent=``),
    active for the block, ended on exit (exceptions recorded as ``error``
    before re-raising).  ``with span(name, ...) as s`` binds the
    :class:`Span`.

    For its whole extent it is also a ``jax.profiler.TraceAnnotation`` of
    the same name, so it lies on the host plane of any running profile, on
    the device trace's clock.  The one duration the record carries is also
    added to ``stage`` (a ``StageTimer``) and handed to ``stall`` (a
    ``StallDetector``, with the span, so that a flagged stall can say what
    the ring saw) when the call site passes them: one pair of clock reads,
    three sinks.  With ``floor_s``, a span that took less leaves no
    record: for waits, which mostly return at once.

    A class and not a generator: entering and leaving is most of what a
    span costs."""

    __slots__ = ("_name", "_stage", "_stall", "_floor_s", "_attrs",
                 "_annotation", "_span", "_token", "_outer")

    def __init__(self, name: str, stage: Any = None, stall: Any = None,
                 floor_s: float = 0.0, **attrs: Any) -> None:
        self._name = name
        self._stage = stage
        self._stall = stall
        self._floor_s = floor_s
        self._attrs = attrs

    def __enter__(self) -> Span:
        self._annotation = profiler_annotation(self._name)
        self._annotation.__enter__()
        s = self._span = start_span(self._name, **self._attrs)
        s._floor_s = self._floor_s
        self._token = _current.set(s)
        self._outer = _open.get(s._tid)
        _open[s._tid] = s
        return s

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._span
        if exc is not None:
            s.end(error=f"{exc_type.__name__}: {exc}")
        else:
            s.end()
        try:
            _current.reset(self._token)
        except ValueError:
            # a span opened inside a generator dies wherever the generator
            # is finalized: GC can close an abandoned iterator from another
            # thread's context, where this token is foreign.  The span
            # still ends; only the ambient-context pop is moot.
            pass
        if self._outer is None:
            _open.pop(s._tid, None)
        else:
            _open[s._tid] = self._outer
        if self._stage is not None:
            self._stage.add(s.dur_s)
        if self._stall is not None:
            self._stall.observe(s.dur_s, span=s)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def record_completed(name: str, dur_s: float, **attrs: Any) -> None:
    """Record a span that ended just now and took ``dur_s``: for work timed
    by someone else (JAX reports a compile's duration once it is over).
    Nobody took its thread's CPU clock, so the record has no ``cpu_us``."""
    s = start_span(name, **attrs)
    s._t0_wall -= dur_s
    s._t0_mono -= dur_s
    s._t0_cpu = None
    s.end()


@contextlib.contextmanager
def activate(ctx: Optional[TraceContext]) -> Iterator[None]:
    """Re-enter a context that crossed a thread or wire boundary (ids
    only — the originating span keeps ownership of its record).  ``None``
    is a no-op so call sites need no branching."""
    if ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


def add_event(name: str, **attrs: Any) -> None:
    """Annotate the active span; with only a re-activated context (or no
    trace at all) record a standalone instant event instead, so signals
    like retries are never dropped on untraced paths."""
    node = _current.get()
    if isinstance(node, Span):
        node.event(name, **attrs)
        return
    record_event(name, _ids_of(node), **attrs)


def record_event(name: str, ctx: Optional[TraceContext] = None,
                 **attrs: Any) -> None:
    """A standalone instant event in the ring, whatever span is active
    (``stall.capture``: the stalled span has ended, and its findings are
    not an annotation of whichever span encloses it)."""
    t = threading.current_thread()
    rec = {
        "kind": "event",
        "name": name,
        "trace_id": format_id(ctx.trace_id) if ctx else None,
        "span_id": format_id(ctx.span_id) if ctx else None,
        "ts_us": int(time.time() * 1e6),
        "pid": os.getpid(),
        "tid": t.ident or 0,
        "thread": t.name,
        "attrs": _jsonable(attrs),
    }
    s = _sampler
    if s is not None:
        s.on_event(ctx.trace_id if ctx else None, rec)
    else:
        recorder.record(rec)
