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

Usage::

    with span("serving.client.predict", rows=4):        # scoped span
        ...                                             # children nest

    with span("device_loader.pack", stage=timer, stall=detector):
        ...     # one clock pair: the record, the stage total, the detector

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
    "add_event", "format_id", "wire_ids", "from_wire", "set_sampler",
    "get_sampler",
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
_id_lock = threading.Lock()


def new_trace_id() -> int:
    """Random non-zero 63-bit id (zero is the wire's 'untraced' marker;
    bit 63 is reserved as the tail-sampling ``debug=1`` force-keep flag
    — see ``telemetry.sampling`` — so it is never minted by accident)."""
    with _id_lock:
        return _id_rng.randrange(1, 1 << 63)


class SpanRecorder:
    """Lock-protected ring buffer of finished span/event records.

    Records are plain JSON-ready dicts (see :meth:`Span.end` for the
    schema) so exports never touch live objects.  Bounded by
    ``capacity`` (env ``DMLC_SPAN_BUFFER``): under sustained load old
    spans fall off the back — observability must never become the
    memory leak it exists to find.
    """

    def __init__(self, capacity: int = 4096) -> None:
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=max(1, int(capacity)))
        self._dropped = 0

    def record(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            evicted = len(self._buf) == self._buf.maxlen
            if evicted:
                self._dropped += 1
            self._buf.append(rec)
        if evicted:
            # eviction at maxlen used to be invisible — consumers of a
            # lossy /spans window must be able to see that it is lossy
            metrics.counter("telemetry.spans_dropped").add(1)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._buf)

    @property
    def dropped(self) -> int:
        """Records evicted by the ring since construction/clear()."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


#: process-global recorder (the /spans endpoint and Chrome export read it)
recorder = SpanRecorder(capacity=get_env("DMLC_SPAN_BUFFER", 4096))

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
                 "events", "dur_s", "_t0_wall", "_t0_mono", "_tid",
                 "_thread", "_ended")

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
        self._t0_wall = time.time()
        self._t0_mono = time.monotonic()
        t = threading.current_thread()
        self._tid = t.ident or 0
        self._thread = t.name
        self._ended = False

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
        """Finish the span and push its record into the ring buffer."""
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        self.dur_s = max(0.0, time.monotonic() - self._t0_mono)
        rec = {
            "kind": "span",
            "name": self.name,
            "trace_id": format_id(self.trace_id),
            "span_id": format_id(self.span_id),
            "parent_id": (format_id(self.parent_id)
                          if self.parent_id else None),
            "ts_us": int(self._t0_wall * 1e6),
            # the start on the clock ``dur_us`` was taken from: in-process
            # arithmetic between records uses this, never the wall clock
            "mono_us": int(self._t0_mono * 1e6),
            "dur_us": int(self.dur_s * 1e6),
            "pid": os.getpid(),
            "tid": self._tid,
            "thread": self._thread,
            "attrs": _jsonable(self.attrs),
            "events": self.events,
        }
        s = _sampler
        if s is not None:
            s.on_end(self.trace_id, rec)
        else:
            recorder.record(rec)


def _jsonable(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Attrs must survive json.dumps — coerce exotic values to str."""
    out: Dict[str, Any] = {}
    for k, v in attrs.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
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
    return Span(name, trace_id, new_trace_id(), parent_id, _jsonable(attrs))


@contextlib.contextmanager
def span(name: str, stage: Any = None, stall: Any = None,
         **attrs: Any) -> Iterator[Span]:
    """Scoped span: child of the ambient context (or of ``parent=``),
    active for the block, ended on exit (exceptions recorded as ``error``
    before re-raising).

    For its whole extent it is also a ``jax.profiler.TraceAnnotation`` of
    the same name, so it lies on the host plane of any running profile, on
    the device trace's clock.  The one duration the record carries is also
    added to ``stage`` (a ``StageTimer``) and handed to ``stall`` (a
    ``StallDetector``) when the call site passes them: one pair of clock
    reads, three sinks."""
    with profiler_annotation(name):
        s = start_span(name, **attrs)
        token = _current.set(s)
        try:
            yield s
        except BaseException as e:
            s.end(error=f"{type(e).__name__}: {e}")
            raise
        finally:
            try:
                _current.reset(token)
            except ValueError:
                # a span opened inside a generator dies wherever the
                # generator is finalized: GC can close an abandoned
                # iterator from another thread's context, where this token
                # is foreign.  The span still ends; only the
                # ambient-context pop is moot.
                pass
            s.end()
            if stage is not None:
                stage.add(s.dur_s)
            if stall is not None:
                stall.observe(s.dur_s)


def record_completed(name: str, dur_s: float, **attrs: Any) -> None:
    """Record a span that ended just now and took ``dur_s``: for work timed
    by someone else (JAX reports a compile's duration once it is over)."""
    s = start_span(name, **attrs)
    s._t0_wall -= dur_s
    s._t0_mono -= dur_s
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
    ctx = _ids_of(node)
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
