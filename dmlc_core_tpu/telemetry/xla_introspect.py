"""XLA introspection: retrace watchdog + device-memory gauges.

The serving engine's whole performance story is the **no-retrace
ladder**: every request shape is bucketed up to an ahead-of-time
compiled executable, so steady-state traffic never touches the XLA
compiler.  That property is invisible until it breaks — a new shape
falls off the ladder, a checkpoint hot-reload silently changes a
signature, a dtype drifts — and then p99 jumps by a compile (seconds,
not microseconds) with nothing in the metrics naming the culprit.

:class:`RetraceWatchdog` makes the property observable:

* every compile is counted per shape bucket with its wall time
  (``xla.compiles``, ``xla.compile_seconds``, ``xla.compile.<bucket>``);
* cache hits are counted so the miss *ratio* is computable;
* once a bucket is **steady** (warmed up / first compile done), any
  further compile for it raises a retrace alert — that is exactly the
  "requests fell off the no-retrace ladder" condition;
* ladder misses (requests too large for any bucket) are counted and
  noted, since they are the adjacent failure mode with the same
  operator response (extend the ladder).

The watchdog sees only what the serving engine tells it.
:func:`install_compile_listeners` hears every backend compile of the
process from JAX itself (``jax.monitoring``): ``xla.backend_compiles`` and
``xla.persistent_cache_hits`` (counters), ``xla.backend_compile_seconds``
(histogram), and one ``xla.backend_compile`` span record per compile, so
"did anything compile while this ran" is a question for the span ring.
``utils.compile_cache.enable_compile_cache`` — which every entry point
calls before its first compile — installs them.

Alerts bump ``xla.retrace_alerts``, latch the ``xla.retrace_alert``
gauge, and leave a note in the flight recorder (via ``sys.modules`` —
this module never imports ``flight``).

:func:`sample_memory` publishes live-buffer and per-device memory
gauges on whatever cadence the caller already has (the rabit telemetry
push, the SLO monitor tick).  It is a guarded no-op without JAX, and
tolerates backends that do not implement ``memory_stats`` (CPU).
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, Optional

from ..utils.logging import log_warning
from ..utils.metrics import MetricsRegistry, metrics
from . import trace

__all__ = ["RetraceWatchdog", "watchdog", "sample_memory",
           "install_compile_listeners"]


def _flight_mod():
    return sys.modules.get("dmlc_core_tpu.telemetry.flight")


class RetraceWatchdog:
    """Compile/retrace accounting per shape bucket (see module doc)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self._reg = registry if registry is not None else metrics
        self._lock = threading.Lock()
        # bucket -> {"compiles": n, "wall_s": total, "steady": bool}
        self._buckets: Dict[str, Dict[str, Any]] = {}
        self._alerted = False

    def _bucket(self, key: str) -> Dict[str, Any]:
        b = self._buckets.get(key)
        if b is None:
            b = {"compiles": 0, "wall_s": 0.0, "steady": False}
            self._buckets[key] = b
        return b

    # -- feed points (engine calls these) --------------------------------
    def note_compile(self, bucket: str, wall_s: float) -> bool:
        """A compile happened for ``bucket``; returns True when it was a
        retrace (compile after the bucket went steady) — the alert."""
        retrace = False
        with self._lock:
            b = self._bucket(bucket)
            b["compiles"] += 1
            b["wall_s"] += wall_s
            retrace = b["steady"]
            if retrace:
                self._alerted = True
        self._reg.counter("xla.compiles").add(1)
        self._reg.counter(f"xla.compile.{bucket}").add(1)
        self._reg.histogram("xla.compile_seconds").observe(wall_s)
        if retrace:
            self._reg.counter("xla.retrace_alerts").add(1)
            self._reg.gauge("xla.retrace_alert").set(1)
            log_warning("retrace alert: bucket %s recompiled after steady "
                        "state (%.3fs) — requests fell off the no-retrace "
                        "ladder", bucket, wall_s)
            fl = _flight_mod()
            if fl is not None:
                fl.flight_recorder.note("retrace_alert", bucket=bucket,
                                        wall_s=wall_s)
                fl.dump_incident("retrace_alert", registry=self._reg,
                                 bucket=bucket, wall_s=wall_s)
        return retrace

    def note_hit(self, bucket: str) -> None:
        """A request was served from the compiled cache."""
        self._reg.counter("xla.cache_hits").add(1)
        with self._lock:
            # first hit proves the executable exists → the bucket is
            # steady even if warmup was skipped
            self._bucket(bucket)["steady"] = True

    def note_ladder_miss(self, detail: str = "") -> None:
        """A request did not fit any bucket (``RequestTooLarge``)."""
        self._reg.counter("xla.ladder_misses").add(1)
        self._reg.gauge("xla.retrace_alert").set(1)
        with self._lock:
            self._alerted = True
        fl = _flight_mod()
        if fl is not None:
            fl.flight_recorder.note("ladder_miss", detail=detail)

    def mark_steady(self, bucket: Optional[str] = None) -> None:
        """Declare bucket(s) warmed: compiles from here on are retraces.
        ``warmup_all`` calls this with no argument after the sweep."""
        with self._lock:
            if bucket is None:
                for b in self._buckets.values():
                    b["steady"] = True
            else:
                self._bucket(bucket)["steady"] = True

    def begin_warmup(self) -> None:
        """Open a declared compile window: a fresh engine (checkpoint
        hot-reload, a second replica in-process) recompiles every bucket,
        and those compiles are expected, not retraces."""
        with self._lock:
            for b in self._buckets.values():
                b["steady"] = False

    # -- reading ---------------------------------------------------------
    @property
    def alerted(self) -> bool:
        with self._lock:
            return self._alerted

    def reset_alert(self) -> None:
        with self._lock:
            self._alerted = False
        self._reg.gauge("xla.retrace_alert").set(0)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"alerted": self._alerted,
                    "buckets": {k: dict(v)
                                for k, v in self._buckets.items()}}


#: process-global watchdog (the serving engine feeds it)
watchdog = RetraceWatchdog()

# JAX keeps a listener for the life of the process and cannot drop one, so
# "installed" is a fact about the process
_listeners_installed = False
_listeners_lock = threading.Lock()


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    # fires once the compile (or the load from the persistent cache) is
    # over: the record is placed at (now - duration, duration)
    if event == "/jax/core/compile/backend_compile_duration":
        metrics.counter("xla.backend_compiles").add(1)
        metrics.histogram("xla.backend_compile_seconds").observe(duration)
        trace.record_completed("xla.backend_compile", duration)


def _on_event(event: str, **_kw: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        metrics.counter("xla.persistent_cache_hits").add(1)


def install_compile_listeners() -> None:
    """Count and record every backend compile of this process (see the
    module doc).  Idempotent."""
    global _listeners_installed
    import jax.monitoring
    with _listeners_lock:
        if _listeners_installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listeners_installed = True


_mem_warned = False


def sample_memory(registry: Optional[MetricsRegistry] = None) -> bool:
    """Publish ``xla.live_buffers`` and per-device ``xla.mem.<id>.*``
    gauges; returns False (and stays silent) when JAX is absent.  Safe
    to call on any cadence — it reads runtime counters, it does not walk
    the heap."""
    global _mem_warned
    reg = registry if registry is not None else metrics
    try:
        import jax
    except Exception:
        return False
    try:
        reg.gauge("xla.live_buffers").set(len(jax.live_arrays()))
    except Exception as e:     # pragma: no cover - version drift
        if not _mem_warned:
            _mem_warned = True
            log_warning("xla live-buffer sampling unavailable: %s", e)
    try:
        devices = jax.local_devices()
    except Exception:
        return True
    for dev in devices:
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None           # CPU backend: not implemented
        if not stats:
            continue
        did = getattr(dev, "id", 0)
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                reg.gauge(f"xla.mem.{did}.{key}").set(stats[key])
    reg.gauge("xla.mem.sampled_ts").set(time.time())
    return True
