"""Metrics & tracing subsystem — the structured upgrade over the
reference's ad-hoc instrumentation (SURVEY §5).

The reference's observability is wall-clock ``GetTime()`` (`timer.h:27`)
plus periodic MB/s prints in ingest loops (`basic_row_iter.h:68-76`,
`disk_row_iter.h:120-126`) and a tracker job-duration log
(`tracker.py:317-320`). This module keeps those habits but makes them
first-class and queryable:

* :class:`Counter` / :class:`Gauge` — monotonic / point-in-time values.
* :class:`Histogram` — value distribution with quantile estimation
  (p50/p95/p99 request latency is the serving subsystem's SLO surface;
  exact up to a sample cap, reservoir-sampled beyond it).
* :class:`ThroughputMeter` — bytes-or-records rate with total + windowed
  rate (what the MB/s prints computed inline).
* :class:`StageTimer` — accumulated wall time per pipeline stage, usable
  as a context manager or decorator; exposes count/total/mean.
* :class:`MetricsRegistry` — process-global named registry with
  ``snapshot()`` (one dict, JSON-serializable) and ``report()`` logging.
* :func:`profiler_annotation` — the ``jax.profiler.TraceAnnotation`` that
  :meth:`StageTimer.time` and ``telemetry.trace.span`` hold for their
  extent, so every timed stage shows on the host plane of a profile, on
  the device trace's clock.
"""

from __future__ import annotations

import contextlib
import math
import random
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

from .logging import log_info

__all__ = [
    "Counter", "Gauge", "Histogram", "ThroughputMeter", "StageTimer",
    "MetricsRegistry", "metrics", "profiler_annotation",
]


class Counter:
    """Monotonic counter (thread-safe)."""

    def __init__(self) -> None:
        self._v = 0
        self._lock = threading.Lock()

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> int:
        return self._v

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"type": "counter", "value": self._v}

    def state(self) -> Dict[str, Any]:
        """Serialized mergeable state (same as snapshot for counters)."""
        return self.snapshot()

    @classmethod
    def merge(cls, states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        return {"type": "counter",
                "value": sum(int(s.get("value", 0)) for s in states)}


class Gauge:
    """Last-set value."""

    def __init__(self) -> None:
        self._v: float = 0.0

    def set(self, v: float) -> None:
        self._v = v

    @property
    def value(self) -> float:
        return self._v

    def snapshot(self) -> Dict[str, Any]:
        return {"type": "gauge", "value": self._v}

    def state(self) -> Dict[str, Any]:
        return self.snapshot()

    @classmethod
    def merge(cls, states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        """Fleet view of a gauge is the worst (max) rank — health-style
        gauges encode severity as magnitude (0 ok / 1 degraded / ...)."""
        vals = [float(s.get("value", 0.0)) for s in states]
        return {"type": "gauge", "value": max(vals) if vals else 0.0}


#: exemplar slots per histogram — one per value region (well below
#: half the mean, below the mean, up to 2x the mean, the tail beyond)
_EXEMPLAR_SLOTS = 4


def _active_trace_hex() -> Optional[str]:
    """Hex trace id of the ambient trace context, or None.

    Resolved through ``sys.modules`` so this module never imports the
    telemetry package (which imports it back): if tracing was never
    imported there are no traces to reference, and the probe costs one
    dict lookup.
    """
    tr = sys.modules.get("dmlc_core_tpu.telemetry.trace")
    if tr is None:
        return None
    try:
        return tr.current_trace_id()
    except Exception:
        return None


class Histogram:
    """Value distribution with quantile estimation (thread-safe).

    Exact while the stream fits in ``max_samples``; past that, reservoir
    sampling keeps a uniform sample of everything seen so far, so
    quantiles stay unbiased over unbounded streams at O(1) memory while
    count/sum/min/max remain exact.  The reservoir RNG is seeded, so a
    replayed stream reports identical quantiles.

    When an observation happens inside an active trace context, the
    (value, trace_id, ts) triple is retained as an *exemplar* in one of
    :data:`_EXEMPLAR_SLOTS` slots bucketed by value region relative to
    the running mean — so the tail slot always references a concrete
    slow request.  Exemplars ride :meth:`snapshot` (key absent when none
    exist) and render in the OpenMetrics exposition format.
    """

    def __init__(self, max_samples: int = 8192, seed: int = 0) -> None:
        if max_samples <= 0:
            raise ValueError("max_samples must be > 0")
        self._cap = int(max_samples)
        self._samples: List[float] = []
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._rng = random.Random(seed)
        self._exemplars: List[Any] = [None] * _EXEMPLAR_SLOTS
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        tid = _active_trace_hex()
        with self._lock:
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v
            if len(self._samples) < self._cap:
                self._samples.append(v)
            else:
                j = self._rng.randrange(self._count)
                if j < self._cap:
                    self._samples[j] = v
            if tid is not None:
                mean = self._sum / self._count
                slot = (0 if v <= 0.5 * mean else
                        1 if v <= mean else
                        2 if v <= 2.0 * mean else 3)
                self._exemplars[slot] = (v, tid, time.time())

    @contextlib.contextmanager
    def time(self, clock: Callable[[], float] = time.monotonic
             ) -> Iterator[None]:
        """Observe the wall time of a block (seconds)."""
        t0 = clock()
        try:
            yield
        finally:
            self.observe(clock() - t0)

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    @property
    def min(self) -> float:
        return self._min if self._count else 0.0

    @property
    def max(self) -> float:
        return self._max if self._count else 0.0

    def quantile(self, q: float) -> float:
        return self.quantiles([q])[0]

    @staticmethod
    def _interp(sorted_samples: List[float], qs: Sequence[float]
                ) -> List[float]:
        """Linear interpolation between closest ranks (numpy's default)."""
        for q in qs:
            if not 0.0 <= q <= 1.0:
                raise ValueError(f"quantile must be in [0, 1], got {q}")
        s = sorted_samples
        if not s:
            return [0.0 for _ in qs]
        out = []
        for q in qs:
            pos = q * (len(s) - 1)
            lo = int(math.floor(pos))
            hi = min(lo + 1, len(s) - 1)
            out.append(s[lo] + (pos - lo) * (s[hi] - s[lo]))
        return out

    def quantiles(self, qs: Sequence[float]) -> List[float]:
        """Quantiles over the (possibly sampled) observation set."""
        with self._lock:
            s = sorted(self._samples)
        return self._interp(s, qs)

    def snapshot(self) -> Dict[str, Any]:
        # One lock acquisition for the whole view: quantiles, count, and
        # moments must describe the same instant or a concurrent observe()
        # tears the snapshot (count ahead of sum, quantile behind max).
        with self._lock:
            count, sum_ = self._count, self._sum
            mn = self._min if count else 0.0
            mx = self._max if count else 0.0
            s = sorted(self._samples)
            ex = [{"value": val, "trace_id": t, "ts": ts}
                  for (val, t, ts) in
                  (e for e in self._exemplars if e is not None)]
        p50, p95, p99 = self._interp(s, [0.5, 0.95, 0.99])
        snap = {"type": "histogram", "count": count,
                "mean": sum_ / count if count else 0.0, "min": mn, "max": mx,
                "p50": p50, "p95": p95, "p99": p99}
        if ex:
            # additive key: absent when no traced observation happened,
            # so snapshot consumers that never see traces are unchanged
            snap["exemplars"] = ex
        return snap

    def state(self) -> Dict[str, Any]:
        """Serialized reservoir state — exact moments + the sample set —
        consistent under one lock.  This is what ranks ship to the tracker;
        :meth:`merge` reconstructs fleet quantiles from a list of these."""
        with self._lock:
            count = self._count
            return {"type": "histogram", "count": count, "sum": self._sum,
                    "min": self._min if count else 0.0,
                    "max": self._max if count else 0.0,
                    "samples": list(self._samples)}

    @classmethod
    def merge(cls, states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        """Merge serialized states into one snapshot-form dict.

        Moments (count/sum/min/max) merge exactly.  Quantiles come from
        the union of reservoirs with each sample weighted by how many
        observations it stands for (``count_i / len(samples_i)``), so a
        rank that saw 10x the traffic pulls the fleet quantile 10x harder.
        Exact when no reservoir ever overflowed (weights all 1).
        """
        count = 0
        sum_ = 0.0
        mn, mx = math.inf, -math.inf
        weighted: List[Any] = []   # (value, weight) pairs
        for s in states:
            c = int(s.get("count", 0))
            if c <= 0:
                continue
            count += c
            sum_ += float(s.get("sum", 0.0))
            mn = min(mn, float(s.get("min", math.inf)))
            mx = max(mx, float(s.get("max", -math.inf)))
            samples = s.get("samples") or []
            if samples:
                w = c / len(samples)
                weighted.extend((float(v), w) for v in samples)
        if not count:
            return {"type": "histogram", "count": 0, "mean": 0.0,
                    "min": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0,
                    "p99": 0.0}
        weighted.sort(key=lambda vw: vw[0])
        p50, p95, p99 = cls._weighted_quantiles(weighted, [0.5, 0.95, 0.99])
        return {"type": "histogram", "count": count, "mean": sum_ / count,
                "min": mn, "max": mx, "p50": p50, "p95": p95, "p99": p99}

    @staticmethod
    def _weighted_quantiles(sorted_vw: List[Any], qs: Sequence[float]
                            ) -> List[float]:
        """Weighted quantiles by the midpoint rule: sample i sits at
        cumulative position ``cum_i - w_i/2``; interpolate between the
        bracketing samples.  Reduces to :meth:`_interp` for equal weights."""
        total_w = sum(w for _, w in sorted_vw)
        if total_w <= 0:
            return [0.0 for _ in qs]
        pos = []
        cum = 0.0
        for _, w in sorted_vw:
            pos.append(cum + w / 2.0)
            cum += w
        out = []
        for q in qs:
            target = q * total_w
            if target <= pos[0]:
                out.append(sorted_vw[0][0])
                continue
            if target >= pos[-1]:
                out.append(sorted_vw[-1][0])
                continue
            # binary search for the bracketing pair
            lo, hi = 0, len(pos) - 1
            while hi - lo > 1:
                mid = (lo + hi) // 2
                if pos[mid] <= target:
                    lo = mid
                else:
                    hi = mid
            v0, v1 = sorted_vw[lo][0], sorted_vw[hi][0]
            span = pos[hi] - pos[lo]
            frac = (target - pos[lo]) / span if span > 0 else 0.0
            out.append(v0 + frac * (v1 - v0))
        return out


class ThroughputMeter:
    """Rate meter: total units + overall and windowed rates.

    The structured form of the reference's inline MB/s computation
    (`basic_row_iter.h:70-75`): ``add(n)`` per batch, ``rate()`` anywhere.
    """

    def __init__(self, window_sec: float = 5.0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self._clock = clock
        self._start = clock()
        self._total = 0
        self._win_start = self._start
        self._win_total = 0
        self._win_rate = 0.0
        self._win_closed = False
        self._window = window_sec
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        with self._lock:
            self._total += n
            self._win_total += n
            now = self._clock()
            if now - self._win_start >= self._window:
                self._win_rate = self._win_total / (now - self._win_start)
                self._win_closed = True
                self._win_start = now
                self._win_total = 0

    @property
    def total(self) -> int:
        return self._total

    def _rate_locked(self, now: float) -> float:
        dt = now - self._start
        return self._total / dt if dt > 0 else 0.0

    def _windowed_locked(self, now: float) -> float:
        elapsed = now - self._win_start
        if elapsed >= self._window:
            # window overdue: rate over the open (possibly stalled) span
            return self._win_total / elapsed
        if self._win_closed:
            return self._win_rate
        return self._rate_locked(now)   # before the first window closes

    def rate(self) -> float:
        """Overall units/sec since construction."""
        with self._lock:
            return self._rate_locked(self._clock())

    def windowed_rate(self) -> float:
        """Units/sec over the current/most recent window. A stalled stream
        (no ``add`` calls) decays toward 0 as the open window ages — it must
        NOT keep reporting the last healthy rate."""
        with self._lock:
            return self._windowed_locked(self._clock())

    def snapshot(self) -> Dict[str, Any]:
        # total and both rates read at one instant under one lock — a
        # concurrent add() between them would report rate ahead of total
        with self._lock:
            now = self._clock()
            return {"type": "throughput", "total": self._total,
                    "rate": self._rate_locked(now),
                    "windowed_rate": self._windowed_locked(now)}

    def state(self) -> Dict[str, Any]:
        return self.snapshot()

    @classmethod
    def merge(cls, states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        """Totals and rates sum across ranks (parallel streams)."""
        return {"type": "throughput",
                "total": sum(int(s.get("total", 0)) for s in states),
                "rate": sum(float(s.get("rate", 0.0)) for s in states),
                "windowed_rate": sum(float(s.get("windowed_rate", 0.0))
                                     for s in states)}


# jax.profiler, resolved once per process; False caches a failed import
_profiler_mod: Any = None


def profiler_annotation(name: Optional[str]) -> Any:
    """A ``jax.profiler.TraceAnnotation(name)`` to hold with ``with``; a
    null context for no name, and in a process that has not imported JAX:
    no profile can be running there, and a timer must not be what drags
    JAX in.  With no profile running, entering one costs well under a
    microsecond."""
    global _profiler_mod
    prof = _profiler_mod
    if prof is None and name and "jax" in sys.modules:
        try:
            import jax.profiler as prof
        except Exception:
            prof = False
        _profiler_mod = prof
    return prof.TraceAnnotation(name) if prof and name \
        else contextlib.nullcontext()


class StageTimer:
    """Accumulated wall time for one pipeline stage.

    Use as context manager::

        with metrics.stage("parse").time():
            ...

    or decorate a function with the timer itself
    (``@metrics.stage("parse")``). Reports count / total / mean seconds.
    A timer the registry made knows its ``name`` and holds a profiler
    annotation of that name for each timed block.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 name: Optional[str] = None) -> None:
        self._clock = clock
        self.name = name
        self._count = 0
        self._total = 0.0
        self._lock = threading.Lock()

    def add(self, seconds: float) -> None:
        """Account one block that the caller timed itself."""
        with self._lock:
            self._count += 1
            self._total += seconds

    @contextlib.contextmanager
    def time(self) -> Iterator[None]:
        with profiler_annotation(self.name):
            t0 = self._clock()
            try:
                yield
            finally:
                self.add(self._clock() - t0)

    def __call__(self, fn: Callable) -> Callable:
        def wrapped(*a, **kw):
            with self.time():
                return fn(*a, **kw)
        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapped

    @property
    def count(self) -> int:
        return self._count

    @property
    def total_sec(self) -> float:
        return self._total

    @property
    def mean_sec(self) -> float:
        return self._total / self._count if self._count else 0.0

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:   # count and total from the same instant
            count, total = self._count, self._total
        return {"type": "stage", "count": count, "total_sec": total,
                "mean_sec": total / count if count else 0.0}

    def state(self) -> Dict[str, Any]:
        return self.snapshot()

    @classmethod
    def merge(cls, states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        count = sum(int(s.get("count", 0)) for s in states)
        total = sum(float(s.get("total_sec", 0.0)) for s in states)
        return {"type": "stage", "count": count, "total_sec": total,
                "mean_sec": total / count if count else 0.0}


class MetricsRegistry:
    """Named metrics with one-call snapshot/report.

    Hierarchical names by convention (``ingest.bytes``, ``device.batches``).
    """

    def __init__(self) -> None:
        self._m: Dict[str, Any] = {}
        self._lock = threading.Lock()
        #: bumped by reset(); hot paths that cache metric handles compare
        #: this (one int read, no lock) and re-fetch when it changes
        self.generation = 0

    def _get(self, key: str, cls, **kw):
        with self._lock:
            m = self._m.get(key)
            if m is None:
                m = cls(**kw)
                self._m[key] = m
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, max_samples: int = 8192) -> Histogram:
        return self._get(name, Histogram, max_samples=max_samples)

    def throughput(self, name: str, window_sec: float = 5.0) -> ThroughputMeter:
        return self._get(name, ThroughputMeter, window_sec=window_sec)

    def stage(self, name: str) -> StageTimer:
        return self._get(name, StageTimer, name=name)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: v.snapshot() for k, v in sorted(self._m.items())}

    def state(self) -> Dict[str, Dict[str, Any]]:
        """Serialized mergeable view of every metric (histograms carry
        their reservoir).  This is the payload workers push to the
        tracker; ``telemetry.aggregate`` merges a set of them."""
        with self._lock:
            items = sorted(self._m.items())
        return {k: (v.state() if hasattr(v, "state") else v.snapshot())
                for k, v in items}

    def report(self) -> None:
        for name, snap in self.snapshot().items():
            log_info("metric %s: %s", name,
                     " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in snap.items()
                              if k != "type"))

    def reset(self) -> None:
        with self._lock:
            self._m.clear()
            self.generation += 1


#: process-global registry (modules grab sub-metrics by name)
metrics = MetricsRegistry()
