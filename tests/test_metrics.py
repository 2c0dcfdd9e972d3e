"""Metrics/tracing subsystem tests + wiring checks (ingest stages must
populate the process-global registry)."""

import threading

import pytest

from dmlc_core_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StageTimer,
    ThroughputMeter,
    metrics,
)


def test_counter_thread_safe():
    c = Counter()

    def bump():
        for _ in range(1000):
            c.add()

    ts = [threading.Thread(target=bump) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 4000


def test_gauge():
    g = Gauge()
    g.set(3.5)
    assert g.value == 3.5
    assert g.snapshot() == {"type": "gauge", "value": 3.5}


def test_throughput_meter_rates():
    now = [0.0]
    m = ThroughputMeter(window_sec=1.0, clock=lambda: now[0])
    now[0] = 1.0
    m.add(100)          # closes no window yet? t=1.0, win_start=0 → closes
    assert m.total == 100
    assert m.rate() == pytest.approx(100.0)
    now[0] = 2.0
    m.add(50)
    assert m.windowed_rate() > 0


def test_stage_timer_context_and_decorator():
    now = [0.0]
    st = StageTimer(clock=lambda: now[0])
    with st.time():
        now[0] += 2.0
    assert st.count == 1
    assert st.total_sec == pytest.approx(2.0)

    @st
    def work():
        now[0] += 1.0
        return 7

    assert work() == 7
    assert st.count == 2
    assert st.mean_sec == pytest.approx(1.5)


def test_registry_snapshot_and_reuse():
    r = MetricsRegistry()
    r.counter("a.b").add(3)
    r.counter("a.b").add(2)          # same instance by name
    r.gauge("g").set(1.0)
    with r.stage("s").time():
        pass
    snap = r.snapshot()
    assert snap["a.b"]["value"] == 5
    assert snap["g"]["value"] == 1.0
    assert snap["s"]["count"] == 1
    import json
    json.dumps(snap)                  # snapshot must be JSON-serializable
    r.report()                        # must not raise
    r.reset()
    assert r.snapshot() == {}


def test_histogram_exact_quantiles_under_cap():
    """While the sample count fits the reservoir, quantiles are EXACT
    (linear interpolation between closest ranks)."""
    h = Histogram(max_samples=1000)
    for v in range(1, 101):               # 1..100, in order
        h.observe(float(v))
    assert h.count == 100
    assert h.min == 1.0 and h.max == 100.0
    assert h.mean == pytest.approx(50.5)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 100.0
    assert h.quantile(0.5) == pytest.approx(50.5)
    p50, p95, p99 = h.quantiles([0.5, 0.95, 0.99])
    assert p50 == pytest.approx(50.5)
    assert p95 == pytest.approx(95.05)
    assert p99 == pytest.approx(99.01)


def test_histogram_insertion_order_irrelevant():
    import random
    vals = list(range(1, 101))
    random.Random(7).shuffle(vals)
    h = Histogram(max_samples=1000)
    for v in vals:
        h.observe(float(v))
    assert h.quantile(0.5) == pytest.approx(50.5)


def test_histogram_reservoir_beyond_cap_stays_bounded_and_sane():
    h = Histogram(max_samples=64, seed=3)
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000              # exact even when sampling
    assert h.mean == pytest.approx(4999.5)
    assert h.min == 0.0 and h.max == 9999.0
    # sampled median of U[0,10000) lands near the middle
    assert 2000.0 < h.quantile(0.5) < 8000.0


def test_histogram_empty_and_errors():
    h = Histogram()
    assert h.count == 0
    assert h.quantile(0.5) == 0.0
    assert h.mean == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        h.quantile(-0.1)
    with pytest.raises(ValueError):
        Histogram(max_samples=0)


def test_histogram_snapshot_and_registry():
    r = MetricsRegistry()
    h = r.histogram("lat")
    assert r.histogram("lat") is h        # same instance by name
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = r.snapshot()["lat"]
    assert snap["type"] == "histogram"
    assert snap["count"] == 4
    assert snap["p50"] == pytest.approx(2.5)
    import json
    json.dumps(snap)


def test_histogram_time_context():
    h = Histogram()
    with h.time():
        pass
    assert h.count == 1
    assert h.min >= 0.0


def test_cached_handles_rebind_after_reset(tmp_path):
    """A parser built BEFORE metrics.reset() must still report into the
    registry afterwards (generation-based rebinding)."""
    f = tmp_path / "r.libsvm"
    f.write_text("".join(f"{i%2} {i%5+1}:1.0\n" for i in range(100)))
    from dmlc_core_tpu.data import create_parser
    p = create_parser(f"file://{f}", 0, 1, "libsvm", threaded=False)
    metrics.reset()                       # epoch boundary
    rows = sum(blk.size for blk in p)
    p.close()
    assert rows == 100
    assert metrics.snapshot()["parser.bytes"]["total"] == f.stat().st_size


def test_ingest_populates_global_metrics(tmp_path):
    metrics.reset()
    f = tmp_path / "d.libsvm"
    f.write_text("".join(f"{i%2} {i%5+1}:1.0\n" for i in range(200)))
    from dmlc_core_tpu.data import create_parser
    p = create_parser(f"file://{f}", 0, 1, "libsvm")
    rows = sum(blk.size for blk in p)
    p.close()
    assert rows == 200
    snap = metrics.snapshot()
    assert snap["parser.bytes"]["total"] == f.stat().st_size
    assert snap["parser.parse"]["count"] >= 1
    assert snap["parser.chunk"]["count"] >= 1
