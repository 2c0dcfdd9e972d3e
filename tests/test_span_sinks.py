"""The one span primitive on its three sinks (record, stage total, stall
detector) and on the profiler's host plane; the loader's work and waits as
spans on every pack path; compiles as program events; the device scopes in
the compiled HLO's ``op_name``."""

import glob
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dmlc_core_tpu.data import create_parser
from dmlc_core_tpu.models import (DCNv2, FactorizationMachine,
                                  make_train_step)
from dmlc_core_tpu.pipeline import DeviceLoader
from dmlc_core_tpu.pipeline.device_loader import (_fused_words_meta,
                                                  make_decoder)
from dmlc_core_tpu.telemetry import trace
from dmlc_core_tpu.telemetry.anomaly import StallDetector
from dmlc_core_tpu.telemetry.xla_introspect import install_compile_listeners
from dmlc_core_tpu.utils.metrics import StageTimer, metrics

metrics_mod = sys.modules[StageTimer.__module__]   # the module, not the registry


def records(name=None):
    return [r for r in trace.recorder.snapshot()
            if r["kind"] == "span" and name in (None, r["name"])]


@pytest.fixture()
def clean_ring():
    trace.recorder.clear()
    yield
    trace.recorder.clear()


# ---------------------------------------------------------------- primitive

def test_span_feeds_three_sinks_from_one_duration(clean_ring):
    class Seen:
        got = []
        spans = []

        def observe(self, dur_s, span=None):
            self.got.append(dur_s)
            self.spans.append(span)

    stage, stall = StageTimer(), Seen()
    for _ in range(3):
        with trace.span("unit.sinks", stage=stage, stall=stall, rows=7):
            time.sleep(0.002)
    recs = records("unit.sinks")
    assert len(recs) == 3 and stage.count == 3
    # the detector is handed the span that just ended, with its duration
    assert [s.dur_s for s in stall.spans] == stall.got
    assert all(r["attrs"] == {"rows": 7} for r in recs)
    # the stage total IS the recorded durations (dur_us drops the fraction
    # of a microsecond), and the detector saw the same three numbers
    assert stage.total_sec == pytest.approx(sum(stall.got), abs=1e-12)
    assert sum(r["dur_us"] for r in recs) <= stage.total_sec * 1e6 < \
        sum(r["dur_us"] for r in recs) + 3
    assert min(stall.got) >= 0.002


def test_span_hands_a_real_stall_detector_its_duration(clean_ring):
    stall = StallDetector("unit.sinks.stall")
    with trace.span("unit.sinks.stall", stall=stall):
        pass
    assert stall._stat.n == 1


def test_record_carries_the_monotonic_start(clean_ring):
    before = time.monotonic()
    with trace.span("unit.mono"):
        with trace.span("unit.mono.child"):
            time.sleep(0.001)
    after = time.monotonic()
    parent, child = records("unit.mono")[0], records("unit.mono.child")[0]
    for r in (parent, child):
        assert before * 1e6 - 1 <= r["mono_us"] <= after * 1e6
        assert r["mono_us"] + r["dur_us"] <= after * 1e6 + 1
    # children lie inside their parent on the one clock
    assert parent["mono_us"] <= child["mono_us"]
    assert (child["mono_us"] + child["dur_us"]
            <= parent["mono_us"] + parent["dur_us"] + 1)
    assert child["parent_id"] == parent["span_id"]


def _spin(seconds):
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        pass


def test_a_sleeping_span_has_no_cpu_time_and_a_spinning_one_has_all(
        clean_ring):
    with trace.span("unit.cpu.sleep") as asleep:
        time.sleep(0.05)
    # the best of a few: a spin on a shared machine can lose its core
    for _ in range(5):
        with trace.span("unit.cpu.spin"):
            _spin(0.05)
    (sleep,) = records("unit.cpu.sleep")
    assert sleep["dur_us"] >= 50_000 and sleep["cpu_us"] < 0.1 * sleep["dur_us"]
    assert asleep.cpu_s == pytest.approx(sleep["cpu_us"] * 1e-6, abs=1e-6)
    spin = max(records("unit.cpu.spin"),
               key=lambda r: r["cpu_us"] / r["dur_us"])
    assert abs(spin["dur_us"] - spin["cpu_us"]) <= 0.2 * spin["dur_us"]


def test_cpu_time_is_the_spans_own_thread_only(clean_ring):
    # a worker burns a core while the span's thread sleeps: not in cpu_us
    t = threading.Thread(target=_spin, args=(0.05,))
    with trace.span("unit.cpu.own"):
        t.start()
        t.join(timeout=30)
    (rec,) = records("unit.cpu.own")
    assert rec["dur_us"] >= 50_000 and rec["cpu_us"] < 0.2 * rec["dur_us"]


def test_a_span_ended_on_another_thread_has_no_cpu_time(clean_ring):
    s = trace.start_span("unit.cpu.handed_over")
    t = threading.Thread(target=s.end)
    t.start()
    t.join(timeout=30)
    (rec,) = records("unit.cpu.handed_over")
    assert "cpu_us" not in rec and s.cpu_s is None


SPAN_KEYS_BEFORE = {"kind", "name", "trace_id", "span_id", "parent_id",
                    "ts_us", "mono_us", "dur_us", "pid", "tid", "thread",
                    "attrs", "events"}


def test_snapshot_renders_the_same_keys_plus_cpu_us(clean_ring):
    import json
    import os
    with trace.span("unit.keys", rows=3, wire=(0, 0)) as s:
        s.event("retry", attempt=2)
    (rec,) = trace.recorder.snapshot()
    assert set(rec) == SPAN_KEYS_BEFORE | {"cpu_us"}
    assert rec["pid"] == os.getpid()
    assert rec["trace_id"] == trace.format_id(s.trace_id)
    assert rec["span_id"] == trace.format_id(s.span_id)
    assert rec["attrs"] == {"rows": 3, "wire": [0, 0]} or \
        rec["attrs"] == {"rows": 3, "wire": (0, 0)}
    assert rec["events"][0]["name"] == "retry"
    json.dumps(rec)
    # a rendered record handed to the ring comes back as it went in
    trace.recorder.record({"kind": "span", "name": "unit.keys.dict"})
    assert trace.recorder.snapshot()[-1] == {"kind": "span",
                                             "name": "unit.keys.dict"}
    # only what ended at or after an instant
    later = time.monotonic() + 1.0
    assert trace.recorder.snapshot(since_mono_s=later) == []
    assert [r["name"] for r in trace.recorder.snapshot(
        since_mono_s=s._t0_mono)] == ["unit.keys"]


def test_the_default_ring_holds_32768_records_and_counts_the_rest(
        clean_ring, monkeypatch):
    monkeypatch.setattr(trace, "recorder", trace.SpanRecorder())
    for _ in range(40_000):
        with trace.span("unit.ring"):
            pass
    assert len(trace.recorder) == 32_768
    assert trace.recorder.dropped == 7_232


def test_a_span_under_its_floor_leaves_no_record(clean_ring):
    stage = StageTimer()
    with trace.span("unit.floor", stage=stage, floor_s=0.01):
        pass
    with trace.span("unit.floor", stage=stage, floor_s=0.01):
        time.sleep(0.02)
    (rec,) = records("unit.floor")
    assert rec["dur_us"] >= 20_000 and stage.count == 2


# ------------------------------------------------------------ stall capture

def test_a_forced_stall_leaves_one_capture_of_what_the_ring_saw(
        clean_ring, caplog):
    """A detector warmed on 20 short spans, then one long one whose time is
    in one child while another thread works and a third sits in a wait."""
    from dmlc_core_tpu.telemetry import flight
    stall = StallDetector("unit.stalled", min_samples=16)

    def turn(child_s):
        with trace.span("unit.stalled", stall=stall):
            with trace.span("unit.stalled.quick"):
                pass
            with trace.span("unit.stalled.long"):
                time.sleep(child_s)

    for _ in range(20):
        turn(0.001)
    assert not [r for r in trace.recorder.snapshot()
                if r["name"] == "stall.capture"]

    go, done = threading.Event(), threading.Event()

    def other():
        go.wait(10)
        for _ in range(3):
            with trace.span("unit.other.work"):
                _spin(0.01)

    def waiter():
        with trace.span("unit.waiter.blocked"):
            done.wait(10)

    threads = [threading.Thread(target=other, name="unit-other"),
               threading.Thread(target=waiter, name="unit-waiter")]
    for t in threads:
        t.start()
    time.sleep(0.01)            # the waiter is inside its span by now
    go.set()
    turn(0.15)
    done.set()
    for t in threads:
        t.join(timeout=30)

    (cap,) = [r for r in trace.recorder.snapshot()
              if r["name"] == "stall.capture"]
    assert cap["kind"] == "event"
    a = cap["attrs"]
    assert a["span"] == "unit.stalled" and a["thread"] == "MainThread"
    assert a["dur_us"] >= 150_000 and a["cpu_us"] < 0.2 * a["dur_us"]
    assert set(a["children"]) == {"unit.stalled.quick", "unit.stalled.long"}
    long = a["children"]["unit.stalled.long"]
    assert long["n"] == 1 and long["dur_us"] >= 150_000
    assert long["cpu_us"] < 0.2 * long["dur_us"]
    assert a["children"]["unit.stalled.quick"]["dur_us"] < 1_000
    # the thread that worked: its spans' seconds inside the stalled extent
    worked = a["threads"]["unit-other"]
    assert 0.02 <= worked["spans"]["unit.other.work"] <= 0.06
    assert worked["uncovered_s"] == pytest.approx(
        a["dur_us"] * 1e-6 - worked["spans"]["unit.other.work"], abs=2e-3)
    # the thread that waited all along has finished nothing: it is named by
    # the span it is still in
    blocked = a["threads"]["unit-waiter"]
    assert blocked["open"]["name"] == "unit.waiter.blocked"
    assert blocked["open"]["for_s"] >= 0.15 and not blocked["spans"]
    # the same capture as a flight note and as one line on standard error
    notes = [n for n in flight.flight_recorder.notes()
             if n["kind"] == "stall_capture" and n["span"] == "unit.stalled"]
    assert notes and notes[-1]["children"] == a["children"]
    assert caplog.text.count("stall.capture {") == 1
    assert not trace.open_spans()


def test_an_error_still_reaches_every_sink(clean_ring):
    stage = StageTimer()
    with pytest.raises(KeyError):
        with trace.span("unit.err", stage=stage):
            raise KeyError("x")
    assert stage.count == 1
    assert "KeyError" in records("unit.err")[0]["attrs"]["error"]


def test_record_completed_is_placed_in_the_past(clean_ring):
    now = time.monotonic()
    trace.record_completed("unit.done", 1.5, why="timed elsewhere")
    r = records("unit.done")[0]
    assert r["dur_us"] == pytest.approx(1.5e6, abs=5e3)
    assert r["mono_us"] == pytest.approx((now - 1.5) * 1e6, abs=5e4)
    assert r["attrs"] == {"why": "timed elsewhere"}
    assert "cpu_us" not in r       # nobody took its thread's CPU clock


def test_registry_stage_timer_knows_its_name_and_takes_add():
    st = metrics.stage("unit.named_stage")
    assert st.name == "unit.named_stage" and StageTimer().name is None
    n, total = st.count, st.total_sec
    st.add(0.25)
    assert (st.count, st.total_sec) == (n + 1, pytest.approx(total + 0.25))


def test_no_annotation_and_no_import_in_a_process_without_jax(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_profiler_mod", None)
    monkeypatch.delitem(sys.modules, "jax")
    import contextlib
    assert isinstance(metrics_mod.profiler_annotation("unit.nojax"),
                      contextlib.nullcontext)
    assert "jax" not in sys.modules and metrics_mod._profiler_mod is None
    with metrics.stage("unit.nojax").time():       # still times
        pass
    with trace.span("unit.nojax"):
        pass


# ------------------------------------------------- the profiler's host plane

@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """{event name: count} of the host plane of one CPU profile, taken
    while the primitive and a registry stage timer ran on the main thread
    and on a worker."""
    import jax.profiler as prof
    out = str(tmp_path_factory.mktemp("profile"))

    def work(tag):
        with trace.span(f"unit.prof.span.{tag}"):
            with metrics.stage(f"unit.prof.stage.{tag}").time():
                time.sleep(0.002)

    prof.start_trace(out)
    try:
        work("main")
        t = threading.Thread(target=work, args=("worker",))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        prof.stop_trace()
    pb = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    assert pb
    counts = {}
    for plane in prof.ProfileData.from_file(pb[-1]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("unit.prof."):
                        counts[e.name] = counts.get(e.name, 0) + 1
                        assert e.duration_ns >= 2e6
    return counts


@pytest.mark.parametrize("what", ["span", "stage"])
@pytest.mark.parametrize("thread", ["main", "worker"])
def test_profile_holds_a_host_event_of_the_name(host_events, what, thread):
    assert host_events.get(f"unit.prof.{what}.{thread}") == 1


# ------------------------------------------------------------- the loader

def _text(path, fmt, rows=1500, seed=3):
    rng = np.random.default_rng(seed)
    field = "" if fmt == "libsvm" else "3:"
    with open(path, "w") as f:
        for i in range(rows):
            idx = np.sort(rng.choice(5000, size=int(rng.integers(1, 9)),
                                     replace=False))
            f.write(f"{i % 2} " + " ".join(
                f"{field}{j}:{rng.random():.3f}" for j in idx) + "\n")
    return f"file://{path}"


PATHS = {
    # name: (parser kwargs, loader kwargs, the path's own predicate)
    "streampack": (dict(nthreads=1, threaded=False), {},
                   lambda ld: ld._use_streampack()),
    "native": ({}, {},
               lambda ld: ld._use_native_pack()
               and not ld._use_streampack()),
    # field batches (libfm text) have no fused wire: the numpy packer
    "python": ({}, dict(fields=True),
               lambda ld: not ld._use_native_pack()),
    "ragged": ({}, dict(ragged=True), lambda ld: ld.ragged),
    "pool": ({}, dict(put_threads=2), lambda ld: ld._use_native_pack()),
    "compact": ({}, dict(wire_compact=True),
                lambda ld: ld._use_native_pack()),
}


def _inside(child, parent):
    return (parent["mono_us"] <= child["mono_us"] and
            child["mono_us"] + child["dur_us"]
            <= parent["mono_us"] + parent["dur_us"] + 1)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_loader_spans_on_every_pack_path(tmp_path, clean_ring, path):
    from dmlc_core_tpu import native
    if path not in ("python", "ragged") and not native.has_packer():
        pytest.skip("native packer not built")
    parser_kw, loader_kw, on_path = PATHS[path]
    metrics.reset()
    kw = dict(batch_rows=128, nnz_cap=2048, wire_compact=False)
    kw.update(loader_kw)
    fmt = "libfm" if kw.get("fields") else "libsvm"
    loader = DeviceLoader(
        create_parser(_text(tmp_path / f"t.{fmt}", fmt), 0, 1, fmt,
                      **parser_kw), **kw)
    try:
        assert on_path(loader)
        batches = 0
        while loader.next_batch() is not None:
            batches += 1
    finally:
        loader.close()
    assert batches >= 10

    # the consumer's wait: one record a call, got false only for the None
    nb = records("device_loader.next_batch")
    assert [r["attrs"]["got"] for r in nb] == [True] * batches + [False]
    assert all(r["thread"] == "MainThread" for r in nb)

    pool = path == "pool"
    h2d = records("device_loader.h2d_pool" if pool else "device_loader.h2d")
    assert len(h2d) == batches
    assert not records("device_loader.h2d" if pool
                       else "device_loader.h2d_pool")
    by_id = {r["span_id"]: r for r in h2d}
    put = records("device_loader.put")
    assert len(put) == batches
    assert all(_inside(r, by_id[r["parent_id"]]) for r in put)
    # a fused batch's put says which wire it rode, (id_width, dict_bits):
    # (0, 0) is v2; these ids need 13 bits, which the packer rounds to 16
    wires = {tuple(r["attrs"].get("wire", ())) for r in put}
    if path in ("python", "ragged"):
        assert wires == {()}
    elif path == "compact":
        assert wires and all(w == 16 for w, _ in wires)
    else:
        assert wires == {(0, 0)}
    # the fused paths keep a ring of in-flight batches (or, in pool mode,
    # wait for each); per-array batches are left to JAX and wait for none
    wait_name = "device_loader.pool_wait" if pool else \
        "device_loader.ring_wait"
    waits = records(wait_name)
    assert not records("device_loader.ring_wait" if pool
                       else "device_loader.pool_wait")
    nested = [r for r in waits if r["parent_id"] in by_id]
    assert all(_inside(r, by_id[r["parent_id"]]) for r in nested)
    if path in ("python", "ragged"):
        assert not waits
    elif pool:
        assert len(nested) == len(waits) == batches
    else:
        # all but the ring's depth wait inside a transfer; the drain at
        # the end of the epoch, which no transfer causes, waits for the rest
        assert nested and len(waits) == batches
        assert all(r["parent_id"] is None for r in waits
                   if r["parent_id"] not in by_id)

    # put at its two calls: the transfer, then (where a compiled decoder
    # is dispatched: on the CPU only the compact wire's) the decode
    puts = {r["span_id"]: r for r in put}
    for name, n in (("device_loader.put.transfer", batches),
                    ("device_loader.put.decode",
                     batches if path == "compact" else 0)):
        kids = records(name)
        assert len(kids) == n, name
        assert all(_inside(r, puts[r["parent_id"]]) for r in kids), name
        assert all(r["cpu_us"] <= r["dur_us"] + 1000 for r in kids), name

    pack = records("device_loader.pack")
    assert len(pack) >= batches

    # the consumer's records carry the process's CPU clock, which only rises
    clock = [r["attrs"]["proc_cpu_us"] for r in nb]
    assert clock == sorted(clock) and clock[-1] > clock[0] > 0

    # the parser's stages are spans too (one pass of streampack has no
    # parse of its own), on the thread that ran them, with the team size
    # the kernel was built with
    chunks, parses = records("parser.chunk"), records("parser.parse")
    assert chunks and chunks[-1]["attrs"].get("bytes", 0) == 0
    if path == "streampack":
        assert not parses
    else:
        assert len(parses) == len(chunks) - 1
        assert all(r["attrs"]["nthreads"] >= 1 and r["attrs"]["bytes"] > 0
                   for r in parses)
        # on the parser's own thread, named after its queue; the put's
        # thread is the last queue's (the pool's workers are unnamed)
        assert {r["thread"] for r in parses} == {"parser.prefetch"}
        if not pool:
            assert {r["thread"] for r in put} == \
                {"device_loader.batch_queue"}
        for name in ("parser.chunk", "parser.parse"):
            assert metrics.stage(name).count == len(records(name)), name

    # each span is the stage timer of its name: same count, same seconds
    for name in ("device_loader.next_batch", "device_loader.put",
                 "device_loader.pack", *([wait_name] if waits else []),
                 "device_loader.h2d_pool" if pool else "device_loader.h2d"):
        recs, st = records(name), metrics.stage(name)
        assert st.count == len(recs), name
        assert sum(r["dur_us"] for r in recs) <= st.total_sec * 1e6 \
            < sum(r["dur_us"] for r in recs) + len(recs), name


# --------------------------------------------------------------- compiles

def test_a_fresh_shape_is_one_compile_event(clean_ring):
    install_compile_listeners()
    install_compile_listeners()          # idempotent: still one listener
    x = jax.block_until_ready(jnp.arange(1237, dtype=jnp.float32))
    f = jax.jit(lambda a: a * 3.0 + 1.0)
    trace.recorder.clear()               # making x may have compiled too
    n0 = metrics.counter("xla.backend_compiles").value
    h0 = metrics.histogram("xla.backend_compile_seconds").count
    t0 = time.monotonic()
    jax.block_until_ready(f(x))
    t1 = time.monotonic()
    assert metrics.counter("xla.backend_compiles").value == n0 + 1
    assert metrics.histogram("xla.backend_compile_seconds").count == h0 + 1
    (rec,) = records("xla.backend_compile")
    assert t0 * 1e6 - 1e3 <= rec["mono_us"]
    assert rec["mono_us"] + rec["dur_us"] <= t1 * 1e6 + 1e3
    jax.block_until_ready(f(x))          # the same shape: no new compile
    assert metrics.counter("xla.backend_compiles").value == n0 + 1
    assert len(records("xla.backend_compile")) == 1


# ----------------------------------------------------------- device scopes

ROWS, NNZ, F = 64, 512, 1024
V2_META = NNZ
V3_META = NNZ | (10 << 32) | (6 << 40)      # 10-bit ids, 6-bit value codes


def _batch():
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return {"ids": S((NNZ,), i32), "vals": S((NNZ,), f32),
            "segments": S((NNZ,), i32), "labels": S((ROWS,), f32),
            "weights": S((ROWS,), f32)}


def _fm_train_step():
    model, opt = FactorizationMachine(num_features=F, dim=8), optax.adam(1e-2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return make_train_step(model, opt).lower(
        params, jax.eval_shape(opt.init, params), _batch())


def _dcn_forward():
    model = DCNv2(num_features=F, dim=8, layers=2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.jit(model.forward).lower(params, _batch())


def _decoder(meta):
    buf = jax.ShapeDtypeStruct((_fused_words_meta(ROWS, meta),), jnp.int32)
    return jax.jit(make_decoder(ROWS, meta)).lower(buf)


DECODE = ["wire_decode/ids", "wire_decode/vals", "wire_decode/segments"]
SCOPED = {
    "fm_train_step": (_fm_train_step, [
        "loss_and_grad", "optimizer_update", "apply_updates", "csr_gather",
        "csr_segment_sum", "loss", "transpose(jvp(csr_gather))"]),
    "dcn_forward": (_dcn_forward, [
        "csr_gather", "csr_segment_sum", "dcn_cross", "dcn_head"]),
    "decoder_v2": (lambda: _decoder(V2_META), DECODE),
    "decoder_compact": (lambda: _decoder(V3_META), DECODE),
}


@pytest.mark.parametrize("program", sorted(SCOPED))
def test_compiled_hlo_names_every_scope(program):
    lower, scopes = SCOPED[program]
    text = lower().compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        # a path component of its own, bare or inside jvp(...)/transpose(...)
        at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
        assert any(at.search(n) for n in op_names), (scope,
                                                     sorted(op_names))
    # metadata only: the program keeps the name the trace readers find
    want = "jit__unpack" if program.startswith("decoder") else \
        {"fm_train_step": "jit_step", "dcn_forward": "jit_forward"}[program]
    assert f"HloModule {want}" in text
