"""Event-driven connection fabric (r19): the ``transport.reactor``
primitives (timer wheel, frame reassembly under adversarial chunking,
idle reaping, EMFILE backoff, executor handoff / loop-lag honesty) and
the ported tiers — byte-identical wire vs the threaded router, legacy
clients against a reactor router, the dispatcher's JSON-line RPC plane,
and the SIGKILL chaos drill with router-less client failover."""

import errno
import os
import random
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from dmlc_core_tpu.models import SparseLogReg  # noqa: E402
from dmlc_core_tpu.serving import (  # noqa: E402
    BucketLadder, InferenceEngine, PredictClient, PredictionServer,
    ServingRouter)
from dmlc_core_tpu.serving.server import (  # noqa: E402
    HELLO_REQ_ID, REQ_HEADER, RSP_HEADER, STATUS_BAD_REQUEST, STATUS_OK)
from dmlc_core_tpu.transport.listener import (  # noqa: E402
    FD_EXHAUSTION_ERRNOS, Listener, accept_once)
from dmlc_core_tpu.transport.reactor import (  # noqa: E402
    FrameAssembler, Reactor, TimerWheel)
from dmlc_core_tpu.utils.metrics import metrics  # noqa: E402

F = 1000
LEN = struct.Struct("<I")               # toy [u32 length][payload] wire


def _counter(name):
    return metrics.counter(name).value


def _wait_for(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _engine(w_scale=1.0):
    model = SparseLogReg(num_features=F)
    params = {"w": jnp.full((F,), w_scale, jnp.float32),
              "b": jnp.float32(0.0)}
    return InferenceEngine(model, params,
                           buckets=BucketLadder([(16, 512)]))


def _req(rng, rows=4, nnz_per_row=8):
    counts = rng.integers(1, nnz_per_row + 1, size=rows)
    ids = rng.integers(0, F, size=int(counts.sum())).astype(np.int32)
    vals = rng.random(len(ids), dtype=np.float32)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return ids, vals, row_ptr


def _ref_scores(w_scale, ids, vals, row_ptr):
    return np.array([w_scale * float(vals[row_ptr[r]:row_ptr[r + 1]].sum())
                     for r in range(len(row_ptr) - 1)])


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _echo_reactor(idle_s=0.0):
    """Reactor serving the toy length-prefixed echo protocol; returns
    ``(reactor, listener, frames)`` — frames collects reassembled
    payloads in arrival order."""
    r = Reactor("test-echo", executor_workers=1).start()
    lst = Listener("127.0.0.1", 0)
    frames = []

    def on_frame(conn, header, payload):
        frames.append(bytes(payload))
        conn.write(header + payload)

    def on_accept(sock, _addr):
        asm = FrameAssembler(LEN.size,
                             lambda c, h: LEN.unpack(h)[0], on_frame)
        conn = r.add_connection(sock, lambda c, v: asm.feed(c, v),
                                idle_s=idle_s)
        conn.data = asm

    r.add_listener(lst.sock, on_accept)
    return r, lst, frames


# ---------------------------------------------------------------------------
# timer wheel
# ---------------------------------------------------------------------------

def test_timer_wheel_fires_cancels_and_reports_lag():
    w = TimerWheel(granularity_s=0.05)
    fired = []
    now = 100.0
    w.schedule(now, 0.10, lambda: fired.append("a"))
    t_b = w.schedule(now, 0.10, lambda: fired.append("b"))
    w.schedule(now, 0.30, lambda: fired.append("c"))
    t_b.cancel()
    assert w.next_deadline() == pytest.approx(0.05 * int(100.10 / 0.05))

    # a's slot has fully elapsed at +0.2; c's has not
    n, lag = w.fire_due(now + 0.20)
    assert fired == ["a"] and n == 1
    assert lag == pytest.approx(0.10, abs=0.051)

    # firing late reports the delay — this is the loop-lag ground truth
    n, lag = w.fire_due(now + 1.00)
    assert fired == ["a", "c"] and n == 1
    assert lag == pytest.approx(0.70, abs=0.051)
    assert w.next_deadline() is None


# ---------------------------------------------------------------------------
# frame reassembly under adversarial chunking
# ---------------------------------------------------------------------------

def test_frame_reassembly_trickle_coalesced_torn():
    """Echo fuzz: the same frame set arrives as a 1-byte trickle, as one
    coalesced blob, and in random torn chunks (headers split across
    reads) — reassembly and the echoed byte stream must be exact."""
    rng = random.Random(19)
    payloads = [b"", b"x", bytes(rng.getrandbits(8) for _ in range(3)),
                rng.randbytes(257), rng.randbytes(70000),  # > scratch
                rng.randbytes(1)]
    stream = b"".join(LEN.pack(len(p)) + p for p in payloads)

    def chunkings():
        yield [stream[i:i + 1] for i in range(len(stream))
               ] if len(stream) < 4096 else None     # trickle (bounded)
        yield [stream]                               # fully coalesced
        for _ in range(3):                           # random torn cuts
            cuts = sorted(rng.sample(range(1, len(stream)),
                                     k=min(40, len(stream) - 1)))
            yield [stream[a:b] for a, b in
                   zip([0] + cuts, cuts + [len(stream)])]

    r, lst, frames = _echo_reactor()
    try:
        for chunks in chunkings():
            if chunks is None:
                # trickle the header-heavy prefix only — full 70 KB
                # 1-byte trickle is pointlessly slow
                head = stream[:600]
                chunks = [head[i:i + 1] for i in range(len(head))] \
                    + [stream[600:]]
            del frames[:]
            cli = socket.create_connection((lst.host, lst.port),
                                           timeout=10)
            try:
                for ch in chunks:
                    cli.sendall(ch)
                echoed = _recv_exact(cli, len(stream))
            finally:
                cli.close()
            assert echoed == stream
            assert frames == payloads
    finally:
        lst.close()
        r.stop()


# ---------------------------------------------------------------------------
# idle reaping
# ---------------------------------------------------------------------------

def test_idle_connections_reaped_active_ones_kept(monkeypatch):
    # the reactor reads this clock, which moves only when the test moves
    # it: a slow host stretches the real gaps, never the idle ones
    from dmlc_core_tpu.transport import reactor as reactor_mod
    clock = [1000.0]
    monkeypatch.setattr(reactor_mod, "time",
                        types.SimpleNamespace(monotonic=lambda: clock[0]))
    r, lst, _frames = _echo_reactor(idle_s=1.0)
    before = _counter("transport.reactor.idle_reaped")
    try:
        silent = socket.create_connection((lst.host, lst.port), timeout=10)
        chatty = socket.create_connection((lst.host, lst.port), timeout=10)
        silent.settimeout(10.0)
        chatty.settimeout(10.0)
        # chatty speaks every 0.4 s of the clock, 3.2 s in all: silent's
        # 1 s passes, chatty's never does
        for _ in range(8):
            chatty.sendall(LEN.pack(2) + b"hi")
            assert _recv_exact(chatty, LEN.size + 2) is not None
            if clock[0] < 1000.9:         # not yet idle for its limit
                silent.setblocking(False)
                with pytest.raises(BlockingIOError):
                    silent.recv(1)
                silent.settimeout(10.0)
            clock[0] += 0.4
        assert silent.recv(1) == b""        # reaped: EOF
        assert _counter("transport.reactor.idle_reaped") > before
        chatty.sendall(LEN.pack(2) + b"yo")
        assert _recv_exact(chatty, LEN.size + 2) == LEN.pack(2) + b"yo"
        silent.close()
        chatty.close()
    finally:
        lst.close()
        r.stop()


# ---------------------------------------------------------------------------
# EMFILE backoff: reactor accept path and threaded accept_once
# ---------------------------------------------------------------------------

class _FlakyListener:
    """Wraps a real listener; the first ``fails`` accepts raise EMFILE
    (selectors only needs ``fileno()``, so the wrapper registers fine)."""

    def __init__(self, inner, fails):
        self.inner = inner
        self.fails = fails

    def fileno(self):
        return self.inner.sock.fileno()

    def setblocking(self, flag):
        self.inner.sock.setblocking(flag)

    def accept(self):
        if self.fails > 0:
            self.fails -= 1
            raise OSError(errno.EMFILE, "too many open files")
        return self.inner.sock.accept()


def test_reactor_emfile_backoff_rearms_and_recovers():
    r = Reactor("test-emfile", executor_workers=1).start()
    lst = Listener("127.0.0.1", 0)
    flaky = _FlakyListener(lst, fails=2)
    before = _counter("transport.reactor.emfile_backoffs")

    def on_accept(sock, _addr):
        asm = FrameAssembler(LEN.size, lambda c, h: LEN.unpack(h)[0],
                             lambda c, h, p: c.write(h + p))
        conn = r.add_connection(sock, lambda c, v: asm.feed(c, v))
        conn.data = asm

    r.add_listener(flaky, on_accept)
    try:
        cli = socket.create_connection((lst.host, lst.port), timeout=10)
        cli.settimeout(10.0)
        # both EMFILE rounds unregister + re-arm after a jittered pause;
        # the third readiness event accepts for real and echo works
        cli.sendall(LEN.pack(4) + b"ping")
        assert _recv_exact(cli, LEN.size + 4) == LEN.pack(4) + b"ping"
        assert _counter("transport.reactor.emfile_backoffs") - before == 2
        assert flaky.fails == 0
        cli.close()
    finally:
        lst.close()
        r.stop()


def test_accept_once_retries_fd_exhaustion_then_accepts():
    a, b = socket.socketpair()

    class _Srv:
        calls = 0

        def accept(self):
            self.calls += 1
            if self.calls == 1:
                raise OSError(errno.ENFILE, "file table overflow")
            return a, ("peer", 0)

    before = _counter("transport.accept_backoffs")
    got = accept_once(_Srv())
    assert got is not None and got[0] is a
    assert _counter("transport.accept_backoffs") - before == 1

    class _Closed:
        def accept(self):
            raise OSError(errno.EBADF, "closed")         # shutdown path

    assert accept_once(_Closed()) is None
    assert errno.EMFILE in FD_EXHAUSTION_ERRNOS
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# executor handoff + loop-lag honesty
# ---------------------------------------------------------------------------

def test_executor_results_hop_back_to_loop():
    r = Reactor("test-exec", executor_workers=1).start()
    done = threading.Event()
    seen = {}

    def on_done(res, exc):
        seen["res"], seen["exc"], seen["on_loop"] = res, exc, r.in_loop()
        done.set()

    try:
        r.executor.submit(lambda: 40 + 2, on_done)
        assert done.wait(5.0)
        assert seen == {"res": 42, "exc": None, "on_loop": True}

        done.clear()
        r.executor.submit(lambda: 1 / 0, on_done)
        assert done.wait(5.0)
        assert isinstance(seen["exc"], ZeroDivisionError)
    finally:
        r.stop()


def test_loop_lag_visible_under_executor_saturation():
    """Flood a 1-worker executor from the loop: the bounded queue fills,
    overflow runs inline on the loop thread, and the heartbeat timer's
    fire-time slip surfaces on ``transport.reactor.loop_lag_ms`` —
    saturation is visible, never a silent deadlock."""
    r = Reactor("test-lag", executor_workers=1)
    inline_before = _counter("transport.reactor.executor_inline")
    r.start()
    gauge = metrics.gauge("transport.reactor.loop_lag_ms")

    def flood():
        for _ in range(40):
            r.executor.submit(lambda: time.sleep(0.02))

    try:
        r.call_soon(flood)
        peak, deadline = 0.0, time.monotonic() + 3.0
        while time.monotonic() < deadline:
            peak = max(peak, gauge.value)
            time.sleep(0.01)
        assert _counter("transport.reactor.executor_inline") > inline_before
        assert peak >= 50.0, f"loop lag never surfaced (peak {peak} ms)"
        # and the loop survived the abuse
        pong = threading.Event()
        r.call_soon(pong.set)
        assert pong.wait(5.0)
    finally:
        r.stop()


# ---------------------------------------------------------------------------
# ported tiers: wire equivalence + legacy interop
# ---------------------------------------------------------------------------

def _raw_response(host, port, frame, status):
    """Send one raw request frame, return the full response bytes."""
    with socket.create_connection((host, port), timeout=10) as s:
        s.settimeout(10.0)
        s.sendall(frame)
        head = _recv_exact(s, RSP_HEADER.size)
        assert head is not None
        req_id, st, n = RSP_HEADER.unpack(head)
        assert st == status
        body = _recv_exact(s, 4 * n if st == STATUS_OK else n)
        assert body is not None
        return head + body


def test_wire_byte_identical_threaded_vs_reactor_router():
    """The port's core promise: the reactor router emits the exact bytes
    the threaded router does — OK scores and BAD_REQUEST rejects — for
    identical request frames against the same replica."""
    srv = PredictionServer(_engine(2.0), metrics_port=0).start()
    threaded = ServingRouter(replicas=[(srv.host, srv.port)],
                             reactor=False).start()
    reactor = ServingRouter(replicas=[(srv.host, srv.port)],
                            reactor=True).start()
    try:
        rng = np.random.default_rng(7)
        ids, vals, row_ptr = _req(rng, rows=3)
        rows, nnz = len(row_ptr) - 1, len(ids)
        ok_frame = REQ_HEADER.pack(77, 0, 0, rows, nnz) \
            + row_ptr.tobytes() + ids.tobytes() + vals.tobytes()
        # hello preamble + request: model routing is part of the wire
        blob = b"default"
        hello_ok = REQ_HEADER.pack(HELLO_REQ_ID, 0, 0, 0, len(blob)) \
            + blob + ok_frame
        # header validation rejects before reading any tail
        bad_frame = REQ_HEADER.pack(78, 0, 0, (1 << 20) + 1, 4)

        for frame, status in ((ok_frame, STATUS_OK),
                              (hello_ok, STATUS_OK),
                              (bad_frame, STATUS_BAD_REQUEST)):
            a = _raw_response(threaded.host, threaded.port, frame, status)
            b = _raw_response(reactor.host, reactor.port, frame, status)
            assert a == b, f"wire divergence for status={status}"

        scores = np.frombuffer(
            _raw_response(reactor.host, reactor.port, ok_frame,
                          STATUS_OK)[RSP_HEADER.size:], np.float32)
        np.testing.assert_allclose(
            scores, _ref_scores(2.0, ids, vals, row_ptr), rtol=1e-5)
    finally:
        reactor.stop()
        threaded.stop()
        srv.stop()


def test_legacy_client_unmodified_against_reactor_router():
    """PredictClient predates the reactor and must not notice it —
    pipelined predicts, hello model routing, clean close."""
    srv = PredictionServer(_engine(1.5), metrics_port=0).start()
    router = ServingRouter(replicas=[(srv.host, srv.port)],
                           reactor=True).start()
    cli = PredictClient(router.host, router.port, model_id="default")
    try:
        rng = np.random.default_rng(3)
        futs, refs = [], []
        for _ in range(16):                       # pipelined, no waits
            ids, vals, row_ptr = _req(rng)
            futs.append(cli.submit(ids, vals, row_ptr))
            refs.append(_ref_scores(1.5, ids, vals, row_ptr))
        for fut, ref in zip(futs, refs):
            np.testing.assert_allclose(fut.result(timeout=30), ref,
                                       rtol=1e-5)
    finally:
        cli.close()
        router.stop()
        srv.stop()


def test_dispatcher_reactor_rpc_plane():
    """JSON-line RPCs against the reactor-backed dispatcher: a trickled
    request parses, junk gets an error reply, and a line that never
    terminates is killed at the 4 MB bound instead of buffered forever."""
    from dmlc_core_tpu.pipeline.data_service.dispatcher import Dispatcher

    d = Dispatcher(port=0, reactor=True)
    d.start()
    try:
        # one-byte trickle of a valid command
        msg = b'{"cmd": "list_workers"}\n'
        with socket.create_connection((d.host, d.port), timeout=10) as s:
            s.settimeout(10.0)
            for i in range(len(msg)):
                s.sendall(msg[i:i + 1])
            reply = s.makefile("r").readline()
        assert "workers" in reply and "error" not in reply

        with socket.create_connection((d.host, d.port), timeout=10) as s:
            s.settimeout(10.0)
            s.sendall(b"this is not json\n")
            reply = s.makefile("r").readline()
        assert "error" in reply

        # unterminated line: the reactor kills the connection at the
        # bound — recv sees EOF/RST, never an unbounded buffer
        with socket.create_connection((d.host, d.port), timeout=10) as s:
            s.settimeout(10.0)
            try:
                s.sendall(b"x" * ((4 << 20) + (64 << 10)))
                assert s.recv(1) == b""
            except OSError:
                pass                               # RST also acceptable
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# chaos drill: SIGKILL the reactor router mid-load
# ---------------------------------------------------------------------------

def test_chaos_sigkill_reactor_router_client_fails_over():
    """Run a reactor-mode router as a real OS process, SIGKILL it with
    requests in flight, and require the stock client's endpoint sweep to
    land every request on the direct replica — correct scores for all,
    no duplicates (each future settles exactly once), failovers
    counted."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    srv = PredictionServer(_engine(1.0), metrics_port=0).start()
    env = {**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
           "DMLC_SERVE_REACTOR": "1"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "dmlc_core_tpu.serving.fleet.router",
         f"replicas={srv.host}:{srv.port}", "host=127.0.0.1", "port=0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        bufsize=1, env=env)
    cli = None
    try:
        line = proc.stdout.readline()
        assert line.startswith("routing on "), (line, proc.stderr.read())
        rhost, rport = line.split()[-1].rsplit(":", 1)

        before = _counter("serving.client.failovers")
        cli = PredictClient(rhost, int(rport),
                            endpoints=[(srv.host, srv.port)])
        rng = np.random.default_rng(11)
        reqs = [_req(rng) for _ in range(24)]
        futs = []
        for i, (ids, vals, row_ptr) in enumerate(reqs):
            futs.append(cli.submit(ids, vals, row_ptr))
            if i == 7:                     # kill with futures in flight
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)
        results = [f.result(timeout=60) for f in futs]

        # exactly one settled result per request, all correct — replayed
        # frames may score twice server-side, but the client surfaces
        # each exactly once
        assert len(results) == len(reqs)
        for got, (ids, vals, row_ptr) in zip(results, reqs):
            np.testing.assert_allclose(
                got, _ref_scores(1.0, ids, vals, row_ptr), rtol=1e-5)
        assert _counter("serving.client.failovers") > before
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
        proc.stderr.close()
        if cli is not None:
            cli.close()
        srv.stop()
