"""Threaded TCP prediction server over the engine + micro-batcher.

Same wire discipline as the disaggregated ingest service
(`pipeline/ingest_service.py`): length-prefixed little-endian frames over
plain TCP with ``TCP_NODELAY``, no serialization framework in the hot
path.  Requests and responses are correlated by a client-chosen ``req_id``
so one connection can **pipeline** many requests and receive responses in
completion order — that is what lets a single client thread keep the
micro-batcher full.

Wire format (all little-endian)::

    request:   [req_id u64][trace_id u64][parent_span u64][rows u32][nnz u32]
               [row_ptr i32 × (rows+1)][ids i32 × nnz][vals f32 × nnz]
    response:  [req_id u64][status u8][n u32]
               status 0 (OK):  [scores f32 × n]      (n == rows)
               status ≠ 0:     [utf-8 message × n]
    statuses:  0 OK, 1 OVERLOADED, 2 DEADLINE_EXCEEDED, 3 TOO_LARGE,
               4 SHUTDOWN, 5 BAD_REQUEST
    hello:     a request frame with req_id == (1<<64)-1 is a model
               declaration, not a request: rows == 0 and the payload is
               nnz utf-8 bytes naming the model_id (see pack_hello) —
               a replica serving a different model answers BAD_REQUEST
               and drops the connection

``trace_id``/``parent_span`` carry the client's ``telemetry.trace``
context (0 = untraced): a traced request grows a server-side span that
parents the engine's forward span, so client→server→engine share one
trace_id in the Perfetto export (see `docs/observability.md`).

Overload shows up as a **response**, not a dropped connection: clients
need to distinguish "back off and retry" from "server died".

Hot reload: :meth:`PredictionServer.reload_from_checkpoint` swaps weights
atomically mid-stream, and :meth:`watch_checkpoints` polls a
`utils.checkpoint` directory and reloads whenever the trainer publishes a
new step — the serving half of the train→serve loop.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from typing import Dict, Optional

import numpy as np

from ..telemetry import anomaly as telanomaly
from ..transport.frames import send_all
from ..transport.listener import Listener, serve_connection
from ..telemetry import flight as telflight
from ..telemetry import sampling as telsampling
from ..telemetry import trace as teltrace
from ..telemetry.exposition import TelemetryServer
from ..telemetry.wide_events import wide_event
from ..utils.faults import FaultInjected, fault_point
from ..utils.logging import DMLCError, log_info, log_warning
from ..utils.metrics import metrics
from ..utils.parameter import get_env
from .batcher import DeadlineExceeded, MicroBatcher, Overloaded, Shutdown
from .engine import InferenceEngine, RequestTooLarge

__all__ = ["PredictionServer", "REQ_HEADER", "RSP_HEADER", "STATUS_OK",
           "STATUS_OVERLOADED", "STATUS_DEADLINE", "STATUS_TOO_LARGE",
           "STATUS_SHUTDOWN", "STATUS_BAD_REQUEST", "STATUS_NAMES",
           "HELLO_REQ_ID", "pack_hello"]

REQ_HEADER = struct.Struct("<QQQII")    # req_id, trace_id, parent_span,
                                        # rows, nnz (trace ids 0 = untraced)
RSP_HEADER = struct.Struct("<QBI")      # req_id, status, n

#: reserved req_id announcing a HELLO preamble instead of a request: the
#: header's ``nnz`` field counts the utf-8 model_id payload that follows
#: (rows/trace fields are 0).  A server bound to a different model answers
#: BAD_REQUEST and drops the connection, so a misrouted client fails on
#: connect instead of scoring against the wrong checkpoint.  Real req_ids
#: are small counters; (1<<64)-1 can never collide.
HELLO_REQ_ID = (1 << 64) - 1
_MAX_MODEL_ID = 4096


def pack_hello(model_id: str) -> bytes:
    """The model-declaration preamble frame (sent once per connection,
    before the first request)."""
    blob = model_id.encode("utf-8")[:_MAX_MODEL_ID]
    return REQ_HEADER.pack(HELLO_REQ_ID, 0, 0, 0, len(blob)) + blob

STATUS_OK = 0
STATUS_OVERLOADED = 1
STATUS_DEADLINE = 2
STATUS_TOO_LARGE = 3
STATUS_SHUTDOWN = 4
STATUS_BAD_REQUEST = 5
STATUS_NAMES = {0: "OK", 1: "OVERLOADED", 2: "DEADLINE_EXCEEDED",
                3: "TOO_LARGE", 4: "SHUTDOWN", 5: "BAD_REQUEST"}

#: hard parse-time sanity caps — a corrupt header must not allocate GBs
_MAX_ROWS = 1 << 20
_MAX_NNZ = 1 << 26


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            return None
        got += r
    return bytes(buf)


def _status_of(exc: BaseException) -> int:
    if isinstance(exc, Overloaded):
        return STATUS_OVERLOADED
    if isinstance(exc, DeadlineExceeded):
        return STATUS_DEADLINE
    if isinstance(exc, RequestTooLarge):
        return STATUS_TOO_LARGE
    if isinstance(exc, Shutdown):
        return STATUS_SHUTDOWN
    return STATUS_BAD_REQUEST


class PredictionServer:
    """Accept loop + one reader thread per connection; responses are
    written from batcher completion callbacks under a per-connection
    write lock (pipelined requests complete out of order)."""

    def __init__(self, engine: InferenceEngine, *,
                 host: str = "127.0.0.1", port: int = 0,
                 max_delay_s: float = 0.002, max_queue: int = 256,
                 default_deadline_s: float = 1.0,
                 warmup: bool = True, backlog: int = 64,
                 metrics_port: Optional[int] = None,
                 model_id: Optional[str] = None) -> None:
        self.engine = engine
        # fleet identity: which checkpoint lineage this replica serves.
        # "default" keeps single-replica deployments hello-free.
        self.model_id = model_id or "default"
        if warmup:
            engine.warmup_all()
        self.batcher = MicroBatcher(
            engine, max_delay_s=max_delay_s, max_queue=max_queue,
            default_deadline_s=default_deadline_s)
        self._listener = Listener(host, port, backlog=backlog)
        self._srv = self._listener.sock     # compat alias
        self.host, self.port = self._listener.host, self._listener.port
        self._conns: Dict[int, socket.socket] = {}
        self._conn_lock = threading.Lock()
        self._next_conn = 0
        self._stopping = False
        self._accept_thread: Optional[threading.Thread] = None
        self._watcher: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()
        self._m_conns = metrics.gauge("serving.server.connections")
        self._inflight = 0             # submitted, not yet answered
        self._inflight_lock = threading.Lock()
        self._m_inflight = metrics.gauge("serving.server.inflight")
        # queue-depth fraction above which health degrades before the hard
        # admission limit kicks in — load balancers drain "degraded"
        # replicas early instead of discovering "overloaded" via sheds
        self._degraded_ratio = float(
            get_env("DMLC_SERVING_DEGRADED_RATIO", 0.75))
        # telemetry exporter (/metrics /healthz /spans): explicit
        # metrics_port kwarg, else DMLC_METRICS_PORT (0 = ephemeral,
        # unset = disabled); /healthz reflects the live health property
        if metrics_port is None:
            p = get_env("DMLC_METRICS_PORT", -1)
            metrics_port = p if p >= 0 else None
        self.telemetry: Optional[TelemetryServer] = None
        if metrics_port is not None:
            # the full health DOC (status + queue fraction + inflight),
            # not just the status word — the router weights replicas off
            # this body without needing a second endpoint
            self.telemetry = TelemetryServer(
                port=int(metrics_port), health_fn=self.health_doc)
        # fleet membership: DMLC_ROUTER_REGISTRY=host:port opts this
        # replica into a ReplicaRegistry (registration + heartbeats via
        # an in-process ReplicaAgent; lazily imported — single-replica
        # deployments never load the fleet package)
        self._agent = None
        reg = str(get_env("DMLC_ROUTER_REGISTRY", ""))
        if reg:
            from .fleet.registry import ReplicaAgent
            h, _, p = reg.rpartition(":")
            self._agent = ReplicaAgent(self, (h, int(p)),
                                       model_id=self.model_id)
        # observability companions (each an exact no-op when its env is
        # unset): flight recorder arms on DMLC_FLIGHT_DIR; the SLO
        # monitor compiles DMLC_SLO_SPEC and starts on server start
        telflight.maybe_arm_from_env()
        telsampling.maybe_install_from_env()
        self.slo_monitor: Optional[telanomaly.SloMonitor] = \
            telanomaly.maybe_monitor_from_env(autostart=False)

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "PredictionServer":
        self._accept_thread = self._listener.spawn(
            self._on_conn, name="serving-accept",
            stopping=lambda: self._stopping)
        if self.telemetry is not None:
            self.telemetry.start()
        if self.slo_monitor is not None:
            self.slo_monitor.start()
        if self._agent is not None:
            self._agent.start()
        log_info("serving: listening on %s:%d (%d buckets, queue=%d)",
                 self.host, self.port, len(self.engine.ladder),
                 self.batcher.max_queue)
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful shutdown: stop accepting, drain the batcher (in-flight
        requests get their answers), then drop connections."""
        self._stopping = True
        self._watch_stop.set()
        if self._agent is not None:
            self._agent.stop()     # deregister before the port vanishes
        if self.slo_monitor is not None:
            self.slo_monitor.stop()
        if self.telemetry is not None:
            self.telemetry.stop()
        # Listener.close() is shutdown()-before-close(): the accept
        # thread blocked inside accept() holds a kernel reference to the
        # listening socket, so a bare close() would leave the port
        # ACCEPTING — a reconnecting client would land on this half-dead
        # server and get SHUTDOWN answers instead of a refused dial it
        # can retry against the restarted replica
        self._listener.close()
        self.batcher.close(drain=drain, timeout=timeout)
        with self._conn_lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self._watcher is not None:
            self._watcher.join(timeout=5.0)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def serve_forever(self, window_s: float = 5.0,
                      max_windows: Optional[int] = None) -> int:
        """Block until :meth:`stop` (or ``max_windows`` elapses), driving
        the **ambient serving autotuner** when ``DMLC_AUTOTUNE`` opts in.

        Each window is one autotune epoch over the live batcher knobs
        (:func:`~..pipeline.autotune.serving_knob_space` →
        ``MicroBatcher.apply_knobs``): the objective is windowed
        QPS / (1 + p99 latency) — higher is better, so the controller
        climbs toward throughput but a cut trigger that buys QPS by
        letting requests sit is charged for the latency it costs.  A
        window with zero traffic (or one cut short by shutdown) is
        aborted, not judged — idling must never steer the knobs.

        With the wiring off (``DMLC_AUTOTUNE`` unset or ``0``) this is
        exactly the pre-autotune foreground loop: sleep until stopped,
        touch nothing.  Returns the number of windows run.
        """
        from ..pipeline.autotune import maybe_autotuner, serving_knob_space
        from ..pipeline.fingerprint import autotune_key
        tuner = maybe_autotuner(lambda: serving_knob_space(self.batcher),
                                key=autotune_key(None, platform="serving"),
                                gate="auto")
        m_reqs = metrics.throughput("serving.batcher.requests")
        m_lat = metrics.histogram("serving.latency_s")
        windows = 0
        while (not self._stopping
               and (max_windows is None or windows < max_windows)):
            if tuner is None:
                # no-tuner path: plain interruptible sleep, no side effects
                t0 = time.monotonic()
                while (not self._stopping
                       and time.monotonic() - t0 < window_s):
                    time.sleep(min(0.05, window_s))
                windows += 1
                continue
            tuner.begin_epoch()         # pushes this window's knob values
            t0 = time.monotonic()
            base = m_reqs.total
            while not self._stopping and time.monotonic() - t0 < window_s:
                time.sleep(min(0.05, window_s))
            dt = max(1e-9, time.monotonic() - t0)
            delta = m_reqs.total - base
            if delta <= 0 or self._stopping:
                tuner.abort_epoch()
            else:
                p99 = float(m_lat.snapshot()["p99"])
                tuner.end_epoch((delta / dt) / (1.0 + p99))
            windows += 1
        if tuner is not None:
            tuner.abort_epoch()         # drop any half-evaluated mutation
        return windows

    # -- health ----------------------------------------------------------
    @property
    def health(self) -> str:
        """``ok`` | ``degraded`` | ``overloaded`` from batcher queue depth
        and live SLO breaches.

        ``degraded`` starts at ``DMLC_SERVING_DEGRADED_RATIO`` (default
        0.75) of ``max_queue``; ``overloaded`` means the admission limit is
        reached and new submits are being shed.  A currently-breached
        ``DMLC_SLO_SPEC`` rule (``slo.active_breaches`` > 0) degrades an
        otherwise-ok replica — a load balancer should drain a replica that
        is violating its objectives even when its queue looks healthy.
        Also exported as the gauge ``serving.server.health``
        (0 ok / 1 degraded / 2 overloaded)."""
        depth = self.batcher.queue_depth
        cap = max(1, self.batcher.max_queue)
        if depth >= cap:
            state, level = "overloaded", 2
        elif depth >= self._degraded_ratio * cap:
            state, level = "degraded", 1
        else:
            state, level = "ok", 0
        if level == 0 and metrics.gauge("slo.active_breaches").value > 0:
            state, level = "degraded", 1
        metrics.gauge("serving.server.health").set(level)
        return state

    def health_doc(self) -> Dict[str, object]:
        """The ``/healthz`` JSON body: the :attr:`health` status word
        (bit-compatible — ``status`` keeps its exact values and HTTP
        code mapping) plus the live load facts a balancer weights on:
        queue-depth fraction of ``max_queue`` and the in-flight count."""
        depth = self.batcher.queue_depth
        cap = max(1, self.batcher.max_queue)
        with self._inflight_lock:
            inflight = self._inflight
        return {"status": self.health, "model_id": self.model_id,
                "queue_depth": depth,
                "queue_fraction": round(depth / cap, 4),
                "inflight": inflight}

    # -- hot reload ------------------------------------------------------
    def reload_from_checkpoint(self, directory: str,
                               step: Optional[int] = None) -> int:
        return self.engine.reload_from_checkpoint(directory, step)

    def watch_checkpoints(self, directory: str,
                          interval_s: float = 10.0) -> None:
        """Poll ``directory``'s manifest; hot-reload whenever the trainer
        publishes a newer step.  A failed poll/reload logs and keeps
        serving the current weights — the watcher must never take down a
        healthy replica over a half-published checkpoint."""
        from ..utils.checkpoint import CheckpointManager
        mgr = CheckpointManager(directory)
        state = {"step": None}

        def poll_once() -> None:
            latest = mgr.latest_step
            if latest is not None and latest != state["step"]:
                self.reload_from_checkpoint(directory, latest)
                state["step"] = latest

        try:
            poll_once()                 # load an existing checkpoint NOW —
        except DMLCError as e:          # serve the current weights if none
            log_warning("serving: initial checkpoint load failed: %s", e)

        def run() -> None:
            while not self._watch_stop.wait(interval_s):
                try:
                    poll_once()
                except DMLCError as e:
                    log_warning("serving: checkpoint watch failed "
                                "(%s) — keeping current weights", e)

        self._watcher = threading.Thread(target=run, name="serving-watch",
                                         daemon=True)
        self._watcher.start()

    # -- connection handling --------------------------------------------
    def _on_conn(self, conn: socket.socket, _addr) -> None:
        with self._conn_lock:
            cid = self._next_conn
            self._next_conn += 1
            self._conns[cid] = conn
            self._m_conns.set(len(self._conns))
        serve_connection(self._serve_conn, cid, conn,
                         name=f"serving-conn-{cid}")

    def _drop_conn(self, cid: int, conn: socket.socket) -> None:
        with self._conn_lock:
            self._conns.pop(cid, None)
            self._m_conns.set(len(self._conns))
        try:
            conn.close()
        except OSError:
            pass

    def _serve_conn(self, cid: int, conn: socket.socket) -> None:
        wlock = threading.Lock()

        def respond(req_id: int, status: int, payload: bytes) -> None:
            # n counts SCORES for OK (payload is n×f32), BYTES otherwise
            n = len(payload) // 4 if status == STATUS_OK else len(payload)
            try:
                with wlock:
                    send_all(conn, RSP_HEADER.pack(req_id, status, n)
                             + payload)
            except OSError:
                pass                   # client gone; reader will notice

        def on_done(req_id: int, fut, span: Optional[teltrace.Span],
                    rows: int, nnz: int, t0: float) -> None:
            with self._inflight_lock:
                self._inflight -= 1
                self._m_inflight.set(self._inflight)
            exc = fut.exception()
            if exc is None:
                scores = np.ascontiguousarray(fut.result(),
                                              dtype=np.float32)
                outcome = "OK"
                if span is not None:
                    span.end(status="OK")
                respond(req_id, STATUS_OK, scores.tobytes())
            else:
                status = _status_of(exc)
                if status == STATUS_OVERLOADED:
                    metrics.counter("serving.server.shed").add(1)
                outcome = STATUS_NAMES.get(status, str(status))
                if span is not None:
                    span.end(status=outcome)
                respond(req_id, status,
                        str(exc).encode("utf-8", "replace"))
            # the canonical log line: one wide event per served request,
            # emitted AFTER span.end so a server-rooted trace already has
            # its tail-sampling verdict.  Batch/queue facts ride in on
            # the future (see MicroBatcher._run).
            wide_event("serving.request", model=self.model_id, conn=cid,
                       req_id=req_id, rows=rows, nnz=nnz,
                       dur_ms=round((time.monotonic() - t0) * 1e3, 3),
                       outcome=outcome,
                       trace_id=(teltrace.format_id(span.trace_id)
                                 if span is not None else None),
                       debug=(bool(span.trace_id & telsampling.DEBUG_BIT)
                              if span is not None else None),
                       **getattr(fut, "wide", {}))

        try:
            while True:
                head = _recv_exact(conn, REQ_HEADER.size)
                if head is None:
                    return
                req_id, trace_id, parent_span, rows, nnz = \
                    REQ_HEADER.unpack(head)
                if req_id == HELLO_REQ_ID:
                    # model-declaration preamble (see pack_hello): checked
                    # before the rows==0 guard — its header carries rows=0
                    # and the payload is nnz raw utf-8 bytes, not CSR
                    if nnz > _MAX_MODEL_ID:
                        respond(req_id, STATUS_BAD_REQUEST,
                                b"oversized hello")
                        return
                    blob = _recv_exact(conn, nnz)
                    if blob is None:
                        return
                    wanted = blob.decode("utf-8", "replace") or "default"
                    if wanted != self.model_id:
                        respond(req_id, STATUS_BAD_REQUEST,
                                f"model {wanted!r} not served here "
                                f"(this is {self.model_id!r})".encode())
                        return         # wrong fleet — drop the conn
                    continue
                # traced requests (non-zero trace_id in the header) get a
                # server span parented on the client's wire context; the
                # span object travels with the request and is ended from
                # the completion callback — requests finish out of order
                span = None
                if trace_id:
                    span = teltrace.start_span(
                        "serving.server.request",
                        parent=teltrace.TraceContext(trace_id, parent_span),
                        req_id=req_id, rows=rows, nnz=nnz, conn=cid)
                if rows == 0 or rows > _MAX_ROWS or nnz > _MAX_NNZ:
                    if span is not None:
                        span.end(status="BAD_REQUEST")
                    respond(req_id, STATUS_BAD_REQUEST,
                            f"bad header rows={rows} nnz={nnz}".encode())
                    return             # framing is broken; drop the conn
                payload = _recv_exact(conn, 4 * (rows + 1) + 8 * nnz)
                if payload is None:
                    if span is not None:
                        span.end(status="DISCONNECT")
                    return
                row_ptr = np.frombuffer(payload, np.int32, rows + 1, 0)
                ids = np.frombuffer(payload, np.int32, nnz,
                                    4 * (rows + 1))
                vals = np.frombuffer(payload, np.float32, nnz,
                                     4 * (rows + 1) + 4 * nnz)
                try:
                    # chaos harness hook: an injected error here sheds the
                    # request exactly as real admission control would —
                    # a deterministic OVERLOADED burst for client tests
                    fault_point("serving.server.admit")
                except FaultInjected as e:
                    metrics.counter("serving.server.shed").add(1)
                    if span is not None:
                        span.end(status="OVERLOADED", injected=True)
                    wide_event("serving.request", model=self.model_id,
                               conn=cid, req_id=req_id, rows=rows,
                               nnz=nnz, outcome="OVERLOADED",
                               trace_id=(teltrace.format_id(span.trace_id)
                                         if span is not None else None))
                    respond(req_id, STATUS_OVERLOADED, str(e).encode())
                    continue
                with self._inflight_lock:
                    self._inflight += 1
                    self._m_inflight.set(self._inflight)
                t_req = time.monotonic()
                try:
                    fut = self.batcher.submit(ids, vals,
                                              row_ptr.astype(np.int64),
                                              trace_ctx=(span.context
                                                         if span else None))
                except BaseException:
                    with self._inflight_lock:
                        self._inflight -= 1
                        self._m_inflight.set(self._inflight)
                    raise
                fut.add_done_callback(
                    lambda f, rid=req_id, sp=span, r=rows, z=nnz,
                    t0=t_req: on_done(rid, f, sp, r, z, t0))
        except OSError as e:
            log_info("serving: connection %d ended: %r", cid, e)
        finally:
            self._drop_conn(cid, conn)


def serve_main(argv=None) -> int:
    """CLI: ``python -m dmlc_core_tpu.serving.server ckpt_dir=DIR
    model=fm features=N [dim=N] [port=N] [watch_s=SEC] ...`` — build the
    zoo model, load the latest checkpoint, serve until interrupted."""
    import sys
    args = dict(a.split("=", 1) for a in (sys.argv[1:] if argv is None
                                          else argv))
    if not args.get("ckpt_dir") or not args.get("features"):
        print("usage: serving.server ckpt_dir=DIR features=N [model=fm] "
              "[dim=16] [task=binary] [port=0] [host=0.0.0.0] "
              "[watch_s=10] [max_delay_ms=2] [max_queue=256] "
              "[model_id=default] [ragged=0|1]   (env "
              "DMLC_SERVE_RAGGED=1 is the default for ragged=; env "
              "DMLC_ROUTER_REGISTRY=H:P joins a replica fleet)",
              file=sys.stderr)
        return 2
    import os

    import jax

    from ..models.cli import MODEL_REGISTRY, TrainParams
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    p = TrainParams()
    p.init({"data": "unused", "model": args.get("model", "fm"),
            "features": args["features"], "dim": args.get("dim", "16"),
            "task": args.get("task", "binary")})
    model = MODEL_REGISTRY[p.model](p)
    params = model.init(jax.random.PRNGKey(0))
    # ragged capacity engine: CLI key wins, env var is the fleet-wide
    # default (flip a deployment without touching every launch line)
    ragged = args.get("ragged",
                      get_env("DMLC_SERVE_RAGGED", "0"))
    engine = InferenceEngine(
        model, params,
        postprocess="sigmoid" if p.task == "binary" else "none",
        ragged=str(ragged).lower() in ("1", "true", "yes", "on"))
    srv = PredictionServer(
        engine, host=args.get("host", "0.0.0.0"),
        port=int(args.get("port", "0")),
        max_delay_s=float(args.get("max_delay_ms", "2")) / 1e3,
        max_queue=int(args.get("max_queue", "256")),
        model_id=args.get("model_id"))
    srv.watch_checkpoints(args["ckpt_dir"],
                          interval_s=float(args.get("watch_s", "10")))
    srv.start()
    print(f"serving on {srv.host}:{srv.port}", flush=True)
    try:
        # foreground loop doubles as the ambient autotuner driver when
        # DMLC_AUTOTUNE opts in; otherwise it only sleeps
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(serve_main())
