"""``serve_open``: online scoring under an open loop.  ``InferenceEngine`` +
``PredictionServer`` built as ``serving.server.serve_main`` builds them
(in-process, on threads), requests over TCP through ``PredictClient.submit``
from one sender thread that keeps to a schedule whether or not earlier
requests have finished.

The schedule (``schedule``) is a fixed amount of work, in another order for
every seed: request sizes are the quantile grid of a log-uniform law over
``rows_min..rows_max``, gaps the quantile grid of an exponential law at
``rate_per_s``, each shuffled by the seed.  A request's latency runs from
when it was *due*; how late the sender ran is reported beside it.  A request
that is shed, fails or is not answered a minute past the close counts in
``failed``.

Once the window has closed a sample of the answered requests, drawn from
the seed with the longest in it, is held row by row against the plain
reference.
"""

from __future__ import annotations

import time

import numpy as np

import checks
import reference
import textfeed


def schedule(rate_per_s: float, seconds: float, rows_min: int, rows_max: int,
             seed: int):
    """(due times [n] from 0, rows per request [n]): ``n = rate * seconds``
    requests whose gaps and sizes are the same multiset for every seed."""
    n = max(1, int(round(rate_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s            # exponential quantiles
    gaps *= seconds / gaps.sum()                  # last arrival at the close
    rows = np.floor(rows_min * (rows_max / rows_min + 1.0 / rows_min) ** q
                    ).astype(np.int64)            # log-uniform quantiles
    rows = np.clip(rows, rows_min, rows_max)
    rng = np.random.default_rng([int(seed), 0x0BE7])
    rng.shuffle(gaps)
    rng.shuffle(rows)
    due = np.cumsum(gaps) - gaps[0]
    return due, rows


class Cell:
    host_labels = ("bench.send", "bench.wait_due", "bench.drain")

    def __init__(self, ctx):
        self.ctx = ctx
        self.feed = None
        self.srv = None
        self.clients = []

    def setup(self) -> None:
        from dmlc_core_tpu.serving import (InferenceEngine, PredictClient,
                                           PredictionServer)
        ctx = self.ctx
        self.feed = feed = textfeed.TextFed(ctx, write_file=False)
        self.params = feed.make_weights()
        # as serve_main builds them: its own defaults, no ladder, no knobs
        self.engine = InferenceEngine(
            feed.model, self.params,
            postprocess="sigmoid" if feed.p.task == "binary" else "none")
        t0 = time.perf_counter()
        self.engine.warmup_all()
        ctx.say(f"[setup] {self.engine.compile_count} bucket programs warm "
                f"in {time.perf_counter() - t0:.1f}s")
        self.srv = PredictionServer(self.engine, host="127.0.0.1", port=0)
        self.srv.start()
        self.clients = [PredictClient(self.srv.host, self.srv.port)
                        for _ in range(int(ctx.traffic["connections"]))]
        # the corpus as one CSR, folded into the feature space: request r
        # takes the next rows[r] rows, wrapping round
        c = feed.corpus
        self.ids, self.vals, self.row_ptr, _ = c.rows_csr(0, c.rows,
                                                          feed.features)
        # one request of every size class through the whole path
        for rows in (1, int(ctx.traffic["rows_max"])):
            self.clients[0].predict(*self._request(0, rows), timeout=60.0)

    def _request(self, start: int, rows: int):
        a, b = self.row_ptr[start], self.row_ptr[start + rows]
        return (self.ids[a:b], self.vals[a:b],
                (self.row_ptr[start:start + rows + 1] - a).astype(np.int32))

    def window(self, seconds: float) -> None:
        import jax.profiler as prof
        from dmlc_core_tpu.utils.metrics import metrics
        ctx, t = self.ctx, self.ctx.traffic
        due, rows = schedule(float(t["rate_per_s"]), seconds,
                             int(t["rows_min"]), int(t["rows_max"]),
                             ctx.seed)
        n = len(due)
        starts = np.concatenate(([0], np.cumsum(rows)))[:-1] % (
            self.feed.corpus.rows - int(t["rows_max"]))
        reqs = [self._request(int(s), int(r)) for s, r in zip(starts, rows)]
        done = np.full(n, np.nan)
        sent = np.empty(n)
        futures = [None] * n
        fwd = metrics.stage("serving.engine.forward")
        fwd0 = (fwd.total_sec, fwd.count)

        def on_done(i):
            def cb(_fut):
                done[i] = time.perf_counter()
            return cb

        t0 = time.perf_counter()
        for i in range(n):
            with prof.TraceAnnotation("bench.wait_due"):
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            with prof.TraceAnnotation("bench.send"):
                sent[i] = time.perf_counter()
                fut = self.clients[i % len(self.clients)].submit(*reqs[i])
                fut.add_done_callback(on_done(i))
                futures[i] = fut
        with prof.TraceAnnotation("bench.drain"):
            close = t0 + max(seconds, due[-1])
            # late is late, not wrong: wait a minute past the close
            results, failed = [None] * n, 0
            for i, fut in enumerate(futures):
                left = close + float(t["grace_s"]) - time.perf_counter()
                try:
                    results[i] = fut.result(timeout=max(left, 0.0))
                except Exception as e:   # shed, failed, or never answered
                    failed += 1
                    if failed <= 3:
                        ctx.say(f"[window] request {i} ({rows[i]} rows) "
                                f"failed: {type(e).__name__}: {e}")
        wall = time.perf_counter() - t0
        ok = np.array([r is not None for r in results])
        lat_ms = 1e3 * (done - (t0 + due))[ok]
        lag_ms = 1e3 * (sent - (t0 + due))
        self.reqs, self.results, self.rows, self.starts = (reqs, results,
                                                           rows, starts)
        v = ctx.values
        v["attempted"] = n
        v["failed"] = failed
        v["window_wall_s"] = wall
        v["steps"] = max(1, fwd.count - fwd0[1])
        if ok.any():
            v["p50_ms"] = float(np.percentile(lat_ms, 50))
            v["p99_ms"] = float(np.percentile(lat_ms, 99))
            v["p90_ms"] = float(np.percentile(lat_ms, 90))
        v["generator_lag_p99_ms"] = float(np.percentile(lag_ms, 99))
        if fwd.count > fwd0[1]:
            v["engine_forward_mean_ms"] = 1e3 * (fwd.total_sec - fwd0[0]) / (
                fwd.count - fwd0[1])
        v["rows_per_s"] = float(rows[ok].sum()) / wall
        # what the engine's calls needed, had the answered rows been spread
        # evenly over them
        nnz = sum(len(reqs[i][0]) for i in np.flatnonzero(ok))
        v["needed_work"] = ("dcn_forward", (
            float(rows[ok].sum()) / v["steps"], nnz / v["steps"],
            int(self.feed.p.dim), int(self.feed.p.layers)))
        ctx.say(f"[window] {n} requests ({int(rows.sum())} rows) offered "
                f"at {t['rate_per_s']}/s over {seconds:.0f}s; {failed} "
                f"failed; p50 {v.get('p50_ms', float('nan')):.3f} ms p90 "
                f"{v.get('p90_ms', float('nan')):.3f} ms p99 "
                f"{v.get('p99_ms', float('nan')):.3f} ms; sender lag p99 "
                f"{v['generator_lag_p99_ms']:.3f} ms; "
                f"{v['steps']} engine calls, mean forward "
                f"{v.get('engine_forward_mean_ms', float('nan')):.3f} ms; "
                f"drained {wall - seconds:.3f}s past the close")

    def verify(self) -> list:
        ctx, t = self.ctx, self.ctx.traffic
        self._stop()
        answered = [i for i, r in enumerate(self.results) if r is not None]
        limits = t["limits"]
        if not answered:
            return [checks.check(name, float("inf"), limits[name])
                    for name in limits]
        rng = np.random.default_rng([ctx.seed, 0x5C0])
        k = min(int(t["sampled_requests"]), len(answered))
        pick = set(rng.choice(answered, size=k, replace=False).tolist())
        pick.add(max(answered, key=lambda i: self.rows[i]))  # the longest
        t0 = time.perf_counter()
        gap, rms, n = served_gap(ctx, self.feed, self.params, sorted(pick),
                                 self.starts, self.rows, self.results)
        ctx.say(f"[verify] {n} served rows of {len(pick)} requests against "
                f"the reference in {time.perf_counter() - t0:.1f}s")
        short = sum(len(self.results[i]) != self.rows[i] for i in answered)
        got = {"score_gap": gap, "score_rms_gap": rms,
               "rows_missing": float(short)}
        return [checks.check(name, got[name], limits[name])
                for name in limits]

    def _stop(self) -> None:
        for c in self.clients:
            c.close()
        self.clients = []
        if self.srv is not None:
            self.srv.stop()
            self.srv = None

    def close(self) -> None:
        self._stop()
        if self.feed is not None:
            self.feed.close()


def served_gap(ctx, feed, params, picked, starts, rows, results, dtype=None):
    """Widest and root-mean-square ``|served - reference|`` over every row
    of the picked requests, the reference run once over all of them in
    blocks, and the number of rows compared."""
    spans = [(int(starts[i]), int(rows[i])) for i in picked]
    ids, vals = [], []
    for s, r in spans:
        a, b, _ = feed.corpus.rows_padded(s, s + r, feed.features)
        ids.append(a)
        vals.append(b)
    ref = reference.scores(ctx.cfg["reference"], params,
                           np.concatenate(ids), np.concatenate(vals), dtype)
    served = np.concatenate([np.asarray(results[i], np.float32)
                             for i in picked])
    if served.shape != ref.shape:
        return float("inf"), float("inf"), len(ref)
    widest, squares = checks.gap_sums(served, ref)
    return widest, (squares / len(ref)) ** 0.5, len(ref)
