"""``score_text``: offline batch scoring.  Text on disk → the program's
parser → ``DeviceLoader`` → jitted ``model.forward`` → sigmoid → scores read
back per batch with a one-batch lag, as ``mode=predict`` of the train CLI
does — without its per-row Python write.  Epoch after epoch until the clock
ends; a row counts once its score is on the host.

The comparison takes a sample of batches, drawn from the seed with the
epoch's last batch in it, keeps what the window read back for them the
first and the last time each came by, and holds every row of them against
the plain reference once the window has closed.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

import checks
import reference
import textfeed


class Cell:
    host_labels = ("bench.read_scores", "bench.next_batch", "bench.dispatch")

    def __init__(self, ctx):
        self.ctx = ctx
        self.feed = None

    def setup(self) -> None:
        import jax
        ctx = self.ctx
        self.feed = feed = textfeed.TextFed(ctx)
        self.task = feed.p.task
        self.fwd = jax.jit(feed.model.forward)
        self.params = feed.make_weights()
        feed.open_loader()
        per_epoch = feed.batches_per_epoch
        rng = np.random.default_rng([ctx.seed, 0x5C0])
        n = min(int(ctx.traffic["sampled_batches"]), per_epoch)
        pick = set(rng.choice(per_epoch - 1, size=n - 1, replace=False)
                   .tolist()) if n > 1 else set()
        self.sample = sorted(pick | {per_epoch - 1})
        self.first: dict = {}
        self.last: dict = {}
        t0 = time.perf_counter()
        for _ in range(int(ctx.traffic["warm_batches"])):
            np.asarray(self._score(self.feed.next_batch())[1])
        ctx.say(f"[setup] warm-up batches in {time.perf_counter() - t0:.1f}s;"
                f" sampled batches {self.sample}")

    def _score(self, item):
        import jax
        i, batch = item
        scores = self.fwd(self.params, batch)
        if self.task == "binary":
            scores = jax.nn.sigmoid(scores)
        return i, scores

    def window(self, seconds: float) -> None:
        import jax.profiler as prof
        ctx = self.ctx
        stages0 = textfeed.stage_seconds()
        rows = bad = batches = 0
        held = None
        stamps: list = []       # (clock, rows) of every batch read
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def read(item):
            nonlocal rows, bad, batches
            i, scores = item
            with prof.TraceAnnotation("bench.read_scores"):
                host = np.asarray(scores)
            lo = i * self.feed.rows
            n = min(self.feed.rows, self.feed.corpus.rows - lo)
            rows += n
            batches += 1
            stamps.append((time.perf_counter(), n))
            bad += int(n - np.isfinite(host[:n]).sum())
            if i in self.sample:
                self.first.setdefault(i, host[:n])
                self.last[i] = host[:n]

        while True:
            with prof.TraceAnnotation("bench.next_batch"):
                batch = self.feed.next_batch()
            with prof.TraceAnnotation("bench.dispatch"):
                item = self._score(batch)
            if held is not None:
                read(held)
            held = item
            if time.perf_counter() >= deadline:
                break
        read(held)
        wall = time.perf_counter() - t0
        v = ctx.values
        v["attempted"] = rows
        v["failed"] = bad
        v["rows_per_s"] = rows / wall
        v["window_wall_s"] = wall
        v["steps"] = batches
        v["stages"] = textfeed.stage_delta(stages0)
        nnz = self.feed.corpus.nnz / self.feed.batches_per_epoch
        v["needed_work"] = ("dcn_forward", (self.feed.rows, nnz,
                                            int(self.feed.p.dim),
                                            int(self.feed.p.layers)))
        ctx.say(f"[window] {batches} batches, {rows} rows in {wall:.3f}s = "
                f"{rows / wall:.0f} rows/s; {bad} scores not finite; stages "
                f"{ {k: round(s, 3) for k, s in v['stages'].items()} }")
        # not a metric: whether a slow run is slow throughout or in episodes
        per, tail = per_second(stamps, t0)
        v["per_second_rows"], v["tail_rows"] = per, tail
        if per:
            mid = statistics.median(per)
            ctx.say(f"[window] rows in each whole second: {per}; slowest "
                    f"{min(per) / mid:.4f}, fastest {max(per) / mid:.4f} of "
                    f"the median second ({mid:.0f})")

    def verify(self) -> list:
        ctx, feed = self.ctx, self.feed
        feed.close()
        t0 = time.perf_counter()
        gap, rms, n = score_gap(ctx, feed, self.params, self.first,
                                self.last)
        ctx.say(f"[verify] {n} served rows of batches "
                f"{sorted(self.first)} against the reference in "
                f"{time.perf_counter() - t0:.1f}s")
        limits = ctx.traffic["limits"]
        # every sampled batch that came by in the window has to be there
        missing = float(len(self.first) == 0)
        got = {"score_gap": gap, "score_rms_gap": rms,
               "sample_missing": missing}
        return [checks.check(k, got[k], limits[k]) for k in limits]

    def close(self) -> None:
        if self.feed is not None:
            self.feed.close()


def per_second(stamps: list, t0: float) -> tuple:
    """Rows read back in each whole second after ``t0``, and the rows of
    the part of a second the window ended in: together, every row."""
    if not stamps:
        return [], 0
    whole = int(stamps[-1][0] - t0)
    per = [0] * whole
    tail = 0
    for t, n in stamps:
        k = int(t - t0)
        if k < whole:
            per[k] += n
        else:
            tail += n
    return per, tail


def score_gap(ctx, feed, params, first: dict, last: dict, dtype=None):
    """Widest and root-mean-square ``|served - reference|`` over every row
    of the sampled batches (both readings of each), and the number of rows
    compared."""
    if not first:
        return float("inf"), float("inf"), 0
    worst, squares, n = 0.0, 0.0, 0
    for i in sorted(first):
        ids, vals, _ = feed.batch_truth(i)
        ref = reference.scores(ctx.cfg["reference"], params, ids, vals,
                               dtype)
        for served in (first[i], last[i]):
            widest, sq = checks.gap_sums(served, ref)
            worst = max(worst, widest)
            squares += sq
            n += len(ref)
    return worst, (squares / n) ** 0.5, n
