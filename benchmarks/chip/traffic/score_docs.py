"""``score_docs``: a document store scored by perplexity.  Token documents
on disk → the program's parser → ``DeviceLoader`` → the program's jitted
scorer (``models.cli._scorer``: scores and the device's counters of one
batch) → the scores read back with a one-batch lag, as ``mode=predict`` of
the train CLI does — without its per-row Python write.  Epoch after epoch
until the clock ends; a document counts once its score is on the host.

A forward takes most of a second, so a window sees each batch once: the
comparison keeps the window's first batch and its last (the device batch
itself and the scores read back for it), and once the window has closed
holds them against the plain reference (``reference_lm.py``), a layer at a
time:

(a) every sampled document's score; (b) the logits over the held vocabulary
at a sample of positions — each document's first and last token among them —
from the program's ``probe`` on the kept batch; (c) the experts the program
chose against the reference's, tokens whose ``k``-th and ``(k+1)``-th
``s + b`` lie within ``close_margin`` counted apart; (d) every sampled
batch's tokens and rows as the generator wrote them.
"""

from __future__ import annotations

import os
import time

import numpy as np

import checks
import corpus_docs
import reference_lm
import textfeed
import weights_lm


class Cell:
    host_labels = ("bench.read_scores", "bench.next_batch", "bench.dispatch")

    def __init__(self, ctx):
        self.ctx = ctx
        self.loader = None

    def setup(self) -> None:
        from dmlc_core_tpu import native
        from dmlc_core_tpu.models import cli
        ctx, cfg = self.ctx, self.ctx.cfg
        t0 = time.perf_counter()
        native.require()
        path = os.path.join(ctx.work, "docs.libsvm")
        self.corpus = corpus_docs.generate(path, cfg["corpus"],
                                           cfg["corpus_docs"], ctx.seed)
        ctx.say(f"[setup] native + corpus {self.corpus.rows} documents, "
                f"{self.corpus.nnz} tokens, {self.corpus.nbytes / 1e6:.0f} "
                f"MB in {time.perf_counter() - t0:.1f}s")
        args = {k: str(v) for k, v in cfg["program_args"].items()}
        args["arch"] = os.path.join(ctx.manifest.repo_root, args["arch"])
        self.p = p = cli.TrainParams()
        p.init(dict(args, data=f"file://{path}"))
        self.model = cli.MODEL_REGISTRY[p.model](p)
        self.rows = int(p.batch_rows)
        self.score, self.note = cli._scorer(self.model)
        t0 = time.perf_counter()
        import jax
        self.params = jax.block_until_ready(weights_lm.make(
            self.model.shapes(), ctx.seed, cfg["weights"], cfg["dtype"]))
        ctx.say(f"[setup] weights on the device in "
                f"{time.perf_counter() - t0:.1f}s")
        self.loader = cli._make_loader(p, p.data, "libsvm", False,
                                       emit="device")
        self.index = 0
        self.kept: list = []        # (index, device batch, scores read)
        t0 = time.perf_counter()
        for _ in range(int(ctx.traffic["warm_batches"])):
            _, _, scores, counters = self._dispatch(self._next())
            np.asarray(scores)
            self.note(counters)
        ctx.say(f"[setup] warm-up batches in {time.perf_counter() - t0:.1f}s")

    @property
    def batches_per_epoch(self) -> int:
        return -(-self.corpus.rows // self.rows)

    def _next(self):
        batch = self.loader.next_batch()
        if batch is None:
            self.loader.before_first()
            self.index = 0
            batch = self.loader.next_batch()
        i, self.index = self.index, self.index + 1
        return i, batch

    def _dispatch(self, item):
        i, batch = item
        scores, counters = self.score(self.params, batch)
        return i, batch, scores, counters

    def window(self, seconds: float) -> None:
        import jax.profiler as prof
        ctx = self.ctx
        stages0 = textfeed.stage_seconds()
        docs = bad = batches = 0
        held = None
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def read(item):
            nonlocal docs, bad, batches
            i, batch, scores, counters = item
            with prof.TraceAnnotation("bench.read_scores"):
                host = np.asarray(scores)
                self.note(counters)
            n = min(self.rows, self.corpus.rows - i * self.rows)
            docs += n
            batches += 1
            bad += int(n - np.isfinite(host[:n]).sum())
            # the window's first batch and, so far, its last
            del self.kept[1:]
            self.kept.append((i, batch, host[:n]))

        while True:
            with prof.TraceAnnotation("bench.next_batch"):
                batch = self._next()
            with prof.TraceAnnotation("bench.dispatch"):
                item = self._dispatch(batch)
            if held is not None:
                read(held)
            held = item
            if time.perf_counter() >= deadline:
                break
        read(held)
        wall = time.perf_counter() - t0
        v = ctx.values
        v["attempted"] = docs
        v["failed"] = bad
        v["rows_per_s"] = docs / wall
        v["window_wall_s"] = wall
        v["steps"] = batches
        v["stages"] = textfeed.stage_delta(stages0)
        v["needed_work"] = ("lm_forward",
                            self.corpus.lengths[:self.rows].tolist())
        ctx.say(f"[window] {batches} batches, {docs} documents in "
                f"{wall:.3f}s = {docs / wall:.2f} documents/s; {bad} scores "
                f"not finite; stages "
                f"{ {k: round(s, 3) for k, s in v['stages'].items()} }")

    def verify(self, control=None) -> list:
        ctx = self.ctx
        self.close()
        t0 = time.perf_counter()
        got = gaps(ctx, self.model, self.params, self.corpus, self.rows,
                   self.kept, control)
        ctx.say(f"[verify] batches {[k[0] for k in self.kept]} against the "
                f"reference in {time.perf_counter() - t0:.1f}s: "
                f"{ {k: float(f'{x:.4g}') for k, x in got.items()} }")
        limits = ctx.traffic["limits"]
        return [checks.check(k, got[k], limits[k]) for k in limits]

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()
            self.loader = None


def probe_positions(row_ptr, seed: int, extra: int) -> np.ndarray:
    """Each document's first token (the first after a boundary) and last,
    and ``extra`` more drawn from the seed."""
    rng = np.random.default_rng([seed, 0x9B0B])
    total = int(row_ptr[-1])
    edge = np.concatenate([row_ptr[:-1], row_ptr[1:] - 1])
    return np.unique(np.concatenate(
        [edge, rng.integers(0, total, extra)])).astype(np.int32)


def gaps(ctx, model, params, corpus, rows, kept, control=None) -> dict:
    """Every number the cell's ``limits`` name.  ``control`` puts the
    reference, with that fault planted, in the program's place."""
    import jax
    import jax.numpy as jnp
    spec = ctx.traffic
    out = {"rows_missing": float(not kept)}
    ref = reference_lm.Reference(ctx.cfg, pad_to=int(spec["reference_pad"]))
    stand_in = control and reference_lm.Reference(
        ctx.cfg, control, pad_to=int(spec["reference_pad"]))
    probe = None if stand_in else jax.jit(model.probe)
    worst = sq = n = 0.0
    l_worst = l_sq = l_n = 0.0
    wrong = {"clear": 0, "close": 0}
    seen = {"clear": 0, "close": 0}
    for i, batch, scores in kept:
        lo = i * rows
        ids, row_ptr = corpus.docs(lo, min(lo + rows, corpus.rows))
        total = int(row_ptr[-1])
        fed = np.asarray(batch["ids"])[:total]
        fed_ptr = np.asarray(batch["row_ptr"])[:len(row_ptr)]
        if not (np.array_equal(fed, ids)
                and np.array_equal(fed_ptr, row_ptr)):
            out["rows_missing"] += 1.0
            continue
        pos = probe_positions(row_ptr, ctx.seed + i,
                              int(spec["probe_positions"]))
        want = ref.run(params, ids, row_ptr, pos,
                       head_block=int(spec["reference_head_block"]))
        if stand_in:
            have = stand_in.run(params, ids, row_ptr, pos,
                                head_block=int(spec["reference_head_block"]))
            scores, logits, chosen = (have["scores"], have["logits"],
                                      have["chosen"])
        else:
            logits, chosen = probe(params, batch, jnp.asarray(pos))
            logits = np.asarray(logits)
            chosen = {k: np.asarray(c)[:total] for k, c in chosen.items()}
        widest, squares = checks.gap_sums(scores, want["scores"])
        worst, sq, n = max(worst, widest), sq + squares, n + len(scores)
        widest, squares = checks.gap_sums(logits, want["logits"])
        l_worst, l_sq = max(l_worst, widest), l_sq + squares
        l_n += want["logits"].size
        for name, theirs in want["chosen"].items():
            differs = (np.sort(chosen[name], -1)
                       != np.sort(theirs, -1)).any(-1)
            close = want["margin"][name] < float(spec["close_margin"])
            wrong["close"] += int(differs[close].sum())
            seen["close"] += int(close.sum())
            wrong["clear"] += int(differs[~close].sum())
            seen["clear"] += int((~close).sum())
    if not n:
        return dict(out, **{k: float("inf") for k in spec["limits"]
                            if k != "rows_missing"})
    out.update(
        score_gap=worst, score_rms_gap=(sq / n) ** 0.5,
        logit_gap=l_worst, logit_rms_gap=(l_sq / l_n) ** 0.5,
        choice_clear_wrong_share=wrong["clear"] / max(seen["clear"], 1),
        choice_close_wrong_share=wrong["close"] / max(seen["close"], 1),
        choice_close_share=seen["close"] / max(seen["close"]
                                               + seen["clear"], 1))
    return out
