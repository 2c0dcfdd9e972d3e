"""What the text-fed kinds share: the corpus on disk, the program's model
and loader built the way ``dmlc_core_tpu.models.cli.main`` builds them, and
the program's own stage timers read over a window."""

from __future__ import annotations

import os
import time

import corpus as corpus_mod
import weights

STAGES = ("parser.chunk", "parser.parse", "device_loader.pack",
          "device_loader.h2d", "device_loader.h2d_pool")


class TextFed:
    """Corpus + program objects of one text-fed cell."""

    def __init__(self, ctx, write_file: bool = True):
        from dmlc_core_tpu import native
        from dmlc_core_tpu.models import cli
        self.ctx = ctx
        cfg = ctx.cfg
        t0 = time.perf_counter()
        native.require()            # builds from source on first use
        t1 = time.perf_counter()
        path = os.path.join(ctx.work, "corpus.libsvm")
        self.corpus = corpus_mod.generate(
            path if write_file else None,
            dict(cfg["corpus"], rows=cfg["corpus_rows"]), ctx.seed)
        t2 = time.perf_counter()
        ctx.say(f"[setup] native {t1 - t0:.1f}s; corpus {self.corpus.rows} "
                f"rows, {self.corpus.nnz} values, "
                f"{self.corpus.nbytes / 1e6:.0f} MB in {t2 - t1:.1f}s")
        args = {k: str(v) for k, v in cfg["program_args"].items()}
        self.p = cli.TrainParams()
        self.p.init(dict(args, data=f"file://{path}"))
        self.model = cli.MODEL_REGISTRY[self.p.model](self.p)
        self.features = int(self.p.features)
        self.rows = int(self.p.batch_rows)
        self._cli = cli
        self.loader = None
        self.index = 0              # of the next batch inside its epoch

    def open_loader(self):
        # the one place the program configures a run's ingest loader
        self.loader = self._cli._make_loader(
            self.p, self.p.data, "libsvm", False, emit="device")
        return self.loader

    def next_batch(self):
        """(index inside the epoch, device batch); at the end of an epoch
        the loader is rewound as the train CLI rewinds it, and goes on."""
        batch = self.loader.next_batch()
        if batch is None:
            self.loader.before_first()
            self.index = 0
            batch = self.loader.next_batch()
        i, self.index = self.index, self.index + 1
        return i, batch

    def make_weights(self):
        import jax
        structure = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        params = jax.block_until_ready(weights.make(
            structure, self.ctx.seed, self.features,
            self.ctx.cfg["embed_scale"]))
        self.ctx.say(f"[setup] weights on the device in "
                     f"{time.perf_counter() - t0:.1f}s")
        return params

    def delta_norms(self, params) -> dict:
        """Per-leaf ``||params - make_weights()||``."""
        return weights.delta_norms(params, self.ctx.seed, self.features,
                                   self.ctx.cfg["embed_scale"])

    def batch_truth(self, index: int):
        """Batch ``index`` of an epoch as the generator wrote it: row-padded
        ids (folded into the feature space), values, labels."""
        lo = index * self.rows
        return self.corpus.rows_padded(lo, min(lo + self.rows,
                                               self.corpus.rows),
                                       self.features)

    @property
    def batches_per_epoch(self) -> int:
        return -(-self.corpus.rows // self.rows)

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()
            self.loader = None


def stage_seconds() -> dict:
    """Total seconds of the program's own feed stage timers, so far."""
    from dmlc_core_tpu.utils.metrics import metrics
    return {s: metrics.stage(s).total_sec for s in STAGES}


def stage_delta(before: dict) -> dict:
    now = stage_seconds()
    return {k: now[k] - before[k] for k in before}
