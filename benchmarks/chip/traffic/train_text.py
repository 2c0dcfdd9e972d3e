"""``train_text``: the job a chip-hour buys.  Text on disk → the program's
parser → ``DeviceLoader`` → jitted train step, epoch after epoch with
``before_first()`` between, as ``dmlc_core_tpu.models.cli.main`` drives it
(per-step loop, the loss read every ``log_every`` steps).

Set-up builds ONE step + state, drives it from the seed through its first
``checked_steps`` steps on the corpus's first batches (all rows differ) and
hands the same objects to the window.  After the window the program's state
is freed and the plain reference follows those steps from the same start.
"""

from __future__ import annotations

import statistics
import time

import checks
import reference
import textfeed


class Cell:
    host_labels = ("bench.read_loss", "bench.next_batch", "bench.dispatch")

    def __init__(self, ctx):
        self.ctx = ctx
        self.feed = None

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        import optax
        from dmlc_core_tpu.models.train import make_train_step
        ctx = self.ctx
        self.feed = feed = textfeed.TextFed(ctx)
        p = feed.p
        self.lr = float(p.lr)
        opt = optax.adam(p.lr)
        self.step = make_train_step(feed.model, opt)
        self.params = feed.make_weights()
        self.opt_state = opt.init(self.params)
        feed.open_loader()
        self.log_every = int(p.log_every) or 100
        self.steps_done = 0

        k = int(ctx.traffic["checked_steps"])
        self.prog_losses = []
        t0 = time.perf_counter()
        for i in range(k + int(ctx.traffic["warm_steps"])):
            loss = self._one_step()
            if i < k:
                self.prog_losses.append(float(loss))
            if i == 0:
                mu = next(s.mu for s in self.opt_state if hasattr(s, "mu"))
                self.prog_grad = {a: b / (1 - reference.B1) for a, b in
                                  reference.tree_norms(mu).items()}
            if i == k - 1:
                self.prog_delta = feed.delta_norms(self.params)
        jax.block_until_ready(self.params)
        ctx.say(f"[setup] first {k} steps checked + warm-up in "
                f"{time.perf_counter() - t0:.1f}s; losses "
                f"{[round(x, 6) for x in self.prog_losses]}")

    def _one_step(self):
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, self.feed.next_batch()[1])
        self.steps_done += 1
        return loss

    # -- the measured window ------------------------------------------------
    def window(self, seconds: float) -> None:
        import jax.profiler as prof
        ctx = self.ctx
        stages0 = textfeed.stage_seconds()
        n = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with prof.TraceAnnotation("bench.next_batch"):
                _, batch = self.feed.next_batch()
            with prof.TraceAnnotation("bench.dispatch"):
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, batch)
            n += 1
            if n % self.log_every == 0:
                with prof.TraceAnnotation("bench.read_loss"):
                    float(loss)
            if time.perf_counter() >= deadline:
                break
        with prof.TraceAnnotation("bench.read_loss"):
            last = float(loss)          # a value read closes the window
        wall = time.perf_counter() - t0
        self.steps_done += n
        rows = n * self.feed.rows
        v = ctx.values
        v["attempted"] = n
        v["failed"] = 0 if last == last and abs(last) != float("inf") else n
        v["rows_per_s"] = rows / wall
        v["window_wall_s"] = wall
        v["steps"] = n
        v["stages"] = textfeed.stage_delta(stages0)
        v["last_loss"] = last
        nnz = self.feed.corpus.nnz / self.feed.batches_per_epoch
        v["needed_work"] = ("fm_train_step",
                            (self.feed.rows, nnz, int(self.feed.p.dim)))
        ctx.say(f"[window] {n} steps, {rows} rows in {wall:.3f}s = "
                f"{rows / wall:.0f} rows/s; last loss {last:.5f}; stages "
                f"{ {k: round(s, 3) for k, s in v['stages'].items()} }")

    # -- the comparison -----------------------------------------------------
    def verify(self) -> list:
        ctx, feed = self.ctx, self.feed
        k = int(ctx.traffic["checked_steps"])
        feed.close()
        del self.params, self.opt_state, self.step     # free the program
        t0 = time.perf_counter()
        got = compare(ctx, feed, k, self.lr, self.prog_losses,
                      self.prog_grad, self.prog_delta)
        ctx.say(f"[verify] reference followed {k} steps in "
                f"{time.perf_counter() - t0:.1f}s")
        return got

    def close(self) -> None:
        if self.feed is not None:
            self.feed.close()


def reference_readings(ctx, feed, k: int, lr: float, dtype=None,
                       half_batch: bool = False):
    """(losses, first-gradient norms, update norms) of the plain reference
    over the first ``k`` batches, from the benchmark's own start weights.
    ``dtype`` and ``half_batch`` put it in the program's place with a fault:
    a lower precision, or half of every batch left out and the mean taken
    over the rest."""
    p0 = feed.make_weights()
    batches = [feed.batch_truth(i) for i in range(k)]
    if half_batch:
        batches = [tuple(a[:len(a) // 2] for a in b) for b in batches]
    losses, grad, pk = reference.train(ctx.cfg["reference"], p0, batches,
                                       lr, dtype)
    delta = feed.delta_norms(pk)
    del pk
    return losses, grad, delta


def gaps(prog_losses, prog_grad, prog_delta, ref_losses, ref_grad, ref_delta):
    """The three numbers compared.  Norm gaps are taken by the worst leaf:
    ``| ||prog|| - ||ref|| |`` over the larger of the reference's norm of
    that leaf and of the median leaf.  Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone under
    Adam and are left out of the update gap."""
    def worst(prog, ref, keys):
        med = statistics.median(ref[k] for k in ref)
        return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)

    med_g = statistics.median(ref_grad.values())
    moved = [k for k in ref_grad if ref_grad[k] >= 1e-3 * med_g]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog_losses, ref_losses)),
        "grad_norm_gap": worst(prog_grad, ref_grad, list(ref_grad)),
        "update_norm_gap": worst(prog_delta, ref_delta, moved),
    }


def compare(ctx, feed, k, lr, prog_losses, prog_grad, prog_delta) -> list:
    ref = reference_readings(ctx, feed, k, lr)
    ctx.say(f"[verify] losses program {prog_losses} reference {ref[0]}")
    ctx.say(f"[verify] first gradient norms program {prog_grad} "
            f"reference {ref[1]}")
    ctx.say(f"[verify] update norms after {k} steps program {prog_delta} "
            f"reference {ref[2]}")
    got = gaps(prog_losses, prog_grad, prog_delta, *ref)
    limits = ctx.traffic["limits"]
    return [checks.check(name, got[name], limits[name]) for name in limits]
