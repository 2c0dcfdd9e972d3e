"""``score_docs_afmoe``: ``score_docs``' loop, window and comparison (token
documents -> parser -> ``DeviceLoader`` -> ``models.cli._scorer`` -> scores
and counters read back with a one-batch lag; the window's first and last
batch held against the plain reference a layer at a time) for an
``afmoe``-type configuration: the same text, bound to ``reference_afmoe.py``
in ``reference_lm.py``'s place.  The work a batch needs is counted by
``lm_work_afmoe.py`` (``readers/step_mfu_of.py``,
``readers/kernel_roofline.py``).

As ``score_docs_dsv3`` does, where the configuration's ``weights`` group has
a ``router_bias_balance`` rule the drawn router bias is trained at set-up
(``router_balance.py``), before the window and on other batches than the
window's.

A traced run keeps the trace it read: the harness hands a reader the
reduced trace (its ten longest ops) and deletes the file, and a kernel's
share of its roofline needs every event of one name, so ``Cell`` wraps the
harness's ``xplane.read`` for the run and leaves what it returned under
``ctx.values["trace"]``."""

from __future__ import annotations

import importlib.util
import os
import time

import reference_afmoe
import router_balance
import xplane


def _score_docs_bound_to(reference):
    """A private copy of the kind beside this file whose comparison uses
    ``reference``; the copies the other scorer cells load are others."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "score_docs.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_traffic_score_docs_afmoe_base", path)
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    kind.reference_lm = reference
    return kind


class Cell(_score_docs_bound_to(reference_afmoe).Cell):

    def setup(self) -> None:
        super().setup()
        rule = self.ctx.cfg["weights"].get("router_bias_balance")
        if not rule:
            return
        t0 = time.perf_counter()
        self.params, worst = router_balance.balance(
            self.model, self.params, lambda: self._next()[1], rule)
        self.ctx.say(f"[setup] router bias balanced over {len(worst)} "
                     f"batches in {time.perf_counter() - t0:.1f}s: largest "
                     f"load over the mean {worst[0]:.2f} -> {worst[-1]:.2f}")

    def window(self, seconds: float) -> None:
        if self.ctx.trace:
            read = xplane.read

            def keeping(path):
                xplane.read = read            # this run's one read
                self.ctx.values["trace"] = read(path)
                return self.ctx.values["trace"]
            xplane.read = keeping
        super().window(seconds)
