"""``score_docs_dsv3``: ``score_docs``' loop, window and comparison (token
documents -> parser -> ``DeviceLoader`` -> ``models.cli._scorer`` -> scores
and counters read back with a one-batch lag; the window's first and last
batch held against the plain reference a layer at a time) for a
``deepseek_v3``-type configuration: the same text, bound to
``reference_dsv3.py`` in ``reference_lm.py``'s place.  The work a batch
needs is counted by ``lm_work_dsv3.py`` (``readers/step_mfu_dsv3.py``).

One step more of set-up, where the configuration's ``weights`` group has a
``router_bias_balance`` rule: the drawn router bias is trained on the
corpus's next batches as ``noaux_tc`` trains it (``router_balance.py``),
before the window and on other batches than the window's.  The program is
the warmed one (the bias is an argument), and the comparison reads the
balanced parameters."""

from __future__ import annotations

import importlib.util
import os
import time

import reference_dsv3
import router_balance


def _score_docs_bound_to(reference):
    """A private copy of the kind beside this file whose comparison uses
    ``reference``; the copy ``kimil5_score_docs`` loads is another."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "score_docs.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_traffic_score_docs_dsv3_base", path)
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    kind.reference_lm = reference
    return kind


class Cell(_score_docs_bound_to(reference_dsv3).Cell):

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
