"""``score_docs_sambay``: ``score_docs``' loop, window and comparison (token
documents -> parser -> ``DeviceLoader`` -> ``models.cli._scorer`` -> scores
and counters read back with a one-batch lag; the window's first and last
batch held against the plain reference a layer at a time) for a
SambaY-type configuration: the same text, bound to ``reference_sambay.py``
in ``reference_lm.py``'s place and to ``weights_sambay.py`` in
``weights_lm.py``'s.  No experts: the comparison's ``choice_*`` numbers
read 0 and no limit names them.  The work a batch needs is counted by
``lm_work_sambay.py`` (``readers/step_mfu_of.py``,
``readers/kernel_roofline.py``).

A traced run keeps the trace it read, as ``score_docs_afmoe`` does, for the
kernels' shares of their rooflines."""

from __future__ import annotations

import importlib.util
import os

import reference_sambay
import weights_sambay
import xplane


def _score_docs_bound_to(reference, weights):
    """A private copy of the kind beside this file whose comparison uses
    ``reference`` and whose weights ``weights`` makes."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "score_docs.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_traffic_score_docs_sambay_base", path)
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    kind.reference_lm = reference
    kind.weights_lm = weights
    return kind


class Cell(_score_docs_bound_to(reference_sambay, weights_sambay).Cell):

    def window(self, seconds: float) -> None:
        if self.ctx.trace:
            read = xplane.read

            def keeping(path):
                xplane.read = read            # this run's one read
                self.ctx.values["trace"] = read(path)
                return self.ctx.values["trace"]
            xplane.read = keeping
        super().window(seconds)
