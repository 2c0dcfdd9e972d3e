"""``score_docs_by_reference``: ``score_docs``' loop, window and comparison
(token documents -> parser -> ``DeviceLoader`` -> ``models.cli._scorer`` ->
scores and counters read back with a one-batch lag; the window's first and
last batch held against the plain reference a layer at a time) for any
document-scorer configuration whose file names its plain reference: the
module ``reference`` of the configuration, beside the harness, takes
``reference_lm.py``'s place.  The kinds that bind ``score_docs`` to one
reference each by name (``score_docs_dsv3``, ``score_docs_afmoe``,
``score_docs_sambay``) are this one with the name written in.

A traced run keeps the trace it read: the harness hands a reader the
reduced trace (its ten longest ops) and deletes the file, and a kernel's
share of its roofline needs every event of one name, so the cell wraps the
harness's ``xplane.read`` for the run and leaves what it returned under
``ctx.values["trace"]``."""

from __future__ import annotations

import importlib
import importlib.util
import os

import xplane


def _score_docs_bound_to(reference: str):
    """A private copy of the kind beside this file whose comparison uses
    the module ``reference``; the copies other cells load are others."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "score_docs.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_traffic_score_docs_by_{reference}", path)
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    kind.reference_lm = importlib.import_module(reference)
    return kind


def Cell(ctx):
    """The cell of ``ctx``: ``score_docs``' cell bound to the reference its
    configuration names."""
    base = _score_docs_bound_to(ctx.cfg["reference"]).Cell

    class Bound(base):

        def window(self, seconds: float) -> None:
            if self.ctx.trace:
                read = xplane.read

                def keeping(path):
                    xplane.read = read            # this run's one read
                    self.ctx.values["trace"] = read(path)
                    return self.ctx.values["trace"]
                xplane.read = keeping
            super().window(seconds)

    return Bound(ctx)
