"""span-vocabulary: span names follow the grammar and match the docs.

Motivating bug class (PR 11 flight deck): span names are wire-visible
operator vocabulary the same way metric names are — Perfetto queries,
trace-driven dashboards, and the cross-tier e2e tests are written
against them — yet nothing stopped a PR from opening a
``data_service.serve_stream`` span without a row in the
``docs/observability.md`` span catalog, or from renaming a span a
documented trace-topology diagram still referenced.  Mirrors
``metric-vocabulary``, both directions:

* every **literal** name passed to ``span()`` / ``start_span()`` /
  ``record_completed()``, and every literal of a ``wait_spans=(...)``
  keyword (the names a queue's owner gives its waits,
  ``utils.threaded_iter``), must
  match the span grammar (lowercase dotted segments; single-segment
  names like ``reshard`` are legal for whole-subsystem spans);
* every such name must be covered by a row in the span catalog of
  ``docs/observability.md`` (the table whose header column is
  ``Span``; rows may group with ``{a,b}`` braces and use
  ``<wildcard>`` segments);
* every non-wildcard documented span must still exist in code (stale
  doc rows fail too).

Dynamically-built names are skipped per-site, same as metrics.
``Match.span()`` / ``slice``-style calls don't trip the rule: only a
string-literal first argument is considered.
"""

from __future__ import annotations

import ast
import os
import re
from typing import List, Pattern, Set, Tuple

from .core import (Finding, LintContext, LintRule, ParsedModule, lint_rule,
                   str_const)
from .rules_metrics import _expand_braces

_SPAN_FUNCS = {"span", "start_span", "record_completed"}
_GRAMMAR = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")
#: doc-table token: looks like a (possibly braced/wildcarded) span name
_DOC_TOKEN = re.compile(r"`([a-z][a-z0-9_{}<>,./]*)`")


@lint_rule("span-vocabulary",
           description="span names follow the dotted grammar and are "
                       "documented in the docs/observability.md span "
                       "catalog (both ways)")
class SpanVocabularyRule(LintRule):

    def check_module(self, mod: ParsedModule, ctx: LintContext
                     ) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = (fn.attr if isinstance(fn, ast.Attribute)
                      else fn.id if isinstance(fn, ast.Name) else None)
            names = []
            if callee in _SPAN_FUNCS and node.args:
                names.append(str_const(node.args[0]))
            # a queue's owner names its waits at the call that builds the
            # queue: ``ThreadedIter(..., wait_spans=("q.wait_slot", None))``
            for kw in node.keywords:
                if kw.arg == "wait_spans" and isinstance(kw.value, ast.Tuple):
                    names.extend(str_const(e) for e in kw.value.elts)
            for name in names:
                if name is None:    # dynamic name — wildcard family
                    continue
                ctx.note_span(name, mod.rel)
                if not _GRAMMAR.match(name):
                    out.append(Finding(
                        self.name, mod.rel, node.lineno, node.col_offset,
                        f"span name {name!r} violates the span grammar "
                        f"(lowercase dotted segments)"))
        return out

    def finalize(self, ctx: LintContext) -> List[Finding]:
        if not getattr(ctx, "full_run", False):
            return []
        doc_path = os.path.join(ctx.docs_dir, "observability.md")
        rel = os.path.relpath(doc_path, ctx.repo_root)
        try:
            with open(doc_path, encoding="utf-8") as f:
                doc = f.read()
        except OSError:
            return [Finding(self.name, rel, 0, 0,
                            "docs/observability.md unreadable — the span "
                            "vocabulary has no contract to check against")]
        literals, patterns = _doc_span_vocabulary(doc)
        code_names = set(ctx.span_sites)
        out: List[Finding] = []
        for name in sorted(code_names):
            if name in literals or any(p.match(name) for p in patterns):
                continue
            sites = ", ".join(sorted(ctx.span_sites[name])[:3])
            out.append(Finding(
                self.name, rel, 0, 0,
                f"span {name!r} ({sites}) has no row in the "
                f"docs/observability.md span catalog — document it"))
        for name in sorted(literals):
            if name not in code_names:
                out.append(Finding(
                    self.name, rel, 0, 0,
                    f"documented span {name!r} no longer exists in code — "
                    f"delete the stale doc row (or restore the span)"))
        return out


def _doc_span_vocabulary(doc: str) -> Tuple[Set[str], List[Pattern[str]]]:
    """Parse span-catalog rows into (literal names, wildcard patterns).

    A row counts when it sits in a markdown table whose header has a
    ``Span`` column (the span catalog's signature — the metric tables
    key on ``Type`` instead, so neither vocabulary leaks into the
    other) and its first cell carries backticked span-shaped tokens.
    """
    literals: Set[str] = set()
    patterns: List[Pattern[str]] = []
    in_span_table = False
    for line in doc.splitlines():
        if not line.lstrip().startswith("|"):
            in_span_table = False
            continue
        cells = line.split("|")
        if any(c.strip() == "Span" for c in cells):
            in_span_table = True
            continue
        if not in_span_table or len(cells) < 3:
            continue
        first = cells[1]
        for m in _DOC_TOKEN.finditer(first):
            for name in _expand_braces(m.group(1)):
                if "<" in name:
                    rx = "^" + re.sub(r"<[^<>]*>", r"[a-z0-9_.]+",
                                      re.escape(name)) + "$"
                    patterns.append(re.compile(rx))
                elif _GRAMMAR.match(name):
                    literals.add(name)
    return literals, patterns
