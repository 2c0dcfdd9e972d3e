"""Regression gate over the committed ``BENCH_*.json`` trajectory.

Every benchmark writes one ``BENCH_<family>_r<round>.json`` artifact per
round (``BENCH_serving_r06.json``, ``BENCH_capacity_r05.json``, bare
``BENCH_r01.json``).  Until now those were a folder of JSON — nothing
failed when a PR made serving 30% slower.  This tool turns the
trajectory into a gate:

* group artifacts by family, order by round number;
* flatten the newest and the previous round into dotted numeric keys
  (``scenarios.concurrent.latency_ms.p50``);
* classify each shared key by name — throughput-like tokens
  (qps/rate/throughput/mb_s/rows) regress when they DROP, latency-like
  tokens (latency/p50/p95/p99/seconds/ms/wall/overhead) regress when
  they RISE; keys matching neither heuristic are informational only;
* exit 1 when any shared key moved in its bad direction by more than
  the threshold (default 10%, ``--threshold 0.25`` / env
  ``DMLC_BENCH_THRESHOLD``).

A family with fewer than two rounds passes vacuously (first round of a
new bench is the baseline, not a regression).  Tiny absolute values are
ignored (``--min-abs``, default 1e-9) — a 0.0001ms → 0.0002ms "100%
regression" is measurement noise, not signal.

``--emit-history`` additionally appends one JSON line per gated family
to ``PROGRESS.jsonl`` (newest round, direction-classified headline
metrics, pass/regressed status), so the bench trajectory is
machine-readable — the telemetry time machine for the benches
themselves.

Usage::

    python benchmarks/check_regression.py [--dir REPO]
        [--threshold 0.1] [--min-abs 1e-9] [--family serving]
        [--emit-history] [-v]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

#: BENCH_<family>_r<round>.json; bare BENCH_r01.json → family "core"
_BENCH_RE = re.compile(r"^BENCH_(?:(?P<family>.+)_)?r(?P<round>\d+)"
                       r"(?P<partial>_partial)?\.json$")

_HIGHER_BETTER = ("qps", "rate", "throughput", "mb_s", "mbs", "rows",
                  "goodput", "ok", "hits", "speedup", "mfu", "fill",
                  "conns_held")
# padding_ratio (padded-nnz / true-nnz, ISSUE 6 ragged path): 1.0 is the
# floor, every point above it is padding tax — lower is better.  The
# ragged scenario families (ingest_ragged, *_ragged serving scenarios)
# need no extra tokens: their qps/latency/rows keys classify as usual.
#  epochs_to_converge (ISSUE 7 autotuner cold start): each epoch spent
#  searching is an epoch served on a worse config — fewer is better.
#  The reshard family (ISSUE 9, BENCH_reshard_r*.json) needs no extra
#  tokens either: reshard_wall_s / ckpt_reload_wall_s gate lower-better
#  via "wall", reshard_vs_reload_speedup gates higher-better via
#  "speedup".
#  bytes_per_row (ISSUE 12 sharded embeddings): wire cost of one looked-up
#  row after dedup + hot-row caching — every byte shaved is exchange
#  bandwidth back; the family's embed_lookup_rows_s gates higher-better
#  via "rows" as usual.
#  The router family (ISSUE 13, BENCH_router_r*.json) gates lower-better
#  on shed_pct (via "shed"), rolling_restart_p99_ms (via "p99"/"_ms") and
#  router_overhead_p50 (via "overhead"); scaling_qps gates higher-better
#  via "qps".
#  dispatcher_failover_s (ISSUE 16 dispatcher HA): SIGKILL→journal-replayed
#  dispatcher answering status — recovery time, lower is better.  The
#  fleet speedup keys (speedup_3v1 / parser_speedup_3v1) gate
#  higher-better via "speedup" and are stamped only on hosts with
#  cores >= workers, so a core-starved runner simply doesn't gate them.
#  The ha family (ISSUE 17, BENCH_ha_r*.json): registry_failover_s /
#  tracker_failover_s — SIGKILL→journal-replayed singleton serving its
#  control RPCs again — both gate lower-better via "failover".
#  The trace family (ISSUE 18, BENCH_trace_r*.json): three layered
#  trace_*_qps_overhead_pct keys gate lower-better via "overhead"
#  (all = span instrumentation vs untraced; sampler = buffer/decide
#  machinery at floor 1.0 vs no sampler; tail = dropping at floor 0.01
#  vs keeping everything), and trace_budget_ok (1 while the tail layer
#  stays < 1% — dropping must never cost more than keeping) gates
#  higher-better via "ok" — a budget miss reads as a 100% drop, which
#  fails the gate.
#  The c10k family (ISSUE 19, BENCH_c10k_r*.json): the connection-fabric
#  ladder gates idle_conns_held higher-better via "conns_held" (how many
#  mostly-idle connections one router process holds), and
#  mem_per_conn_kb / resident_threads lower-better — RSS per held
#  connection and the process thread count, which the reactor keeps at
#  O(loops + executor) instead of O(connections); the live-subset p99
#  keys gate lower-better via "p99" as usual.
#  The diagnose family (ISSUE 20, BENCH_diagnose_r*.json): one headline,
#  diagnose_wall_ms — a full /diagnose pass over a worst-case evidence
#  set (2048-event wide ring, 300 series x 300 points, 2k spans) —
#  gates lower-better via "_ms"; an incident diagnosis that itself
#  stalls the exporter is a regression regardless of its verdicts.
_LOWER_BETTER = ("latency", "p50", "p95", "p99", "seconds", "_ms", "ms_",
                 "wall", "overhead", "compile", "stall", "shed", "drops",
                 "errors", "misses", "padding_ratio", "truncated",
                 "epochs_to_converge", "bytes_per_row",
                 "shed_pct", "rolling_restart_p99_ms", "failover",
                 "mem_per_conn", "resident_threads")


def _direction(key: str) -> Optional[str]:
    """'up' = higher is better, 'down' = lower is better, None = no
    opinion.  Lower-better tokens win ties: 'latency_ms.p50' must read
    as latency even though 'p50' alone would too."""
    k = key.lower()
    if any(t in k for t in _LOWER_BETTER):
        return "down"
    if any(t in k for t in _HIGHER_BETTER):
        return "up"
    return None


def _flatten(doc: Any, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            out.update(_flatten(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(doc, (int, float)) and not isinstance(doc, bool):
        out[prefix] = float(doc)
    return out


def discover(directory: str, family: Optional[str] = None
             ) -> Dict[str, List[Tuple[int, str]]]:
    """family → [(round, path)] sorted ascending; partials excluded."""
    families: Dict[str, List[Tuple[int, str]]] = {}
    for name in sorted(os.listdir(directory)):
        m = _BENCH_RE.match(name)
        if m is None or m.group("partial"):
            continue
        fam = m.group("family") or "core"
        if family is not None and fam != family:
            continue
        families.setdefault(fam, []).append(
            (int(m.group("round")), os.path.join(directory, name)))
    for rounds in families.values():
        rounds.sort()
    return families


def compare(prev_path: str, new_path: str, threshold: float,
            min_abs: float) -> List[Dict[str, Any]]:
    """Regressions between two artifacts: shared numeric keys that moved
    in their bad direction past the threshold."""
    prev = _flatten(json.load(open(prev_path)))
    new = _flatten(json.load(open(new_path)))
    regressions: List[Dict[str, Any]] = []
    for key in sorted(set(prev) & set(new)):
        direction = _direction(key)
        if direction is None:
            continue
        p, n = prev[key], new[key]
        if abs(p) < min_abs or abs(n) < min_abs:
            continue
        change = (n - p) / abs(p)
        bad = change < -threshold if direction == "up" \
            else change > threshold
        if bad:
            regressions.append({"key": key, "prev": p, "new": n,
                                "change": change, "direction": direction})
    return regressions


def history_line(fam: str, rnd: int, path: str, status: str,
                 min_abs: float) -> Dict[str, Any]:
    """One ``PROGRESS.jsonl`` record: the round's direction-classified
    headline metrics (keys the gate has an opinion about — the rest is
    config echo, not trajectory).  Registry/console echoes
    (``.registry.`` / ``.router_counters.``) are excluded: they are
    runtime-dependent counters, not headline numbers."""
    flat = _flatten(json.load(open(path)))
    metrics = {k: v for k, v in sorted(flat.items())
               if _direction(k) is not None and abs(v) >= min_abs
               and ".registry." not in k and ".router_counters." not in k}
    return {"schema": "dmlc.bench.progress/1", "family": fam,
            "round": rnd, "artifact": os.path.basename(path),
            "status": status, "metrics": metrics}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="gate the newest BENCH_*.json against the prior round")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_*.json (default: repo root)")
    ap.add_argument("--threshold", type=float, default=float(
        os.environ.get("DMLC_BENCH_THRESHOLD", "0.1")),
        help="relative move that counts as a regression (default 0.10)")
    ap.add_argument("--min-abs", type=float, default=1e-9,
                    help="ignore values smaller than this (noise floor)")
    ap.add_argument("--family", default=None,
                    help="check one family only (e.g. serving)")
    ap.add_argument("--emit-history", action="store_true",
                    help="append each gated family's headline metrics as "
                         "a JSON line to PROGRESS.jsonl")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    families = discover(args.dir, args.family)
    if not families:
        print(f"check_regression: no BENCH_*.json under {args.dir}")
        return 0
    failed = False
    history: List[Dict[str, Any]] = []
    for fam, rounds in sorted(families.items()):
        if len(rounds) < 2:
            print(f"{fam}: r{rounds[-1][0]:02d} only — baseline, pass")
            history.append(history_line(fam, rounds[-1][0], rounds[-1][1],
                                        "baseline", args.min_abs))
            continue
        (pr, prev_path), (nr, new_path) = rounds[-2], rounds[-1]
        regs = compare(prev_path, new_path, args.threshold, args.min_abs)
        if regs:
            failed = True
            print(f"{fam}: r{pr:02d} → r{nr:02d} REGRESSED "
                  f"({len(regs)} metric(s) past "
                  f"{args.threshold * 100:.0f}%):")
            for r in regs:
                arrow = "↓" if r["direction"] == "up" else "↑"
                print(f"  {arrow} {r['key']}: {r['prev']:g} → {r['new']:g} "
                      f"({r['change'] * +100:+.1f}%)")
        else:
            print(f"{fam}: r{pr:02d} → r{nr:02d} ok")
            if args.verbose:
                prev = _flatten(json.load(open(prev_path)))
                new = _flatten(json.load(open(new_path)))
                for key in sorted(set(prev) & set(new)):
                    if _direction(key) is not None and abs(prev[key]) > 0:
                        print(f"    {key}: {prev[key]:g} → {new[key]:g}")
        history.append(history_line(fam, nr, new_path,
                                    "regressed" if regs else "pass",
                                    args.min_abs))
    if args.emit_history:
        out = os.path.join(args.dir, "PROGRESS.jsonl")
        with open(out, "a", encoding="utf-8") as f:
            for line in history:
                f.write(json.dumps(line, sort_keys=True) + "\n")
        print(f"check_regression: appended {len(history)} history "
              f"line(s) to {out}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
