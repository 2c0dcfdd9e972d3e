"""Everything the harness knows about cells, it reads from files.

``BENCHMARK.json`` (repo root) names the configurations, cells and metrics.
The harness finds, by those names and nowhere else:

* ``configs/<config>.json``        shapes and data of one model configuration
* ``workloads/<traffic>.json``     parameters of one traffic mix; its ``kind``
                                   names the module ``traffic/<kind>.py``
* ``layer_metrics/<metric>.json``  one per-layer metric; its ``reader`` names
                                   the module ``readers/<reader>.py``

so a later PR adds a configuration, a traffic mix or a per-layer metric by
adding files and entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# the program's tuning choices: a configuration or traffic file that sets
# one is refused, so no cell can win by editing a file
TUNING_KNOBS = ("kstep", "wire_compact", "put_threads", "ragged", "engine",
                "max_delay_s", "max_queue", "prefetch")


class ManifestError(ValueError):
    pass


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _check_name(what: str, name) -> None:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(f"{what} {name!r}: 1-64 of [A-Za-z0-9_.-], not "
                            f"starting with '.' or '-'")


def _check_metric(m: dict, cells: set) -> None:
    _check_name("metric", m.get("name"))
    if not UNIT.match(str(m.get("unit", ""))):
        raise ManifestError(f"metric {m['name']}: unit {m.get('unit')!r}")
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {m['name']}: better={m.get('better')!r}")
    if m.get("source") not in SOURCES:
        raise ManifestError(f"metric {m['name']}: source={m.get('source')!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise ManifestError(f"metric {m['name']}: unknown cell {w!r}")


class Manifest:
    """``BENCHMARK.json`` plus the benchmark directory it points at."""

    def __init__(self, repo_root: str, bench_dir: str = HERE):
        self.repo_root = repo_root
        self.bench_dir = bench_dir
        self.doc = _load_json(os.path.join(repo_root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.validate()

    def validate(self) -> None:
        d = self.doc
        if len(self.cells) != len(d["workloads"]):
            raise ManifestError("two cells share a name")
        if len(self.configs) != len(d["configs"]):
            raise ManifestError("two configurations share a name")
        for c in d["configs"]:
            _check_name("config", c["name"])
            for k in c.get("reduced", ()):
                _check_name("reduced key", k)
        pairs = set()
        for w in d["workloads"]:
            _check_name("cell", w["name"])
            _check_name("traffic", w["traffic"])
            if w["config"] not in self.configs:
                raise ManifestError(f"cell {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"cell {w['name']}: chips {w['chips']}")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"cell {w['name']}: pair appears twice")
            pairs.add((w["config"], w["traffic"]))
        names = set()
        e2e = set()
        for m in d["end_to_end"]:
            _check_metric(m, set(self.cells))
            e2e.add(m["name"])
        for m in d["end_to_end"] + d["per_layer"]:
            if m["name"] in names:
                raise ManifestError(f"two metrics named {m['name']}")
            names.add(m["name"])
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s among end_to_end")
        reported = {c: {e["name"] for e in self.metrics_for(c, "end_to_end")}
                    for c in self.cells}
        for m in d["per_layer"]:
            _check_metric(m, set(self.cells))
            if m.get("moves") not in e2e:
                raise ManifestError(f"metric {m['name']}: moves "
                                    f"{m.get('moves')!r} is no end_to_end")
            # each of its cells has to report the metric it should move
            for w in m.get("workloads", ()):
                if m["moves"] not in reported[w]:
                    raise ManifestError(
                        f"metric {m['name']}: moves {m['moves']!r}, which "
                        f"its cell {w!r} does not report")

    # -- per-cell views ---------------------------------------------------
    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(f"no cell {name!r} in BENCHMARK.json "
                                f"(have {sorted(self.cells)})")
        return self.cells[name]

    def config(self, cell_name: str) -> dict:
        entry = self.configs[self.cell(cell_name)["config"]]
        cfg = _load_json(os.path.join(self.repo_root, entry["file"]))
        _refuse_knobs(entry["file"], cfg)
        return cfg

    def traffic(self, cell_name: str) -> dict:
        path = os.path.join(self.bench_dir, "workloads",
                            self.cell(cell_name)["traffic"] + ".json")
        spec = _load_json(path)
        _refuse_knobs(path, spec)
        return spec

    def metrics_for(self, cell_name: str, section: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        out = []
        for m in self.doc[section]:
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end":
                out.append(m)
            else:   # no list: every cell that reports what it moves
                if m["moves"] in {e["name"] for e in
                                  self.metrics_for(cell_name, "end_to_end")}:
                    out.append(m)
        return out

    def layer_metric(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "layer_metrics",
                                       name + ".json"))

    def module(self, folder: str, name: str):
        """``traffic/<kind>.py`` or ``readers/<reader>.py``, by name."""
        _check_name(folder, name)
        path = os.path.join(self.bench_dir, folder, name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"no {folder}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{folder}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _refuse_knobs(path: str, obj, trail: str = "") -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            if k in TUNING_KNOBS:
                raise ManifestError(
                    f"{path}: {trail}{k} is a tuning choice of the program; "
                    f"a configuration pins shapes and data only")
            _refuse_knobs(path, v, f"{trail}{k}.")
