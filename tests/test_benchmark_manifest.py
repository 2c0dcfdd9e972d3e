"""``BENCHMARK.json`` as the chip benchmark's harness loads it
(``benchmarks/chip/manifest.py``): it validates, and every file it names —
a configuration's ``file``, a cell's traffic file and kind, a per-layer
metric's ``layer_metrics`` file and reader — is in the tree."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


@pytest.fixture(scope="module")
def man():
    sys.path.insert(0, BENCH)
    try:
        import manifest
        yield manifest.Manifest(REPO, BENCH)
    finally:
        sys.path.remove(BENCH)


def _names(section):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [entry["name"] for entry in json.load(f)[section]]


def test_the_manifest_validates(man):
    man.validate()
    assert set(man.cells) == set(_names("workloads"))
    assert set(man.configs) == set(_names("configs"))
    assert man.doc["paths"] == ["benchmarks/chip"]
    assert len(man.cells) <= 24 and len(man.configs) <= 24
    # every configuration is run by some cell
    assert {c["config"] for c in man.cells.values()} == set(man.configs)


@pytest.mark.parametrize("config", _names("configs"))
def test_a_configuration_s_file_is_there_and_states_what_it_cut(man, config):
    entry = man.configs[config]
    assert entry["file"].startswith("benchmarks/chip/configs/")
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config
    assert cfg.get("reduced", entry["reduced"]) == entry["reduced"]
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]


@pytest.mark.parametrize("cell", _names("workloads"))
def test_a_cell_s_traffic_file_and_kind_are_there(man, cell):
    spec = man.traffic(cell)
    kind = os.path.join(BENCH, "traffic", spec["kind"] + ".py")
    assert os.path.exists(kind), kind
    cfg = man.config(cell)
    assert cfg["program_args"]["model"]
    rates = [m["name"] for m in man.metrics_for(cell, "end_to_end")]
    assert "setup_s" in rates and len(rates) >= 2
    assert set(spec["reports"]) <= set(rates)
    assert man.metrics_for(cell, "per_layer")
    assert len(man.cells[cell]["why"]) <= 200


@pytest.mark.parametrize("metric", _names("per_layer"))
def test_a_layer_metric_s_file_and_reader_are_there(man, metric):
    spec = man.layer_metric(metric)
    reader = os.path.join(BENCH, "readers", spec["reader"] + ".py")
    assert os.path.exists(reader), reader
    entry = next(m for m in man.doc["per_layer"] if m["name"] == metric)
    assert entry["workloads"], "a metric lists the cells that report it"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
