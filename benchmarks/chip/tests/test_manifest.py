import json
import os
import shutil

import pytest

import manifest
from conftest import BENCH, REPO, shrink_config


def test_committed_manifest_is_valid_and_all_files_exist():
    man = manifest.Manifest(REPO, BENCH)
    assert 1 <= len(man.cells) <= 24
    for name, cell in man.cells.items():
        assert cell["chips"] == 1
        cfg = man.config(name)
        spec = man.traffic(name)
        man.module("traffic", spec["kind"])
        # what a configuration states beside program_args, it states alike
        for k in set(cfg) & set(cfg["program_args"]):
            assert cfg[k] == cfg["program_args"][k], (name, k)
        e2e = [m["name"] for m in man.metrics_for(name, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert set(spec["reports"]) <= set(e2e)
        per = man.metrics_for(name, "per_layer")
        assert per
        for m in per:
            man.module("readers", man.layer_metric(m["name"])["reader"])
    for m in man.doc["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json"))


def test_every_rate_lists_its_cells_and_every_cell_reports_one():
    man = manifest.Manifest(REPO, BENCH)
    for m in man.doc["end_to_end"]:
        assert (m["name"] == "setup_s") != ("workloads" in m), m["name"]
    for name in man.cells:
        rates = [m["name"] for m in man.metrics_for(name, "end_to_end")
                 if m["name"] != "setup_s"]
        assert len(rates) == 1, (name, rates)
        assert list(man.traffic(name)["reports"]) == rates


def test_a_layer_metric_moves_what_its_own_cells_report():
    """Loading validates it (the refusal is tested below); here, which
    rate each family of the committed metrics points at."""
    man = manifest.Manifest(REPO, BENCH)
    moves = {m["name"]: m["moves"] for m in man.doc["per_layer"]}
    assert {v for k, v in moves.items() if k.endswith(".lm")} == {
        "score_docs_per_s"}
    assert {v for k, v in moves.items() if k.endswith(".score")} == {
        "score_rows_per_s"}


@pytest.mark.parametrize("name", ["a b", "x/y", "", "-lead", "a,b",
                                  "n" * 65, "greek_μ"])
def test_bad_names_are_refused(name):
    assert not manifest.NAME.match(name)


@pytest.mark.parametrize("name", ["fm24_train_text", "step_mfu.train",
                                  "9lives", "_x", "a-b.c_d"])
def test_good_names_pass(name):
    assert manifest.NAME.match(name)


@pytest.mark.parametrize("unit,ok", [("rows/s", True), ("%", True),
                                     ("ms", True), ("tokens per s", False),
                                     ("u" * 17, False), ("μs", False)])
def test_units(unit, ok):
    assert bool(manifest.UNIT.match(unit)) is ok


@pytest.mark.parametrize("knob", manifest.TUNING_KNOBS)
def test_a_file_that_sets_a_tuning_knob_is_refused(tiny_tree, knob):
    path = os.path.join(tiny_tree.bench_dir, "workloads", "train_text.json")
    with open(path) as f:
        spec = json.load(f)
    spec[knob] = 8
    with open(path, "w") as f:
        json.dump(spec, f)
    with pytest.raises(manifest.ManifestError, match="tuning choice"):
        tiny_tree.traffic("fm24_train_text")


def test_committed_files_set_no_tuning_knob():
    man = manifest.Manifest(REPO, BENCH)
    for cell in man.cells:
        man.config(cell)
        man.traffic(cell)


def test_duplicate_pair_and_unknown_moves_are_refused(tiny_tree):
    doc_path = os.path.join(tiny_tree.repo_root, "BENCHMARK.json")
    with open(doc_path) as f:
        doc = json.load(f)
    bad = json.loads(json.dumps(doc))
    bad["workloads"].append(dict(bad["workloads"][0], name="twin"))
    with open(doc_path, "w") as f:
        json.dump(bad, f)
    with pytest.raises(manifest.ManifestError, match="pair"):
        manifest.Manifest(tiny_tree.repo_root, tiny_tree.bench_dir)
    # a metric no end-to-end one, and one that its own cell does not report
    for moves, said in (("nothing", "is no end_to_end"),
                        ("score_docs_per_s", "does not report")):
        bad = json.loads(json.dumps(doc))
        assert bad["per_layer"][0]["workloads"] == ["fm24_train_text"]
        bad["per_layer"][0]["moves"] = moves
        with open(doc_path, "w") as f:
            json.dump(bad, f)
        with pytest.raises(manifest.ManifestError, match=said):
            manifest.Manifest(tiny_tree.repo_root, tiny_tree.bench_dir)


def test_config_workload_and_metric_are_added_by_files_alone(tiny_tree):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric with new files and new entries, editing no file that exists —
    and the new cell runs."""
    import run
    root, bench = tiny_tree.repo_root, tiny_tree.bench_dir
    before = {p: os.path.getmtime(os.path.join(bench, d, p))
              for d in ("configs", "workloads", "layer_metrics", "traffic",
                        "readers") for p in os.listdir(os.path.join(bench, d))}
    # new files only
    src = os.path.join(bench, "configs", "fm_criteo_h24_d32.json")
    with open(src) as f:
        cfg = json.load(f)
    cfg["name"] = "fm_criteo_tiny_d8"
    cfg["dim"] = cfg["program_args"]["dim"] = 8
    new_cfg = os.path.join(bench, "configs", "fm_criteo_tiny_d8.json")
    with open(new_cfg, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "train_text.json")) as f:
        spec = json.load(f)
    spec["warm_steps"] = 1
    with open(os.path.join(bench, "workloads", "train_text_brief.json"),
              "w") as f:
        json.dump(spec, f)
    with open(os.path.join(bench, "layer_metrics", "last_loss.train.json"),
              "w") as f:
        json.dump({"reader": "value", "args": {"key": "last_loss"}}, f)
    # new entries only
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "fm_criteo_tiny_d8", "source": "test", "reduced": [],
        "file": os.path.relpath(new_cfg, root), "why": "test"})
    doc["workloads"].append({
        "name": "fm_tiny_brief", "config": "fm_criteo_tiny_d8",
        "traffic": "train_text_brief", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] == "train_rows_per_s":
            m["workloads"].append("fm_tiny_brief")
    doc["per_layer"].append({
        "name": "last_loss.train", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "train_rows_per_s", "workloads": ["fm_tiny_brief"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    man = manifest.Manifest(root, bench)
    out = run.run_cell(man, "fm_tiny_brief", seed=11, seconds=0.3,
                       trace=False)
    assert out["correct"] and out["metrics"]["train_rows_per_s"]["value"] > 0
    after = {p: os.path.getmtime(os.path.join(bench, d, p))
             for d in ("configs", "workloads", "layer_metrics", "traffic",
                       "readers") for p in os.listdir(os.path.join(bench, d))
             if p in before}
    assert after == before
