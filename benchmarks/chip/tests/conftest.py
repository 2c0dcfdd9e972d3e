"""Rehearsals of the benchmark on the CPU, at a tiny size.  Run with
``python -m pytest benchmarks/chip/tests -q`` from the repo root; nothing
here needs a chip, and nothing here is a measurement."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"features": 4096, "batch_rows": 256, "nnz_cap": 10240}


def shrink_config(path: str, corpus_rows: int = 2048) -> None:
    with open(path) as f:
        cfg = json.load(f)
    cfg["corpus_rows"] = corpus_rows
    for k, v in TINY.items():
        cfg[k] = v
        cfg["program_args"][k] = v
    cfg["corpus"]["categorical_vocab"] = [
        min(x, 5000) for x in cfg["corpus"]["categorical_vocab"]]
    with open(path, "w") as f:
        json.dump(cfg, f)


@pytest.fixture()
def tiny_tree(tmp_path):
    """A copy of ``BENCHMARK.json`` + ``benchmarks/chip`` whose
    configurations are cut to toy sizes, with the entries of every cell
    under ``proposed/`` merged in; returns its ``Manifest``."""
    import manifest
    root = str(tmp_path)
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    proposed = os.path.join(bench, "proposed")
    for name in sorted(os.listdir(proposed)):
        with open(os.path.join(proposed, name)) as f:
            more = json.load(f)
        for section in ("workloads", "end_to_end", "per_layer"):
            doc[section] += more[section]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    for name in os.listdir(os.path.join(bench, "configs")):
        shrink_config(os.path.join(bench, "configs", name))
    return manifest.Manifest(root, bench)


@pytest.fixture()
def recorded_trace():
    import xplane
    return xplane.read(os.path.join(HERE, "fixtures",
                                    "fm24_train_text_small.xplane.pb"))
