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

TINY_CTR = {"features": 4096, "batch_rows": 256, "nnz_cap": 10240}
# hidden 64, 4 heads of 16, 8 experts top-2 of which 4 are held, 512
# vocabulary rows, float32
TINY_LM = {
    "hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_attention_heads": 4, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_token": 2, "num_experts_held": 4,
    "held_experts": [0, 4], "vocab_size": 1024, "vocab_rows": 512,
    "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12, "dtype": "float32",
}


def _shrink_ctr(cfg: dict, corpus_rows: int) -> None:
    cfg["corpus_rows"] = corpus_rows
    for k, v in TINY_CTR.items():
        cfg[k] = v
        cfg["program_args"][k] = v
    cfg["corpus"]["categorical_vocab"] = [
        min(x, 5000) for x in cfg["corpus"]["categorical_vocab"]]


def _shrink_lm(cfg: dict, corpus_rows: int) -> None:
    cfg.update(TINY_LM)
    cfg["linear_attn_config"].update(head_dim=16, num_heads=4)
    cfg["corpus"].update(categorical_vocab=[cfg["vocab_rows"]],
                         doc_lengths=[35, 130, 64, 91])
    cfg["program_args"].update(features=cfg["vocab_rows"],
                               batch_rows=cfg["batch_rows"],
                               nnz_cap=cfg["nnz_cap"])


# by the configuration's kind (its ``model``); one of a kind not listed is
# left at its size, for the test that brings it to shrink
SHRINK = {"fm": _shrink_ctr, "dcn": _shrink_ctr, "hybrid_moe_lm": _shrink_lm}


def shrink_config(path: str, corpus_rows: int = 2048) -> None:
    with open(path) as f:
        cfg = json.load(f)
    if cfg["model"] not in SHRINK:
        return
    SHRINK[cfg["model"]](cfg, corpus_rows)
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
