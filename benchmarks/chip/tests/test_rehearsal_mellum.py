"""``mellum2_l8_score_docs`` end to end at a tiny size on the CPU (hidden 64,
4 query heads on 2 key heads of 16, 4 layers ``S S S F`` with a window of
16 under documents of 35-130 tokens, the published rotations, 8 experts
top-2 by softmax, all held, 512 vocabulary rows, float32): once sound, once
traced, and once with each control in the program's place — ``correct``
has to come out false.  ``conftest._shrink_lm`` would give the file a
``kv_lora_rank`` and so latent attention: this file shrinks its own."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO

CELL = "mellum2_l8_score_docs"
CONFIG = "mellum2_12b_a2.5b_l8"
TRAFFIC = "score_docs_32k_mellum"
SEED = 2 ** 31 + 97531
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 16,
    "num_hidden_layers": 4, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 512, "vocab_rows": 512,
    "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12, "dtype": "float32",
}
METRICS = ["forward_device_ms.ml", "device_idle_share.ml",
           "feed.starved_share.ml", "compiles_in_window.ml", "step_mfu.ml",
           "moe.load_skew.ml", "attn.window_walk_share.ml",
           "doc_attention_roofline.ml", "moe_experts_roofline.ml",
           "moe_combine_roofline.ml", "step_device_ms.ml",
           "decode_device_ms.ml", "feed.host_share.ml",
           "feed.ring_wait_share.ml", "moe.dispatch_waste.ml"]


@pytest.fixture()
def tiny_ml(tmp_path):
    """A copy of the benchmark whose ``mellum``-type scorer is cut to a toy;
    three of its four documents pass the toy's window."""
    import manifest
    root = str(tmp_path)
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    path = os.path.join(bench, "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["layer_types"] = cfg["layer_types"][:4]
    cfg["weights"]["embed"] = 64 ** -0.5
    cfg["corpus"].update(categorical_vocab=[cfg["vocab_rows"]],
                         doc_lengths=[35, 130, 64, 91])
    cfg["program_args"].update(features=cfg["vocab_rows"],
                               batch_rows=cfg["batch_rows"],
                               nnz_cap=cfg["nnz_cap"])
    with open(path, "w") as f:
        json.dump(cfg, f)
    spec_path = os.path.join(bench, "workloads", TRAFFIC + ".json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec.update(reference_pad=1, reference_head_block=128, probe_positions=8)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return manifest.Manifest(root, bench)


def test_the_committed_files_are_the_published_model_s(tiny_ml):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", TRAFFIC + ".json")) as f:
        spec = json.load(f)
    catalog = {
        "hidden_size": 2304, "intermediate_size": 7168, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "moe_intermediate_size": 896, "num_experts": 64,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "sliding_window": 1024, "vocab_size": 98304, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 131072, "model_type": "mellum",
        "tie_word_embeddings": False, "attention_bias": False}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert cfg["rope_parameters"]["full_attention"]["rope_type"] == "yarn"
    assert not {"first_k_dense_replace", "num_shared_experts",
                "routed_scaling_factor", "rope_theta"} & set(cfg)
    assert cfg["reduced"] == tiny_ml.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "layer_types", "corpus_docs"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 2
    assert cfg["model"] == cfg["program_args"]["model"] == "hybrid_moe_lm"
    assert cfg["reference"] == "reference_mellum"
    assert cfg["linear_attn_config"] == {"kda_layers": []}
    assert cfg["vocab_rows"] == cfg["program_args"]["features"] == 98304
    lengths = cfg["corpus"]["doc_lengths"]
    assert lengths == [328, 558, 952, 1624, 2770, 4724, 8058, 13754]
    assert sum(lengths) == cfg["nnz_cap"] == 32768
    assert len(lengths) == cfg["batch_rows"] == 8
    over = [n for n in lengths if n > cfg["sliding_window"]]
    assert len(over) == 5 and 0.93 < sum(over) / 32768 < 0.95
    assert [n for n in lengths if n > 8192] == [13754]
    assert cfg["corpus_docs"] == 512
    assert spec["kind"] == "score_docs_by_reference"
    assert spec["limits"]["rows_missing"] == 0
    assert tiny_ml.cells[CELL]["chips"] == 1
    assert {m["name"] for m in tiny_ml.metrics_for(CELL, "per_layer")} \
        == set(METRICS)


def test_cell_runs_and_is_correct(tiny_ml):
    out = run.run_cell(tiny_ml, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_ml.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_ml.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_ml, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    real = xplane.read
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_ml, CELL, SEED, 0.5, trace=True)
    assert xplane.read is not real       # the kind put back what it found
    assert out["correct"] is True
    assert set(out["metrics"]) <= set(METRICS)
    for name in ("feed.host_share.ml", "feed.ring_wait_share.ml",
                 "feed.starved_share.ml", "compiles_in_window.ml",
                 "moe.load_skew.ml", "moe.dispatch_waste.ml",
                 "step_device_ms.ml", "device_idle_share.ml",
                 "attn.window_walk_share.ml"):
        assert name in out["metrics"], name
    # 320 tokens are two blocks of 256: a walk of the window's is the
    # whole document's wherever no document holds a whole block
    assert out["metrics"]["attn.window_walk_share.ml"]["value"] == 100.0
    assert out["metrics"]["moe.load_skew.ml"]["value"] >= 1.0
    # no chip, no peaks: a share of a peak or a roofline is left out, not 0
    assert not any("mfu" in k or "roofline" in k for k in out["metrics"])


@pytest.mark.parametrize("kernel,name,work", [
    ("doc_attention", "doc_attention_roofline.ml",
     "lm_work_mellum.attention_pairs"),
    ("ragged-dot-none", "moe_experts_roofline.ml",
     "lm_work_mellum.expert_products"),
    ("moe_combine", "moe_combine_roofline.ml", "lm_work_mellum.combine_rows")])
def test_the_kernels_rooflines_read_their_own_events(tiny_ml, kernel, name,
                                                     work):
    """Each share reads its own kernel's events inside whole runs of the
    program against its own needed work; the others' events are not its."""
    import lm_work_mellum
    import peaks
    spec = tiny_ml.layer_metric(name)
    assert spec == {"reader": "kernel_roofline",
                    "args": {"kernel": kernel, "module": "jit_forward_counted",
                             "work": work}}
    reader = tiny_ml.module("readers", spec["reader"])
    dev = "/device:TPU:0"
    others = [k for k in ("doc_attention", "ragged-dot-none", "moe_combine")
              if k != kernel]
    trace = {
        "host": {"bench.window": [(1.0, 4.0)]},
        "modules": {dev: [("jit_forward_counted(7)", 1.5, 2.4),
                          ("jit_forward_counted(7)", 2.5, 3.4)]},
        "devices": {dev: [
            (f"%{kernel}.3 = f32[1] custom-call(...)", 1.6, 1.7),
            (f"%{kernel}.4 = f32[1] custom-call(...)", 2.6, 2.9),
            (f"%{others[0]}.1 = f32[1] custom-call(...)", 1.8, 2.3),
            (f"%{others[1]}.2 = f32[1] custom-call(...)", 2.0, 2.1)]},
    }
    ctx = run.Context(tiny_ml, CELL, SEED, 0.1, True)
    ctx.values.update(trace=trace, needed_work=("lm_forward", [130, 35]))
    ctx.peaks = peaks.PEAKS["TPU v5 lite"]
    f, b = getattr(lm_work_mellum, work.split(".")[1])(ctx.cfg, [130, 35])
    least = max(f / 197.0e12, b / 819.0e9)
    assert reader.read(ctx, spec["args"]) == pytest.approx(
        100.0 * least / 0.2)


@pytest.mark.parametrize("control", ["fp8", "plain_rope_full",
                                     "no_attention_factor", "sigmoid_router",
                                     "no_window"])
def test_each_control_fails(tiny_ml, control):
    ctx = run.Context(tiny_ml, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_ml.module("traffic", ctx.traffic["kind"]).Cell(ctx)
    try:
        cell.setup()
        cell.window(0.3)
        sound = cell.verify()
        assert all(c["ok"] for c in sound), sound
        planted = cell.verify(control=control)
        assert not all(c["ok"] for c in planted), planted
    finally:
        cell.close()
        shutil.rmtree(ctx.work, ignore_errors=True)


def test_the_parent_refuses_the_configuration_at_once(tiny_ml, monkeypatch):
    """A program from before this model type was computed fails the cell
    cleanly in ``setup``: it reads the spellings it knew, and the file
    names no ``first_k_dense_replace``."""
    from dmlc_core_tpu.models import hybrid_lm

    def parent_init(self, arch):
        a = dict(arch)
        for ours, theirs in (*hybrid_lm.SPELLINGS.items(),
                             *hybrid_lm.SPELLINGS_AFMOE.items()):
            if theirs in a:
                a.setdefault(ours, a.pop(theirs))
        int(a["first_k_dense_replace"])
    monkeypatch.setattr(hybrid_lm.HybridMoELM, "__init__", parent_init)
    with pytest.raises(KeyError, match="first_k_dense_replace"):
        run.run_cell(tiny_ml, CELL, SEED, 0.3, trace=False)


def test_work_count_follows_the_configuration():
    import lm_work_mellum
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    lengths = cfg["corpus"]["doc_lengths"]
    s = lm_work_mellum.sizes(cfg)
    assert s["gqa"] == 21_233_664 and s["router"] == 147_456
    assert s["expert"] * s["held"] == 396_361_728 and s["met"] == 8
    # the reckoning: 144.04 M pairs a full layer, 29.72 M a windowed one;
    # 59.7 TFLOP a batch, the parameters less their norms read once
    one = lambda kinds: lm_work_mellum.pairs(                 # noqa: E731
        dict(cfg, layer_types=kinds, num_hidden_layers=1), lengths)
    assert one(["full_attention"]) == 144_044_176
    assert 29.71e6 < one(["sliding_attention"]) < 29.73e6
    flops, bytes_ = lm_work_mellum.lm_forward(cfg, lengths)
    assert 59.6e12 < flops < 59.8e12
    assert bytes_ - 4 * 32768 - 4 * 8 == 2 * (3_794_966_784 - 8 * 4608 - 2304)
    pair_flops, _ = lm_work_mellum.attention_pairs(cfg, lengths)
    assert pair_flops == (2 * 144_044_176 + 6 * one(["sliding_attention"])) \
        * 16384
    # every expert held: eight a token, 262 144 rows a layer
    f, b = lm_work_mellum.expert_products(cfg, lengths)
    assert f == 8 * 2 * 262_144 * 3 * 2304 * 896
    assert b == 8 * (64 * 3 * 2304 * 896 * 2 + 2 * 262_144 * 2304 * 2)
    f, b = lm_work_mellum.combine_rows(cfg, lengths)
    assert f == 8 * 2 * 262_144 * 2304
    assert b == 8 * (262_144 + 32_768) * 2304 * 2
    # a holder of half the experts meets half of them
    half = dict(cfg, held_experts=[0, 32])
    assert lm_work_mellum.expert_products(half, lengths)[0] \
        == 8 * 2 * 131_072 * 3 * 2304 * 896
