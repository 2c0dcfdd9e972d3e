"""``brumby14b_l8_score_docs`` end to end at a tiny size on the CPU (hidden
64, 4 query heads on 2 key heads of 16, 3 power-retention layers under
documents of 35-130 tokens, plain RoPE at the published theta, the gate's
bias drawn by the file's rule, 512 vocabulary rows, float32): once sound,
once traced, and once with each control in the program's place —
``correct`` has to come out false.  ``conftest._shrink_lm`` would give the
file a ``kv_lora_rank`` and no ``num_key_value_heads`` of its own: this
file shrinks its own."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO

CELL = "brumby14b_l8_score_docs"
CONFIG = "brumby_14b_base_l8"
TRAFFIC = "score_docs_32k_brumby"
SEED = 2 ** 31 + 24680
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "vocab_size": 512, "vocab_rows": 512,
    "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12, "dtype": "float32",
}
METRICS = ["forward_device_ms.br", "step_device_ms.br", "device_idle_share.br",
           "decode_device_ms.br", "feed.starved_share.br",
           "compiles_in_window.br", "step_mfu.br", "retention_roofline.br",
           "retention.work_waste.br"]


@pytest.fixture()
def tiny_br(tmp_path):
    """A copy of the benchmark whose ``brumby``-type scorer is cut to a toy;
    no document is a multiple of the jnp form's chunk."""
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
    cfg["weights"]["embed"] = 64 ** -0.5
    cfg["corpus"].update(categorical_vocab=[cfg["vocab_rows"]],
                         doc_lengths=[35, 130, 63, 92])
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


def test_the_committed_files_are_the_published_model_s(tiny_br):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", TRAFFIC + ".json")) as f:
        spec = json.load(f)
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["layer_types"] == ["power_retention"] * 40
    assert cfg["reduced"] == tiny_br.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "corpus_docs"]
    assert cfg["model"] == cfg["program_args"]["model"] == "hybrid_moe_lm"
    assert cfg["reference"] == "reference_brumby"
    assert cfg["linear_attn_config"] == {"kda_layers": []}
    assert cfg["vocab_rows"] == cfg["program_args"]["features"] == 151936
    assert not {"num_experts", "moe_intermediate_size", "rope_parameters",
                "first_k_dense_replace"} & set(cfg)
    lengths = cfg["corpus"]["doc_lengths"]
    assert lengths == [2185, 4369, 8738, 17476]
    assert all(n % 64 for n in lengths)
    assert sum(lengths) == cfg["nnz_cap"] == 32768
    assert len(lengths) == cfg["batch_rows"] == 4
    assert cfg["corpus_docs"] == 512
    assert spec["kind"] == "score_docs_by_reference"
    assert spec["limits"]["rows_missing"] == 0
    assert tiny_br.cells[CELL]["chips"] == 1
    assert {m["name"] for m in tiny_br.metrics_for(CELL, "per_layer")} \
        == set(METRICS)


def test_cell_runs_and_is_correct(tiny_br):
    out = run.run_cell(tiny_br, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_br.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_br.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_br, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    real = xplane.read
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_br, CELL, SEED, 0.5, trace=True)
    assert xplane.read is not real       # the kind put back what it found
    assert out["correct"] is True
    assert set(out["metrics"]) <= set(METRICS)
    for name in ("feed.starved_share.br", "compiles_in_window.br",
                 "step_device_ms.br", "device_idle_share.br",
                 "retention.work_waste.br"):
        assert name in out["metrics"], name
    # the jnp form's chunks of 64 over 320 tokens against each document's
    # cheaper exact form: a waste of more than one
    assert out["metrics"]["retention.work_waste.br"]["value"] > 1.0
    # no chip, no peaks: a share of a peak or a roofline is left out, not 0
    assert not any("mfu" in k or "roofline" in k for k in out["metrics"])


def test_the_kernel_s_roofline_reads_its_own_events(tiny_br):
    """The share reads the retention kernel's events inside whole runs of
    the program against its own needed work; another kernel's are not its."""
    import lm_work_brumby
    import peaks
    name = "retention_roofline.br"
    spec = tiny_br.layer_metric(name)
    assert spec == {"reader": "kernel_roofline",
                    "args": {"kernel": "power_retention",
                             "module": "jit_forward_counted",
                             "work": "lm_work_brumby.retention_work"}}
    reader = tiny_br.module("readers", spec["reader"])
    dev = "/device:TPU:0"
    trace = {
        "host": {"bench.window": [(1.0, 4.0)]},
        "modules": {dev: [("jit_forward_counted(7)", 1.5, 2.4),
                          ("jit_forward_counted(7)", 2.5, 3.4)]},
        "devices": {dev: [
            ("%power_retention.3 = bf16[1] custom-call(...)", 1.6, 1.7),
            ("%power_retention.4 = bf16[1] custom-call(...)", 2.6, 2.9),
            ("%doc_attention.1 = f32[1] custom-call(...)", 1.8, 2.3)]},
    }
    ctx = run.Context(tiny_br, CELL, SEED, 0.1, True)
    ctx.values.update(trace=trace, needed_work=("lm_forward", [130, 35]))
    ctx.peaks = peaks.PEAKS["TPU v5 lite"]
    f, b = lm_work_brumby.retention_work(ctx.cfg, [130, 35])
    least = max(f / 197.0e12, b / 819.0e9)
    assert reader.read(ctx, spec["args"]) == pytest.approx(
        100.0 * least / 0.2)


@pytest.mark.parametrize("control", ["fp8", "no_normaliser", "no_gate",
                                     "no_rope", "bf16_state"])
def test_each_control_fails(tiny_br, control):
    ctx = run.Context(tiny_br, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_br.module("traffic", ctx.traffic["kind"]).Cell(ctx)
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


def test_the_parent_refuses_the_configuration_at_once(tiny_br, monkeypatch):
    """A program from before this layer kind existed fails the cell cleanly
    in ``setup``: its grouped-query check knew two kinds of layer."""
    from dmlc_core_tpu.models import hybrid_lm

    def parent_init(self, arch):
        known = ("sliding_attention", "full_attention")
        types = list(arch["layer_types"])[:arch["num_hidden_layers"]]
        if set(types) - set(known):
            raise ValueError(f"layer_types has to say one of {known}")
    monkeypatch.setattr(hybrid_lm.HybridMoELM, "__init__", parent_init)
    with pytest.raises(ValueError, match="layer_types"):
        run.run_cell(tiny_br, CELL, SEED, 0.3, trace=False)


def test_work_count_follows_the_configuration():
    """The reckoning: 330.35 M matrix parameters a token meets a layer;
    retention at the cell's lengths 1.030 TFLOP in the pairs form (2 185,
    4 369, 8 738 tokens) + 1.787 in the state form (17 476) a layer; the
    forward 246.7 TFLOP a batch."""
    import lm_work_brumby
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    lengths = cfg["corpus"]["doc_lengths"]
    s = lm_work_brumby.sizes(cfg)
    assert s["retention"] + s["gate"] + s["mlp"] == 330_342_400
    forms = [lm_work_brumby.pairs_and_state(cfg, n) for n in lengths]
    assert [p < st for p, st in forms] == [True, True, True, False]
    one = dict(cfg, num_hidden_layers=1)
    f, b = lm_work_brumby.retention_work(one, lengths)
    assert f == 8 * (sum(p for p, _ in forms[:3]) + forms[3][1])
    assert 1.029e12 < 8 * sum(p for p, _ in forms[:3]) < 1.031e12
    assert 1.786e12 < 8 * forms[3][1] < 1.788e12
    assert b == 32768 * ((2 * 40 + 2 * 8) * 128 * 2 + 4 * 8)
    flops, bytes_ = lm_work_brumby.lm_forward(cfg, lengths)
    assert 246.6e12 < flops < 246.8e12
    assert bytes_ == 2 * (2 * 5120 * 151936 + 8 * 330_342_400) \
        + 4 * 32768 + 4 * 4
