"""``trinity_l5_score_docs`` end to end at a tiny size on the CPU (hidden
64, 4 query heads on 2 key heads of 16, a window of 16 under documents of
35-130 tokens, 16 experts top-4 of which 3 are held, one dense layer and
four mixtures — sliding, sliding, sliding, sliding, full as the file has
them — 512 vocabulary rows, float32): once sound, once traced, and once with
each control in the program's place — ``correct`` has to come out false."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO

CELL = "trinity_l5_score_docs"
CONFIG = "trinity_large_400b_ep8_l5"
SEED = 2 ** 31 + 13579
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 16,
    "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
    "num_experts_held": 3, "held_experts": [0, 3], "vocab_size": 1024,
    "vocab_rows": 512, "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12,
    "dtype": "float32",
}


@pytest.fixture()
def tiny_tr(tmp_path):
    """A copy of the benchmark whose ``afmoe``-type scorer is cut to a toy;
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
    cfg["weights"]["embed"] = 64 ** -0.5
    cfg["corpus"].update(categorical_vocab=[cfg["vocab_rows"]],
                         doc_lengths=[35, 130, 64, 91])
    cfg["program_args"].update(features=cfg["vocab_rows"],
                               batch_rows=cfg["batch_rows"],
                               nnz_cap=cfg["nnz_cap"])
    with open(path, "w") as f:
        json.dump(cfg, f)
    spec_path = os.path.join(bench, "workloads", "score_docs_32k_long.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec.update(reference_pad=1, reference_head_block=128, probe_positions=8)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return manifest.Manifest(root, bench)


def test_the_committed_files_are_the_issue_s(tiny_tr):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads",
                           "score_docs_32k_long.json")) as f:
        spec = json.load(f)
    assert cfg["reduced"] == tiny_tr.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts_held", "vocab_rows", "corpus_docs"]
    assert cfg["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["published"]["num_hidden_layers"] == 60
    lengths = cfg["corpus"]["doc_lengths"]
    assert lengths == [328, 558, 952, 1624, 2770, 4724, 8058, 13754]
    assert sum(lengths) == cfg["nnz_cap"] == 32768
    assert len(lengths) == cfg["batch_rows"] == 8
    assert not any(n % 64 == 0 for n in lengths)
    over = [n for n in lengths if n > cfg["sliding_window"]]
    assert len(over) == 3 and 0.80 < sum(over) / 32768 < 0.82
    assert cfg["corpus_docs"] == 512 and cfg["vocab_rows"] == 25024 \
        == cfg["program_args"]["features"] == 200192 // 8
    assert spec["kind"] == "score_docs_afmoe"
    assert {k: spec[k] for k in (
        "warm_batches", "trace_seconds", "probe_positions", "close_margin",
        "reference_pad", "reference_head_block")} == {
        "warm_batches": 2, "trace_seconds": 3, "probe_positions": 32,
        "close_margin": 0.002, "reference_pad": 1024,
        "reference_head_block": 4096}
    assert tiny_tr.cells[CELL]["chips"] == 1


def test_cell_runs_and_is_correct(tiny_tr):
    out = run.run_cell(tiny_tr, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_tr.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_tr.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_tr, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    real = xplane.read
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_tr, CELL, SEED, 0.5, trace=True)
    assert xplane.read is not real       # the kind put back what it found
    assert out["correct"] is True
    names = {m["name"] for m in tiny_tr.metrics_for(CELL, "per_layer")}
    assert len(names) == 14 and set(out["metrics"]) <= names
    for name in ("feed.host_share.tr", "feed.ring_wait_share.tr",
                 "feed.starved_share.tr", "compiles_in_window.tr",
                 "moe.load_skew.tr", "moe.unserved_share.tr",
                 "moe.dispatch_waste.tr", "step_device_ms.tr",
                 "device_idle_share.tr", "attn.window_walk_share.tr"):
        assert name in out["metrics"], name
    # 320 tokens are two blocks of 256: a walk of the window's is the
    # whole document's wherever no document holds a whole block
    assert out["metrics"]["attn.window_walk_share.tr"]["value"] == 100.0
    assert out["metrics"]["moe.load_skew.tr"]["value"] >= 1.0
    assert 0.0 < out["metrics"]["moe.unserved_share.tr"]["value"] < 100.0
    # no chip, no peaks: a share of a peak or a roofline is left out, not 0
    assert not any("mfu" in k or "roofline" in k for k in out["metrics"])


def test_the_roofline_reader_reads_whole_runs_of_the_module(tiny_tr):
    """A kept trace of two whole runs of the program and one the window
    cuts: the kernel's events inside the whole runs, a batch their mean;
    another kernel's events, and one outside any run, are left out."""
    import peaks
    reader = tiny_tr.module("readers", "kernel_roofline")
    args = tiny_tr.layer_metric("doc_attention_roofline.tr")["args"]
    assert args == {"kernel": "doc_attention",
                    "module": "jit_forward_counted",
                    "work": "lm_work_afmoe.attention_pairs"}
    dev = "/device:TPU:0"
    call = "%doc_attention.{} = f32[48,128,32768]{{2,1,0}} custom-call(...)"
    trace = {
        "host": {"bench.window": [(1.0, 4.0)]},
        "modules": {dev: [("jit_forward_counted(123)", 0.5, 1.4),
                          ("jit_forward_counted(123)", 1.5, 2.4),
                          ("jit__unpack(9)", 2.4, 2.45),
                          ("jit_forward_counted(123)", 2.5, 3.4)]},
        "devices": {dev: [
            (call.format(1), 0.6, 0.9),                  # in the cut run
            ("%doc_attention = f32[48,128,32768]{2,1,0} custom-call(...)",
             1.6, 1.7), (call.format(1), 1.8, 2.0),
            ("%doc_attention_other.1 = f32[8] custom-call(...)", 2.0, 2.3),
            ("%fusion.7 = bf16[32768,3072] fusion(...)", 2.3, 2.4),
            (call.format(2), 2.6, 2.8), (call.format(3), 3.0, 3.3)]},
    }
    took = reader.kernel_seconds(trace, "doc_attention",
                                 "jit_forward_counted")
    assert took == pytest.approx((0.3 + 0.5) / 2)
    assert reader.kernel_seconds(trace, "kda_chunk",
                                 "jit_forward_counted") is None
    assert reader.kernel_seconds(trace, "doc_attention", "jit_step") is None
    ctx = run.Context(tiny_tr, CELL, SEED, 0.1, True)
    assert reader.read(ctx, args) is None          # no trace kept
    ctx.values.update(trace=trace, needed_work=("lm_forward", [130, 35]))
    assert reader.read(ctx, args) is None          # no chip's peaks
    ctx.peaks = peaks.PEAKS["TPU v5 lite"]
    import lm_work_afmoe
    f, b = lm_work_afmoe.attention_pairs(ctx.cfg, [130, 35])
    least = max(f / 197.0e12, b / 819.0e9)
    assert reader.read(ctx, args) == pytest.approx(100.0 * least / 0.4)


def test_the_walk_share_reader_gives_nothing_without_the_counters(tiny_tr):
    """Another program's ``lm.batch`` events lack the walk's counters: the
    reader leaves the metric out and does not raise."""
    from dmlc_core_tpu.telemetry import trace
    spec = tiny_tr.layer_metric("attn.window_walk_share.tr")
    reader = tiny_tr.module("readers", spec["reader"])
    ctx = run.Context(tiny_tr, CELL, SEED, 0.1, True)
    ctx.values["steps"] = 2
    for _ in range(2):
        trace.add_event("lm.batch", tokens=320.0, **{
            "mla.fused_layers": 5.0, "layer_02.assignments": 90.0})
    assert reader.read(ctx, spec["args"]) is None
    for _ in range(2):
        trace.add_event("lm.batch", tokens=320.0, **{
            "attn.key_blocks_window": 1162.0, "attn.key_blocks_full": 1800.0})
    assert reader.read(ctx, spec["args"]) == pytest.approx(64.5555, abs=1e-3)


@pytest.mark.parametrize("control", ["fp8", "half_experts", "no_window",
                                     "no_rope", "no_gate", "no_post_norm"])
def test_each_control_fails(tiny_tr, control):
    ctx = run.Context(tiny_tr, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_tr.module("traffic", ctx.traffic["kind"]).Cell(ctx)
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


def test_the_parent_refuses_the_configuration_at_once(tiny_tr, monkeypatch):
    """A program from before this architecture was computed has to fail the
    cell cleanly in ``setup``: its model looks for ``kv_lora_rank``."""
    from dmlc_core_tpu.models import hybrid_lm

    def parent_init(self, arch):
        int(hybrid_lm.canonical(arch)["kv_lora_rank"])
    monkeypatch.setattr(hybrid_lm.HybridMoELM, "__init__", parent_init)
    with pytest.raises(KeyError, match="kv_lora_rank"):
        run.run_cell(tiny_tr, CELL, SEED, 0.3, trace=False)


def test_work_count_follows_the_configuration():
    import lm_work_afmoe
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    s = lm_work_afmoe.sizes(cfg)
    assert s["gqa"] == 62_914_560 and s["dense"] == 113_246_208
    assert s["moe_resident"] + 256 == 935_067_904     # + the router's bias
    lengths = cfg["corpus"]["doc_lengths"]
    # ISSUE 39's reckoning: 89.35 M pairs a sliding layer, 144.04 M the full
    # one, 12.32 TFLOP of pairs in 56.74 TFLOP and 8.64 GB a batch
    one = lambda kinds: lm_work_afmoe.pairs(                  # noqa: E731
        dict(cfg, layer_types=kinds, num_hidden_layers=1), lengths)
    assert one(["sliding_attention"]) == 89_352_656
    assert one(["full_attention"]) == 144_044_176
    flops, bytes_ = lm_work_afmoe.lm_forward(cfg, lengths)
    pair_flops, _ = lm_work_afmoe.attention_pairs(cfg, lengths)
    assert 12.31e12 < pair_flops < 12.33e12
    assert 56.7e12 < flops < 56.8e12 and 8.64e9 < bytes_ < 8.65e9
    # every layer full: the window takes 38 % off a sliding layer's pairs
    every = lm_work_afmoe.attention_pairs(
        dict(cfg, layer_types=["full_attention"] * 5), lengths)[0]
    assert 17.6e12 < every < 17.8e12
    # a document inside the window counts alike on both kinds of layer
    short = [n for n in lengths if n <= cfg["sliding_window"]]
    assert lm_work_afmoe.pairs(cfg, short) == 5 * sum(
        n * (n + 1) // 2 for n in short)
    # twice the held experts: half an expert more a token and mixture layer
    more = dict(cfg, held_experts=[0, 64])
    extra = lm_work_afmoe.lm_forward(more, lengths)[0] - flops
    assert extra == pytest.approx(
        2 * 32768 * 4 * 0.5 * 3 * 3072 * 3072, rel=1e-9)
