"""``phi4flash_score_docs`` end to end at a tiny size on the CPU (hidden 64,
4 query heads on 2 key heads of 16, ``d_state`` 4, 8 layers keeping the
SambaY pattern, a window of 16 under documents of 35-130 tokens, 512
vocabulary rows, float32): once sound, once traced, and once with each
control in the program's place — ``correct`` has to come out false.
``conftest.SHRINK`` leaves a ``sambay_lm`` configuration alone: this file
shrinks its own."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO

CELL = "phi4flash_score_docs"
CONFIG = "phi4_mini_flash_3.8b"
TRAFFIC = "score_docs_32k_sambay"
SEED = 2 ** 31 + 24681
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 16,
    "mamba_d_state": 4, "mamba_dt_rank": 4, "vocab_size": 512,
    "vocab_rows": 512, "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12,
    "dtype": "float32",
}


@pytest.fixture()
def tiny_pf(tmp_path):
    """A copy of the benchmark whose SambaY scorer is cut to a toy; three
    of its four documents pass the toy's window."""
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
    spec_path = os.path.join(bench, "workloads", TRAFFIC + ".json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec.update(reference_pad=1, reference_head_block=128, probe_positions=8)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return manifest.Manifest(root, bench)


def test_the_committed_files_are_the_issue_s(tiny_pf):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", TRAFFIC + ".json")) as f:
        spec = json.load(f)
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["reduced"] == tiny_pf.configs[CONFIG]["reduced"] \
        == ["corpus_docs"]
    assert cfg["model"] == cfg["program_args"]["model"] == "sambay_lm"
    assert cfg["vocab_rows"] == cfg["program_args"]["features"] == 200064
    lengths = cfg["corpus"]["doc_lengths"]
    assert lengths == [328, 558, 952, 1624, 2770, 4724, 8058, 13754]
    assert sum(lengths) == cfg["nnz_cap"] == 32768
    assert len(lengths) == cfg["batch_rows"] == 8
    assert len([n for n in lengths if n > 4096]) == 3
    assert len([n for n in lengths if n > cfg["sliding_window"]]) == 7
    assert cfg["corpus_docs"] == 512
    assert spec["kind"] == "score_docs_sambay"
    assert {k: spec[k] for k in (
        "warm_batches", "trace_seconds", "probe_positions", "reference_pad",
        "reference_head_block")} == {
        "warm_batches": 2, "trace_seconds": 6, "probe_positions": 32,
        "reference_pad": 1024, "reference_head_block": 1024}
    assert set(spec["limits"]) == {"score_gap", "score_rms_gap", "logit_gap",
                                   "logit_rms_gap", "rows_missing"}
    assert spec["limits"]["rows_missing"] == 0
    assert tiny_pf.cells[CELL]["chips"] == 1


def test_cell_runs_and_is_correct(tiny_pf):
    out = run.run_cell(tiny_pf, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_pf.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_pf.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_pf, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    real = xplane.read
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_pf, CELL, SEED, 0.5, trace=True)
    assert xplane.read is not real       # the kind put back what it found
    assert out["correct"] is True
    names = {m["name"] for m in tiny_pf.metrics_for(CELL, "per_layer")}
    assert len(names) == 12 and all(n.endswith(".pf") for n in names)
    assert set(out["metrics"]) <= names
    for name in ("feed.host_share.pf", "feed.ring_wait_share.pf",
                 "feed.starved_share.pf", "compiles_in_window.pf",
                 "step_device_ms.pf", "device_idle_share.pf",
                 "attn.window_walk_share.pf"):
        assert name in out["metrics"], name
    # 320 tokens are two blocks of 256: a walk of the window's is the
    # whole document's wherever no document holds a whole block
    assert out["metrics"]["attn.window_walk_share.pf"]["value"] == 100.0
    # no chip, no peaks: a share of a peak or a roofline is left out, not 0
    assert not any("mfu" in k or "roofline" in k for k in out["metrics"])


@pytest.mark.parametrize("kernel,work", [
    ("selective_scan", "lm_work_sambay.scan_work"),
    ("diff_attention", "lm_work_sambay.attention_pairs")])
def test_the_kernels_rooflines_read_their_own_events(tiny_pf, kernel, work):
    """Each share reads its own kernel's events inside whole runs of the
    program against its own needed work; the other kernel's events, and
    the parent's ``doc_attention``, are not its."""
    import lm_work_sambay
    import peaks
    name = {"selective_scan": "ssm_scan_roofline.pf",
            "diff_attention": "diff_attention_roofline.pf"}[kernel]
    spec = tiny_pf.layer_metric(name)
    assert spec == {"reader": "kernel_roofline",
                    "args": {"kernel": kernel, "module": "jit_forward_counted",
                             "work": work}}
    reader = tiny_pf.module("readers", spec["reader"])
    dev = "/device:TPU:0"
    trace = {
        "host": {"bench.window": [(1.0, 4.0)]},
        "modules": {dev: [("jit_forward_counted(7)", 1.5, 2.4),
                          ("jit_forward_counted(7)", 2.5, 3.4)]},
        "devices": {dev: [
            (f"%{kernel}.3 = f32[1] custom-call(...)", 1.6, 1.7),
            (f"%{kernel}.4 = f32[1] custom-call(...)", 2.6, 2.9),
            ("%doc_attention.1 = f32[1] custom-call(...)", 1.8, 2.3),
            ("%selective_scan_x = f32[1] custom-call(...)", 2.0, 2.1)]},
    }
    ctx = run.Context(tiny_pf, CELL, SEED, 0.1, True)
    ctx.values.update(trace=trace, needed_work=("lm_forward", [130, 35]))
    ctx.peaks = peaks.PEAKS["TPU v5 lite"]
    f, b = getattr(lm_work_sambay, work.split(".")[1])(ctx.cfg, [130, 35])
    least = max(f / 197.0e12, b / 819.0e9)
    assert reader.read(ctx, spec["args"]) == pytest.approx(
        100.0 * least / 0.2)


@pytest.mark.parametrize("control", ["fp8", "no_reset", "no_lambda"])
def test_each_control_fails(tiny_pf, control):
    ctx = run.Context(tiny_pf, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_pf.module("traffic", ctx.traffic["kind"]).Cell(ctx)
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


def test_the_parent_refuses_the_configuration_at_once(tiny_pf, monkeypatch):
    """A program from before this model was registered fails the cell
    cleanly in ``setup``: ``model=sambay_lm`` is no name it knows."""
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.utils import ParamError
    entries = dict(cli.MODEL_REGISTRY._entries)
    entries.pop("sambay_lm")
    monkeypatch.setattr(cli.MODEL_REGISTRY, "_entries", entries)
    with pytest.raises(ParamError, match="sambay_lm"):
        run.run_cell(tiny_pf, CELL, SEED, 0.3, trace=False)


def test_work_count_follows_the_configuration():
    import lm_work_sambay
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    lengths = cfg["corpus"]["doc_lengths"]
    kinds = lm_work_sambay.kinds(cfg)
    assert kinds.count("mamba") == 9 and kinds.count("swa") == 8
    assert kinds.count("full") == 1 and kinds.count("cross") == 7 \
        and kinds.count("gmu") == 7
    # ISSUE 43's reckoning: 144.0 M pairs a whole-document layer, 15.7 M a
    # windowed one; 272.0 TFLOP a batch, 7.71 GB of parameters
    assert lm_work_sambay.pairs(cfg, lengths) == {"full": 144_044_176,
                                                  "window": 15_747_524}
    flops, bytes_ = lm_work_sambay.lm_forward(cfg, lengths)
    assert 271.9e12 < flops < 272.1e12
    assert bytes_ - 4 * 32768 - 4 * 8 == 2 * 3_852_562_944
    pair_flops, _ = lm_work_sambay.attention_pairs(cfg, lengths)
    assert pair_flops == (8 * 144_044_176 + 8 * 15_747_524) * 15_360
    scan_flops, scan_bytes = lm_work_sambay.scan_work(cfg, lengths)
    assert scan_bytes == 9 * 32768 * (5120 * 10 + 32 * 4)
    assert scan_flops == 9 * 32768 * 5120 * (7 * 16 + 3)
