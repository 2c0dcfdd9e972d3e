"""``kimil5_score_docs`` end to end at a tiny size on the CPU (hidden 64,
4 heads of 16, 8 experts top-2 of which 4 are held, 5 layers in the
published pattern, 512 vocabulary rows, float32): once sound, once traced,
once with each control in the program's place — ``correct`` has to come out
false — and once with a fault planted under the timed path."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO, shrink_config

CELL = "kimil5_score_docs"
SEED = 2 ** 31 + 54321


@pytest.fixture()
def tiny_lm(tmp_path):
    """A copy of the benchmark whose document scorer is cut to a toy."""
    import manifest
    root = str(tmp_path)
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shrink_config(os.path.join(bench, "configs",
                               "kimi_linear_48b_ep2_l5.json"))
    spec_path = os.path.join(bench, "workloads", "score_docs.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec.update(reference_pad=1, reference_head_block=128, probe_positions=8)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return manifest.Manifest(root, bench)


def test_cell_runs_and_is_correct(tiny_lm):
    out = run.run_cell(tiny_lm, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_lm.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_lm.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_lm, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_lm, CELL, SEED, 0.5, trace=True)
    assert out["correct"] is True
    names = {m["name"] for m in tiny_lm.metrics_for(CELL, "per_layer")}
    assert set(out["metrics"]) <= names
    # the program's own records are there whatever the device: the feed's
    # shares, the compile count and the experts' load from the counters
    for name in ("feed.host_share.lm", "feed.ring_wait_share.lm",
                 "feed.starved_share.lm", "compiles_in_window.lm",
                 "moe.load_skew.lm", "step_device_ms.lm",
                 "device_idle_share.lm"):
        assert name in out["metrics"], name
    assert out["metrics"]["moe.load_skew.lm"]["value"] >= 1.0
    assert "compile_s" not in out["metrics"]
    # no chip, no peaks: a share of the chip's peak is left out, not 0
    assert not any("mfu" in k for k in out["metrics"])


@pytest.mark.parametrize("control", ["fp8", "half_experts"])
def test_each_control_fails(tiny_lm, control):
    ctx = run.Context(tiny_lm, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_lm.module("traffic", ctx.traffic["kind"]).Cell(ctx)
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


def test_fault_a_document_scored_with_another_s_tokens(tiny_lm, monkeypatch):
    """The scores of two rows swapped under the timed path."""
    from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
    real = HybridMoELM.forward_counted

    def swapped(self, params, batch):
        scores, counters = real(self, params, batch)
        return scores.at[0].set(scores[1]).at[1].set(scores[0]), counters
    monkeypatch.setattr(HybridMoELM, "forward_counted", swapped)
    out = run.run_cell(tiny_lm, CELL, SEED, 0.3, trace=False)
    assert out["correct"] is False
    assert not out["compared"]["score_gap"]["ok"]


def test_work_count_follows_the_configuration():
    import lm_work
    with open(os.path.join(BENCH, "configs",
                           "kimi_linear_48b_ep2_l5.json")) as f:
        cfg = json.load(f)
    assert lm_work.layer_plan(cfg) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    lengths = cfg["corpus"]["doc_lengths"]
    assert sum(lengths) == cfg["nnz_cap"] and len(lengths) == cfg["batch_rows"]
    assert not any(n % 64 == 0 for n in lengths)
    flops, bytes_ = lm_work.lm_forward(cfg, lengths)
    # ISSUE 30's reckoning: 584 M matrix parameters a token, ~41 TFLOP and
    # 8.57 GB a batch
    assert 39e12 < flops < 42e12
    assert 8.5e9 < bytes_ < 8.6e9
    # twice the held experts: exactly one expert more a token and layer
    more = dict(cfg, held_experts=[0, 256])
    extra = lm_work.lm_forward(more, lengths)[0] - flops
    assert extra == pytest.approx(
        2 * 32768 * 4 * 4 * 3 * 2304 * 1024, rel=1e-9)
