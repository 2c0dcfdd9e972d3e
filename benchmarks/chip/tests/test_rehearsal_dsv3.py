"""``gigachat_l5_score_docs`` end to end at a tiny size on the CPU (hidden
64, 4 heads, ``q_lora_rank`` 32, ``kv_lora_rank`` 16, 16 + 8 / 24 a head,
16 experts in 4 groups of which 2 are kept, top-4, 3 held, one dense layer
and four mixtures, 512 vocabulary rows, float32): once sound, once traced,
and once with each control in the program's place — ``correct`` has to come
out false."""

import json
import os
import shutil

import pytest

import run
from conftest import BENCH, REPO

CELL = "gigachat_l5_score_docs"
CONFIG = "gigachat31_702b_ep16_l5"
SEED = 2 ** 31 + 98765
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "num_attention_heads": 4, "num_key_value_heads": 4,
    "moe_intermediate_size": 32, "n_routed_experts": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "num_experts_held": 3,
    "held_experts": [0, 3], "vocab_size": 1024, "vocab_rows": 512,
    "batch_rows": 4, "nnz_cap": 320, "corpus_docs": 12, "dtype": "float32",
}


@pytest.fixture()
def tiny_gc(tmp_path):
    """A copy of the benchmark whose ``deepseek_v3``-type scorer is cut to
    a toy; positions pass the toy's original context of 64."""
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
    cfg["rope_scaling"]["original_max_position_embeddings"] = 64
    cfg["corpus"].update(categorical_vocab=[cfg["vocab_rows"]],
                         doc_lengths=[35, 130, 64, 91])
    cfg["program_args"].update(features=cfg["vocab_rows"],
                               batch_rows=cfg["batch_rows"],
                               nnz_cap=cfg["nnz_cap"])
    with open(path, "w") as f:
        json.dump(cfg, f)
    spec_path = os.path.join(bench, "workloads", "score_docs_16k.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec.update(reference_pad=1, reference_head_block=128, probe_positions=8)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return manifest.Manifest(root, bench)


def test_cell_runs_and_is_correct(tiny_gc):
    out = run.run_cell(tiny_gc, CELL, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_gc.metrics_for(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"score_docs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == set(tiny_gc.traffic(CELL)["limits"])
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tiny_gc, monkeypatch,
                                              recorded_trace):
    """The recorded v5e trace stands in for the CPU's (it has no device
    plane): every reader finds its input or leaves its metric out."""
    import xplane
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_gc, CELL, SEED, 0.5, trace=True)
    assert out["correct"] is True
    names = {m["name"] for m in tiny_gc.metrics_for(CELL, "per_layer")}
    assert len(names) == 12 and set(out["metrics"]) <= names
    for name in ("feed.host_share.gc", "feed.ring_wait_share.gc",
                 "feed.starved_share.gc", "compiles_in_window.gc",
                 "moe.load_skew.gc", "moe.unserved_share.gc",
                 "moe.dispatch_waste.gc", "step_device_ms.gc",
                 "device_idle_share.gc"):
        assert name in out["metrics"], name
    assert out["metrics"]["moe.load_skew.gc"]["value"] >= 1.0
    assert 0.0 < out["metrics"]["moe.unserved_share.gc"]["value"] < 100.0
    assert out["metrics"]["moe.dispatch_waste.gc"]["value"] >= 1.0
    # no chip, no peaks: a share of the chip's peak is left out, not 0
    assert not any("mfu" in k for k in out["metrics"])


def test_counter_readers_give_nothing_without_the_counters(tiny_gc):
    """A parent commit's ``lm.batch`` events lack ``dispatch_rows``: the
    reader leaves the metric out and does not raise."""
    from dmlc_core_tpu.telemetry import trace
    reader = tiny_gc.module("readers", "moe_counters")
    ctx = run.Context(tiny_gc, CELL, SEED, 0.1, True)
    ctx.values["steps"] = 2
    for _ in range(2):
        trace.add_event("lm.batch", tokens=320.0, **{
            "layer_02.assignments": 90.0, "layer_02.unserved_tokens": 200.0})
    waste = tiny_gc.layer_metric("moe.dispatch_waste.gc")["args"]
    unserved = tiny_gc.layer_metric("moe.unserved_share.gc")["args"]
    assert reader.read(ctx, waste) is None
    assert reader.read(ctx, unserved) == pytest.approx(62.5)
    ctx.values["steps"] = 10 ** 6          # more than the ring holds
    assert reader.read(ctx, unserved) is None


@pytest.mark.parametrize("control", ["fp8", "half_experts", "no_rope",
                                     "plain_rope", "ungrouped"])
def test_each_control_fails(tiny_gc, control):
    ctx = run.Context(tiny_gc, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_gc.module("traffic", ctx.traffic["kind"]).Cell(ctx)
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


def test_setup_trains_the_router_bias_and_nothing_else(tiny_gc):
    """``weights.router_bias_balance``: after ``setup`` every mixture
    layer's ``router_bias`` has left what ``weights_lm.make`` drew, every
    other leaf has not, and the loader stands 2 + 24 batches on."""
    import numpy as np
    import weights_lm
    ctx = run.Context(tiny_gc, CELL, SEED, 0.3, False)
    ctx.fresh_work_dir()
    cell = tiny_gc.module("traffic", ctx.traffic["kind"]).Cell(ctx)
    try:
        cell.setup()
        rule = ctx.cfg["weights"]["router_bias_balance"]
        assert rule == {"batches": 24, "step_first": 0.01,
                        "step_last": 0.0005}
        drawn = weights_lm.make(cell.model.shapes(), SEED,
                                ctx.cfg["weights"], ctx.cfg["dtype"])
        moved = set()
        for name, group in drawn.items():
            for leaf, was in (group.items() if isinstance(group, dict)
                              else [(name, group)]):
                now = cell.params[name][leaf] if isinstance(group, dict) \
                    else cell.params[name]
                if not np.array_equal(np.asarray(was), np.asarray(now)):
                    moved.add(leaf)
                    step = np.abs(np.asarray(now) - np.asarray(was)).max()
                    assert step <= 24 * 0.01
        assert moved == {"router_bias"}
        assert cell.index == (2 + 24) % cell.batches_per_epoch
    finally:
        cell.close()
        shutil.rmtree(ctx.work, ignore_errors=True)


def test_the_parent_refuses_the_configuration_at_once(tiny_gc, monkeypatch):
    """A program from before this architecture was computed has to fail the
    cell cleanly in ``setup``: its model refuses ``q_lora_rank``."""
    from dmlc_core_tpu.models import hybrid_lm

    def parent_init(self, arch):
        raise ValueError("hybrid_moe_lm computes q_lora_rank=None only, the "
                         "architecture says 1536")
    monkeypatch.setattr(hybrid_lm.HybridMoELM, "__init__", parent_init)
    with pytest.raises(ValueError, match="q_lora_rank"):
        run.run_cell(tiny_gc, CELL, SEED, 0.3, trace=False)


def test_work_count_follows_the_configuration():
    import lm_work_dsv3
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    s = lm_work_dsv3.sizes(cfg)
    assert s["mla"] == 132_579_328 and s["dense"] == 396_361_728
    lengths = cfg["corpus"]["doc_lengths"]
    assert sum(lengths) == cfg["nnz_cap"] == 16384
    assert len(lengths) == cfg["batch_rows"]
    assert not any(n % 64 == 0 for n in lengths)
    flops, bytes_ = lm_work_dsv3.lm_forward(cfg, lengths)
    # ISSUE 37's reckoning: 56.2 TFLOP and 8.58 GB a batch
    assert 56.1e12 < flops < 56.3e12
    assert 8.58e9 < bytes_ < 8.59e9
    # twice the held experts: half an expert more a token and mixture layer
    more = dict(cfg, held_experts=[0, 32])
    extra = lm_work_dsv3.lm_forward(more, lengths)[0] - flops
    assert extra == pytest.approx(
        2 * 16384 * 4 * 0.5 * 3 * 7168 * 2048, rel=1e-9)
    # the published three dense layers would each count once
    three = dict(cfg, first_k_dense_replace=3, num_hidden_layers=7)
    assert lm_work_dsv3.lm_forward(three, lengths)[0] - flops == \
        pytest.approx(2 * (2 * 16384 * (s["mla"] + s["dense"])
                           + sum(n * (n + 1) // 2 for n in lengths)
                           * 2 * 64 * 384), rel=1e-9)
