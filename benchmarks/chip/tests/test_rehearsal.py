"""Every cell end to end at a tiny size, the harness's look for a chip
skipped (``run_cell`` is what ``main`` calls after it): once sound, once
with the control in the program's place, once with each fault the cell can
have planted under the timed path — ``correct`` has to come out false."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run
from conftest import BENCH, REPO

# the committed cells and the one under proposed/ (conftest merges it in)
CELLS = ["fm24_train_text", "dcn24_score_text", "dcn24_serve_open"]
SEED = 2 ** 31 + 12345          # the driver's seeds are large


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny_tree, cell):
    out = run.run_cell(tiny_tree, cell, SEED, 0.5, trace=False)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_tree.metrics_for(cell, "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    for c in out["compared"].values():
        assert c["value"] <= c["limit"]
    json.dumps(out)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(tiny_tree, cell, monkeypatch,
                                              recorded_trace):
    """``--trace 1``: the CPU's own trace has no device plane, so the
    recorded v5e trace stands in for it; every reader finds its input or
    leaves its metric out, and none returns 0 for a share."""
    import xplane
    monkeypatch.setattr(xplane, "read", lambda path: recorded_trace)
    out = run.run_cell(tiny_tree, cell, SEED, 0.5, trace=True)
    assert out["correct"] is True
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
    names = {m["name"] for m in tiny_tree.metrics_for(cell, "per_layer")}
    assert set(out["metrics"]) <= names and out["metrics"]
    # no chip, no peaks: a share of the chip's peak is left out, not 0
    assert not any("mfu" in k for k in out["metrics"])


@pytest.mark.parametrize("stamps,per,tail", [
    ([], [], 0),
    ([(0.4, 5), (0.9, 5), (1.2, 7), (2.999, 1), (3.01, 4)], [10, 7, 1], 4),
    ([(0.5, 3)], [], 3),
    ([(1.5, 3), (2.5, 3)], [0, 3], 3),
])
def test_per_second_rows_of_a_window(tiny_tree, stamps, per, tail):
    st = tiny_tree.module("traffic", "score_text")
    t0 = 1000.25
    got = st.per_second([(t0 + t, n) for t, n in stamps], t0)
    assert got == (per, tail)
    assert sum(got[0]) + got[1] == sum(n for _, n in stamps)


def test_score_window_says_its_rate_second_by_second(tiny_tree):
    """The window's own stamps: every row read back lands in one whole
    second or in the part of a second the window ended in, and the line
    goes to standard error (that no metric carries it,
    ``test_cell_runs_and_is_correct`` holds)."""
    said = []
    ctx = run.Context(tiny_tree, "dcn24_score_text", SEED, 2.2, False)
    ctx.say = said.append
    ctx.fresh_work_dir()
    cell = tiny_tree.module("traffic", "score_text").Cell(ctx)
    try:
        cell.setup()
        cell.window(2.2)
    finally:
        cell.close()
    v = ctx.values
    assert len(v["per_second_rows"]) == 2 and min(v["per_second_rows"]) > 0
    assert sum(v["per_second_rows"]) + v["tail_rows"] == v["attempted"]
    line = [m for m in said if "rows in each whole second" in m]
    assert len(line) == 1 and "slowest" in line[0] and "fastest" in line[0]


def test_control_fails_training(tiny_tree):
    import jax.numpy as jnp
    tt = tiny_tree.module("traffic", "train_text")
    ctx = run.Context(tiny_tree, "fm24_train_text", SEED, 0.1, False)
    ctx.fresh_work_dir()
    import textfeed
    feed = textfeed.TextFed(ctx)
    try:
        lr = float(feed.p.lr)
        ref = tt.reference_readings(ctx, feed, 3, lr)
        ctl = tt.reference_readings(ctx, feed, 3, lr, dtype=jnp.bfloat16)
        got = tt.gaps(*ctl, *ref)
        limits = ctx.traffic["limits"]
        assert any(got[k] > limits[k] for k in limits), (got, limits)
        half = tt.gaps(*tt.reference_readings(ctx, feed, 3, lr,
                                              half_batch=True), *ref)
        assert any(half[k] > limits[k] for k in limits), (half, limits)
    finally:
        feed.close()


@pytest.mark.parametrize("cell", ["dcn24_score_text", "dcn24_serve_open"])
def test_control_fails_scoring(tiny_tree, cell):
    import jax.numpy as jnp
    import reference
    import textfeed
    ctx = run.Context(tiny_tree, cell, SEED, 0.1, False)
    ctx.fresh_work_dir()
    feed = textfeed.TextFed(ctx, write_file=False)
    params = feed.make_weights()
    ids, vals, _ = feed.corpus.rows_padded(0, 1024, feed.features)
    a = reference.scores("dcn", params, ids, vals)
    b = reference.scores("dcn", params, ids, vals, jnp.bfloat16)
    assert float(np.max(np.abs(a - b))) > ctx.traffic["limits"]["score_gap"]


def test_fault_state_unchanged(tiny_tree, monkeypatch):
    from dmlc_core_tpu.models import train as program_train
    real = program_train.make_train_step

    def broken(model, opt, *a, **kw):
        step = real(model, opt, *a, donate=False, **kw)
        return lambda params, opt_state, batch: (
            params, opt_state, step(params, opt_state, batch)[2])
    monkeypatch.setattr(program_train, "make_train_step", broken)
    out = run.run_cell(tiny_tree, "fm24_train_text", SEED, 0.3, trace=False)
    assert out["correct"] is False
    assert out["compared"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_batch_left_out(tiny_tree, monkeypatch):
    """Half of every batch gets weight 0: the loss is the mean over the
    rest."""
    import jax.numpy as jnp
    from dmlc_core_tpu.models.sparse import FactorizationMachine
    real = FactorizationMachine.loss

    def half(self, params, batch):
        w = batch["weights"]
        keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return real(self, params, dict(batch, weights=w * keep))
    monkeypatch.setattr(FactorizationMachine, "loss", half)
    out = run.run_cell(tiny_tree, "fm24_train_text", SEED, 0.3, trace=False)
    assert out["correct"] is False


def test_fault_answer_altered_scoring(tiny_tree, monkeypatch):
    cell = "dcn24_score_text"
    from dmlc_core_tpu.models.dcn import DCNv2
    real = DCNv2.forward
    monkeypatch.setattr(DCNv2, "forward", lambda self, p, b:
                        real(self, p, b).at[3].add(0.05))
    out = run.run_cell(tiny_tree, cell, SEED, 0.3, trace=False)
    assert out["correct"] is False


def test_fault_answer_altered_serving(tiny_tree, monkeypatch):
    cell = "dcn24_serve_open"
    from dmlc_core_tpu.serving.engine import InferenceEngine
    real = InferenceEngine.predict

    def altered(self, ids, vals, row_ptr=None):
        out = np.array(real(self, ids, vals, row_ptr))
        out[0] = 1.0 - out[0]
        return out
    monkeypatch.setattr(InferenceEngine, "predict", altered)
    out = run.run_cell(tiny_tree, cell, SEED, 0.5, trace=False)
    assert out["correct"] is False


def test_refuses_without_a_tpu():
    """On a machine with no accelerator: exit code 2, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "fm24_train_text", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert r.stdout.strip() == "" and "refusing" in r.stderr
