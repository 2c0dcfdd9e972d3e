"""The SambaY document scorer (``models/sambay_lm.py``) at small widths on
the CPU (hidden 64, 4 query heads on 2 key heads of 16, ``d_state`` 4, a
window of 16 under documents of up to 600 tokens, 8 layers keeping the
pattern — Mamba and windowed differential attention, the memory's Mamba
layer, the whole-document layer whose keys and values are shared, a GMU and
a cross-attention layer; float32), against the plain reference
(``benchmarks/chip/reference_sambay.py``, loaded by its path)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference
from dmlc_core_tpu.models.sambay_lm import SambaYLM
from test_hybrid_lm import (ROWS, TEMPLATES, edges, make_batch, plain_runs,
                            predict_args, write_docs)

reference_sambay = reference("sambay")

WINDOW = 16
PAD = 64        # documents filled up to 64 rows: fewer shapes, as on the chip
ARCH = {
    "model_type": "phi4flash", "hidden_size": 64, "num_hidden_layers": 8,
    "layer_norm_eps": 1e-5, "hidden_act": "silu", "num_attention_heads": 4,
    "num_key_value_heads": 2, "sliding_window": WINDOW, "mb_per_layer": 2,
    "intermediate_size": 128, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "vocab_size": 512,
    "mamba_d_state": 4, "embd_pdrop": 0, "resid_pdrop": 0,
    "dtype": "float32",
}
KINDS = ["mamba", "swa", "mamba", "swa", "memory", "full", "gmu", "cross"]


def spiced(params, seed=5):
    """``init``'s zeros and ones made random, so every bias, norm weight,
    ``D`` and the steps are exercised."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for key, (path, leaf) in zip(keys, flat):
        name = path[-1].key
        noise = jax.random.normal(key, leaf.shape, leaf.dtype)
        if name.endswith("bias") or name in ("b_q", "b_qkv", "bo"):
            leaf = leaf + 0.1 * noise
        elif "norm" in name or name in ("subln", "D", "A_log"):
            leaf = leaf + 0.2 * noise
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def model():
    return SambaYLM(ARCH)


@pytest.fixture(scope="module")
def params(model):
    return spiced(model.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def scorer(model):
    return jax.jit(model.forward_counted), jax.jit(model.probe)


@pytest.fixture(scope="module")
def plain(params):
    return plain_runs(reference_sambay.Reference(ARCH, pad_to=PAD), params,
                      TEMPLATES)


def test_the_layers_keep_the_pattern(model):
    assert [model.kind(n) for n in range(8)] == KINDS
    shapes = model.shapes()
    assert shapes["embed"] == (512, 64) and "head" not in shapes
    assert set(shapes["layer_04"]) >= {"w_in", "conv", "w_x", "w_dt",
                                       "A_log", "D", "wo"}
    assert shapes["layer_05"]["w_qkv"] == (64, (4 + 2 * 2) * 16)
    assert shapes["layer_07"]["w_q"] == (64, 64) \
        and "w_qkv" not in shapes["layer_07"]
    assert set(shapes["layer_06"]) == {"norm1", "norm1_bias", "norm2",
                                       "norm2_bias", "w_gu", "w_down",
                                       "w_g", "wo"}
    assert (model.d_inner, model.d_state, model.dt_rank) == (128, 4, 4)


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, plain,
                                           template):
    """float32 on both sides, so the gaps are the order of the sums (a
    chunked associative scan against a token-by-token one, a blocked walk
    against dense maps): scores 2e-5, logits 1e-4."""
    lengths = TEMPLATES[template]
    assert max(max(t) for t in TEMPLATES.values()) > 8 * WINDOW
    batch, host = make_batch(lengths)
    total = sum(lengths)
    positions = edges(lengths)
    ref = plain(template)
    scores, counters = scorer[0](params, batch)
    scores = np.asarray(scores)
    np.testing.assert_allclose(scores[:len(lengths)], ref["scores"],
                               atol=2e-5)
    assert (scores[len(lengths):] == 0).all()          # padding rows
    logits, chosen = scorer[1](params, batch, jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(logits), ref["logits"], atol=1e-4)
    assert chosen == {} and ref["chosen"] == ref["margin"] == {}
    rec = SambaYLM.counter_record(counters)
    assert rec["tokens"] == total and rec["documents"] == len(lengths)
    # the CPU: the jnp scan and walk
    assert rec["ssm.fused_layers"] == rec["dattn.fused_layers"] == 0.0
    assert rec["attn.key_blocks_window"] == 5.0
    assert rec["attn.key_blocks_full"] == (6.0 if template == "one_long"
                                           else 5.0)


@pytest.mark.parametrize("control", reference_sambay.CONTROLS[1:])
def test_each_control_of_the_reference_is_another_model(params, plain,
                                                        control):
    lengths = TEMPLATES["mixed"]
    _, host = make_batch(lengths)
    planted = reference_sambay.Reference(ARCH, control, pad_to=PAD).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    assert np.abs(planted - plain("mixed")["scores"]).max() > 1e-3


def test_the_scan_s_state_resets_at_every_document(model, params, scorer):
    """A document scores the same behind another one as alone, in the
    program; the reference that hands the state on does not."""
    batch, host = make_batch([300, 129])
    alone, _ = make_batch([129], seed=7)
    tail = np.asarray(host["ids"])[300:429]
    alone = dict(alone, ids=alone["ids"].at[:129].set(jnp.asarray(tail)))
    both = np.asarray(scorer[0](params, batch)[0])
    single = np.asarray(scorer[0](params, alone)[0])
    assert abs(both[1] - single[0]) < 1e-5
    leaky = reference_sambay.Reference(ARCH, "no_reset", pad_to=PAD).run(
        params, host["ids"], host["row_ptr"][:3])["scores"]
    assert abs(leaky[1] - both[1]) > 1e-3 and abs(leaky[0] - both[0]) < 1e-5


WIDE = dict(ARCH, num_hidden_layers=12)        # three GMUs, three crosses


@pytest.mark.parametrize("reader", [8, 9, 10, 11])
def test_the_memory_and_the_shared_kv_reach_every_cross_layer(reader):
    """Silence the source's own output and every cross-decoder layer's but
    ``reader``'s: a change of the memory layer's ``W_in`` (a GMU reader) or
    of the shared layer's ``W_qkv`` (a cross-attention reader) moves the
    hidden states through ``reader`` alone."""
    model = SambaYLM(WIDE)
    assert [model.kind(n) for n in range(6, 12)] == [
        "memory", "full", "gmu", "cross", "gmu", "cross"]
    params = spiced(model.init(jax.random.PRNGKey(1)))
    source, weight = (("layer_06", "w_in") if model.kind(reader) == "gmu"
                      else ("layer_07", "w_qkv"))
    zero = lambda p, names: dict(p, **{n: jnp.zeros_like(p[n])  # noqa: E731
                                        for n in names if n in p})
    params[source] = zero(params[source], ("wo", "bo"))
    for n in range(8, 12):
        if n != reader:
            name = f"layer_{n:02d}"
            params[name] = zero(params[name], ("wo", "bo"))
    batch, _ = make_batch([70, 33, 129])
    hidden = jax.jit(lambda p: model._hidden(p, batch)[0])
    moved = dict(params[source])
    moved[weight] = moved[weight] * 1.5
    base, after = hidden(params), hidden(dict(params, **{source: moved}))
    assert float(jnp.abs(after - base).max()) > 1e-3
    silent = dict(params)
    silent[f"layer_{reader:02d}"] = zero(params[f"layer_{reader:02d}"],
                                         ("wo", "bo"))
    quiet = hidden(dict(silent, **{source: moved}))
    assert float(jnp.abs(quiet - hidden(silent)).max()) == 0.0


def test_the_benchmark_s_configuration_is_the_issue_s_arithmetic():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "chip", "configs",
                           "phi4_mini_flash_3.8b.json")) as f:
        cfg = json.load(f)
    m = SambaYLM(cfg)
    kinds = [m.kind(n) for n in range(32)]
    assert kinds.count("mamba") + kinds.count("memory") == 9
    assert kinds.count("swa") == 8 and kinds.index("memory") == 16
    assert kinds.index("full") == 17 and kinds.count("full") == 1
    assert kinds.count("gmu") == kinds.count("cross") == 7
    assert (m.head_dim, m.d_inner, m.d_state, m.dt_rank, m.window) == (
        64, 5120, 16, 160, 512)
    sizes = jax.tree.leaves(m.shapes(), is_leaf=lambda x: isinstance(x,
                                                                      tuple))
    total = sum(int(np.prod(s)) for s in sizes)
    # ISSUE 43's table: 3.852 G parameters, 7.71 GB in bfloat16
    assert 3.850e9 < total < 3.854e9
    assert m.vocab == 200_064 == cfg["program_args"]["features"]


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("mb_per_layer", 3),
    ("num_hidden_layers", 10), ("hidden_act", "gelu")])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key if key != "num_hidden_layers"
                       else "multiple of 4"):
        SambaYLM(dict(ARCH, **{key: value}))


def test_predict_scores_a_sambay_arch_file_through_the_cli(tmp_path):
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.telemetry import trace
    from dmlc_core_tpu.utils import CheckpointManager
    arch = tmp_path / "arch.json"
    arch.write_text(json.dumps(ARCH))
    docs = write_docs(tmp_path / "docs.libsvm",
                      [70, 33, 129, 1, 90, 60, 200, 57, 12, 300, 41])
    argv = [a.replace("hybrid_moe_lm", "sambay_lm")
            for a in predict_args(tmp_path, str(arch))]
    p = cli.TrainParams()
    p.init(dict(a.split("=", 1) for a in argv))
    assert p.model == "sambay_lm"
    model = cli.MODEL_REGISTRY[p.model](p)
    params = spiced(model.init(jax.random.PRNGKey(p.seed)))
    CheckpointManager(p.ckpt_dir).save(3, {"params": params},
                                       meta={"model": p.model})
    assert cli.main(argv) == 0
    got = np.loadtxt(f"{tmp_path}/scores.txt")
    assert got.shape == (len(docs),)
    ref = reference_sambay.Reference(ARCH, pad_to=PAD)
    for lo in range(0, len(docs), ROWS):
        part = docs[lo:lo + ROWS]
        rp = np.concatenate([[0], np.cumsum([len(d) for d in part])])
        want = ref.run(params, np.concatenate(part), rp)["scores"]
        np.testing.assert_allclose(got[lo:lo + len(part)], want, atol=2e-5)
    recs = [r["attrs"] for r in trace.recorder.snapshot()
            if r["name"] == "lm.batch"][-2:]
    assert sum(r["documents"] for r in recs) == len(docs)
    for r in recs:
        assert r["ssm.fused_layers"] == r["dattn.fused_layers"] == 0
        assert 0 < r["attn.key_blocks_window"] <= r["attn.key_blocks_full"]
