"""The document scorer on a ``brumby``-type architecture at small widths on
the CPU (hidden 64, 4 query heads on 2 key heads of 16, 3 power-retention
layers, QK-norm, plain RoPE at theta 100, a dense SwiGLU at every layer,
no experts; float32), against the plain reference
(``benchmarks/chip/reference_brumby.py``, loaded by its path)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, reference
from dmlc_core_tpu.models import hybrid_lm
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.ops import moe, power_retention
from test_hybrid_lm import (ROWS, edges, make_batch, plain_runs,
                            predict_args, spiced, write_docs)

reference_brumby = reference("brumby")

ARCH = {
    "model_type": "brumby", "hidden_size": 64, "num_hidden_layers": 3,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "max_position_embeddings": 256,
    "rope_theta": 100, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 3,
    "layer_types": ["power_retention"] * 3, "tie_word_embeddings": False,
    "vocab_size": 512, "dtype": "float32",
}
LAYERS = ["layer_01", "layer_02", "layer_03"]
# boundaries inside the jnp form's chunks of 64, a one-token document, one
# document over several chunks
TEMPLATES = {
    "mixed": [70, 33, 129, 1, 90, 60],
    "one_long": [300],
}


def retentive(params, seed=3):
    """``spiced`` weights with each gate's bias ``log u``, ``u`` uniform in
    [2, 200]: a memory of a few to hundreds of tokens, as the benchmark's
    rule gives at its lengths."""
    out = dict(params)
    for n, name in enumerate(LAYERS):
        p = dict(params[name])
        p["decay_rate"] = jnp.log(jax.random.uniform(
            jax.random.PRNGKey(seed + n), p["decay_rate"].shape,
            jnp.float32, 2.0, 200.0))
        out[name] = p
    return out


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(ARCH)


@pytest.fixture(scope="module")
def params(model):
    return retentive(spiced(model.init(jax.random.PRNGKey(0))))


@pytest.fixture(scope="module")
def scorer(model):
    return jax.jit(model.forward_counted), jax.jit(model.probe)


@pytest.fixture(scope="module")
def plain(params):
    return plain_runs(reference_brumby.Reference(ARCH, pad_to=16), params,
                      TEMPLATES)


def test_the_block_is_the_file_s(model):
    """Every layer retains and is dense; QK-norm and the gate's two
    parameters; no output gate, no post-norms, no router."""
    shapes = model.shapes()
    assert [model.mixer(n) for n in range(1, 4)] == ["retention"] * 3
    assert model.retention_layers == {1, 2, 3} and not model.sliding
    assert model.dense_layers == model.layers == 3
    assert model.qk_norm and not model.gate and not model.sandwich
    for name in LAYERS:
        assert set(shapes[name]) == {
            "norm1", "norm2", "wq", "wk", "wv", "wo", "q_head_norm",
            "k_head_norm", "decay_up", "decay_rate", "w_gu", "w_down"}
    first = shapes["layer_01"]
    assert first["wq"] == (64, 64) and first["wk"] == first["wv"] == (64, 32)
    assert first["decay_up"] == (64, 2) and first["decay_rate"] == (2,)
    assert first["w_gu"] == (64, 256)
    assert model.rotations["power_retention"][2] == "retention/qk_norm_rope"


def test_the_benchmark_s_configuration_counts_its_parameters():
    """``brumby_14b_base_l8``: 330 352 904 parameters a layer (62.9 M of
    projections, 267.4 M of SwiGLU, 41 k of gate), 4 198 652 992 in all
    with the embedding and the head over 151 936 rows."""
    m = HybridMoELM(hybrid_lm.load_arch(os.path.join(
        BENCH, "configs", "brumby_14b_base_l8.json")))
    count = lambda group: sum(int(np.prod(s)) for s in group.values())  # noqa: E731
    shapes = m.shapes()
    for n in range(1, 9):
        assert count(shapes[f"layer_{n:02d}"]) == 330_352_904
    layers = {k: v for k, v in shapes.items() if isinstance(v, dict)}
    assert len(layers) == 8
    assert sum(count(g) for g in layers.values()) + count(
        {k: v for k, v in shapes.items() if k not in layers}) \
        == 4_198_652_992
    assert (m.heads, m.kv_heads, m.head_dim) == (40, 8, 128)
    assert m.retention_layers == set(range(1, 9)) and m.dense_layers == 8
    assert (m.dense_width, m.vocab) == (17408, 151936)


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, plain,
                                           template):
    """float32 on both sides, so the gaps are the order of the sums: scores
    are means of log-probabilities (2e-5), logits single sums (1e-4)."""
    lengths = TEMPLATES[template]
    batch, _ = make_batch(lengths)
    total = sum(lengths)
    ref = plain(template)
    scores, counters = scorer[0](params, batch)
    scores = np.asarray(scores)
    np.testing.assert_allclose(scores[:len(lengths)], ref["scores"],
                               atol=2e-5)
    assert (scores[len(lengths):] == 0).all()          # padding rows
    logits, chosen = scorer[1](params, batch, jnp.asarray(edges(lengths)))
    np.testing.assert_allclose(np.asarray(logits), ref["logits"], atol=1e-4)
    assert chosen == {} and ref["chosen"] == {}
    rec = HybridMoELM.counter_record(counters)
    assert rec["tokens"] == total and rec["documents"] == len(lengths)
    assert rec["retention.fused_layers"] == 0.0
    assert "attn.key_blocks_full" not in rec          # no layer attends
    needed = float(power_retention.needed_flops(
        jnp.asarray(lengths), 4, 2, 16))
    done = power_retention.schedule_flops(
        batch["ids"].shape[0], 4, 2, 16, 64, 136, 2 * 136)
    for name in LAYERS:
        assert rec[f"{name}.retention.flops_needed"] == pytest.approx(needed)
        assert rec[f"{name}.retention.flops_done"] == pytest.approx(done)


def test_the_kernel_in_the_forward_agrees(params, plain, monkeypatch):
    """The retention kernel, interpreted, in the layers' place (chunks of
    8 over the 640-token stream): the same scores.  A model of its own, so
    no trace of the fixture's is reused."""
    def kernel(q, k, v, log_g, seg):
        o = power_retention._retention_kernel(q, k, v, log_g, seg, chunk=8,
                                              heads=1, interpret=True)
        return o, jnp.int32(1), jnp.float32(0.0)
    monkeypatch.setattr(hybrid_lm, "power_retention_counted", kernel)
    lengths = TEMPLATES["mixed"]
    batch, _ = make_batch(lengths)
    scores, counters = jax.jit(HybridMoELM(ARCH).forward_counted)(params,
                                                                   batch)
    np.testing.assert_allclose(np.asarray(scores)[:len(lengths)],
                               plain("mixed")["scores"], atol=2e-5)
    assert int(counters["retention.fused_layers"]) == 3


@pytest.mark.parametrize("control", reference_brumby.CONTROLS[1:])
def test_each_control_of_the_reference_is_another_model(params, plain,
                                                        control):
    lengths = TEMPLATES["mixed"]
    _, host = make_batch(lengths)
    planted = reference_brumby.Reference(ARCH, control, pad_to=16).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    assert np.abs(planted - plain("mixed")["scores"]).max() > 1e-3


def test_the_dense_layer_takes_its_products_in_pieces_past_the_budget(
        monkeypatch):
    """Over a quarter of the chip's memory the float32 gate and up are
    taken in halvings of the tokens: the same result."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)
    w_gu = jnp.asarray(rng.normal(size=(64, 256)) / 8, jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(128, 64)) / 11, jnp.float32)
    whole = moe.swiglu(x, w_gu, w_down)
    monkeypatch.setattr(moe, "_PRODUCTS_BYTES", 96 * 256 * 4 // 3)
    pieces = jax.make_jaxpr(moe.swiglu)(x, w_gu, w_down)
    assert "scan" in str(pieces)                       # four pieces of 24
    np.testing.assert_allclose(np.asarray(moe.swiglu(x, w_gu, w_down)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)


def test_canonical_reads_the_brumby_spelling():
    a = hybrid_lm.canonical(ARCH)
    assert a["qk_norm"] is True
    assert not {"first_k_dense_replace", "num_experts",
                *hybrid_lm.AFMOE_BLOCK} - {"qk_norm"} & set(a)
    # the keys decide, not the name the file gives its model type
    renamed = HybridMoELM(dict(ARCH, model_type="renamed"))
    assert renamed.shapes() == HybridMoELM(ARCH).shapes()


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["power_retention", "linear_attention",
                     "power_retention"]),
    ("layer_types", ["power_retention"] * 2),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("attention_bias", True), ("num_key_value_heads", 3)])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        HybridMoELM(dict(ARCH, **{key: value}))


def test_the_reference_is_one_plain_file():
    with open(os.path.join(BENCH, "reference_brumby.py")) as f:
        text = f.read()
    assert "dmlc_core_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_predict_scores_a_brumby_arch_file_through_the_cli(tmp_path):
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.telemetry import trace
    from dmlc_core_tpu.utils import CheckpointManager
    arch = tmp_path / "arch.json"
    arch.write_text(json.dumps(ARCH))
    docs = write_docs(tmp_path / "docs.libsvm", [37, 9, 23, 1, 40, 12, 30])
    argv = predict_args(tmp_path, str(arch))
    p = cli.TrainParams()
    p.init(dict(a.split("=", 1) for a in argv))
    model = cli.MODEL_REGISTRY[p.model](p)
    params = retentive(spiced(model.init(jax.random.PRNGKey(p.seed))))
    CheckpointManager(p.ckpt_dir).save(3, {"params": params},
                                       meta={"model": p.model})
    assert cli.main(argv) == 0
    got = np.loadtxt(f"{tmp_path}/scores.txt")
    assert got.shape == (len(docs),)
    ref = reference_brumby.Reference(ARCH, pad_to=16)
    rp = np.concatenate([[0], np.cumsum([len(d) for d in docs])])
    want = ref.run(params, np.concatenate(docs), rp)["scores"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert len(docs) <= ROWS
    rec = [r["attrs"] for r in trace.recorder.snapshot()
           if r["name"] == "lm.batch"][-1]
    assert rec["documents"] == len(docs) and rec["retention.fused_layers"] == 0
    for layer in LAYERS:
        assert rec[f"{layer}.retention.flops_done"] \
            > rec[f"{layer}.retention.flops_needed"] > 0
