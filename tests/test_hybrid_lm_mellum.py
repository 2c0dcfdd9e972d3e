"""The document scorer on a ``mellum``-type architecture at small widths on
the CPU (hidden 64, 4 query heads on 2 key heads of 16, 4 layers ``S S S F``
with a window of 8 under documents of up to 40 tokens, plain RoPE on the
sliding layers and YaRN on the full one, 8 experts top-2 by softmax, all
held, no shared expert; float32), against the plain reference
(``benchmarks/chip/reference_mellum.py``, loaded by its path)."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.models import hybrid_lm
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.ops import moe
from test_hybrid_lm import (ARCH as KIMI_ARCH, ROWS, make_batch,
                            predict_args, spiced, write_docs)
from test_hybrid_lm_afmoe import ARCH as AFMOE_ARCH
from test_hybrid_lm_dsv3 import ARCH as DSV3_ARCH

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmarks", "chip")


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_mellum", os.path.join(BENCH, "reference_mellum.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference_mellum = _load_reference()

WINDOW = 8
# a YaRN that bends inside 40 tokens: theta 100 and an original context of
# 32 put the ramp on columns 0-3 of 8, so the slow columns turn a quarter
# as fast, and attention_factor 0.1 ln 4 + 1 as the published formula
YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}
ARCH = {
    "model_type": "mellum", "hidden_size": 64, "num_hidden_layers": 4,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": WINDOW, "use_sliding_window": True,
    "max_window_layers": 0, "max_position_embeddings": 128,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 1
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 8,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 100}},
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "tie_word_embeddings": False, "vocab_size": 512, "dtype": "float32",
}
MIXTURES = ["layer_01", "layer_02", "layer_03", "layer_04"]
TEMPLATES = {
    "mixed": [37, 9, 23, 1, 40, 12],
    "one_long": [40],
    "many_short": [5, 1, 2, 8, 9, 7, 3, 30],
}


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(ARCH)


@pytest.fixture(scope="module")
def params(model):
    return spiced(model.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def scorer(model):
    return jax.jit(model.forward_counted), jax.jit(model.probe)


def yarn_by_transformers(d, theta, factor, original, beta_fast, beta_slow):
    """``_compute_yarn_parameters`` of transformers, written out as it
    reads there: (inverse frequencies, the correction range)."""
    def find_correction_dim(num_rotations):
        return (d * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(theta))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), d - 1)
    pos_freqs = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    linear = (np.arange(d // 2, dtype=np.float64) - low) / (
        (high + 0.001 if low == high else high) - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    inv = inv_freq_interpolation * (1 - extrapolation_factor) \
        + inv_freq_extrapolation * extrapolation_factor
    return inv, (low, high)


def test_the_block_is_the_file_s(model):
    """No output gate, no QK-norm, no post-norms, no router bias, no shared
    expert, no dense layer: only the published parameters."""
    shapes = model.shapes()
    assert [model.mixer(n) for n in range(1, 5)] == ["gqa"] * 4
    assert model.sliding == {1, 2, 3} and model.window == WINDOW
    assert model.dense_layers == 0 and model.shared == 0
    assert model.scoring == "softmax" and model.route_scale == 1.0
    assert model.embed_scale == 1.0 and not model.sandwich
    for n in range(1, 5):
        assert set(shapes[f"layer_{n:02d}"]) == {
            "norm1", "norm2", "wq", "wk", "wv", "wo", "router", "e_gu",
            "e_down"}
    first = shapes["layer_01"]
    assert first["wq"] == (64, 64) and first["wk"] == first["wv"] == (64, 32)
    assert first["e_gu"] == (8, 64, 64) and first["e_down"] == (8, 32, 64)
    assert first["router"] == (64, 8)


def test_the_benchmark_s_configuration_counts_its_parameters():
    """``mellum2_12b_a2.5b_l8``: 21 233 664 parameters of attention a
    layer, 417 747 456 a layer, 3 794 966 784 in all; two full layers of
    eight rotate by YaRN."""
    m = HybridMoELM(hybrid_lm.load_arch(os.path.join(
        BENCH, "configs", "mellum2_12b_a2.5b_l8.json")))
    count = lambda group: sum(int(np.prod(s)) for s in group.values())  # noqa: E731
    shapes = m.shapes()
    layer = shapes["layer_01"]
    assert count({k: layer[k] for k in ("wq", "wk", "wv", "wo")}) \
        == 21_233_664
    assert layer["router"] == (2304, 64) and layer["e_gu"] == (64, 2304, 1792)
    for n in range(1, 9):
        assert count(shapes[f"layer_{n:02d}"]) == 417_747_456
    layers = {k: v for k, v in shapes.items() if isinstance(v, dict)}
    assert sum(count(g) for g in layers.values()) + count(
        {k: v for k, v in shapes.items() if k not in layers}) \
        == 3_794_966_784
    assert (m.heads, m.kv_heads, m.head_dim, m.window) == (32, 4, 128, 1024)
    assert m.sliding == {1, 2, 3, 5, 6, 7} and m.yarn_layers == 2
    assert (m.top_k, m.experts, m.held, m.vocab) == (8, 64, (0, 64), 98304)


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, template):
    """float32 on both sides, so the gaps are the order of the sums: scores
    are means of log-probabilities (2e-5), logits single sums (1e-4); a
    choice is compared where the reference's margin between the k-th and
    the (k+1)-th softmax score is clear of that noise (1e-5)."""
    lengths = TEMPLATES[template]
    assert max(lengths) > 3 * WINDOW
    batch, host = make_batch(lengths)
    total = sum(lengths)
    positions = sorted({0, total - 1} | set(np.cumsum(lengths)[:-1].tolist())
                       | set((np.cumsum(lengths) - 1).tolist()))
    ref = reference_mellum.Reference(ARCH).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1], positions)
    scores, counters = scorer[0](params, batch)
    scores = np.asarray(scores)
    np.testing.assert_allclose(scores[:len(lengths)], ref["scores"],
                               atol=2e-5)
    assert (scores[len(lengths):] == 0).all()          # padding rows
    logits, chosen = scorer[1](params, batch, jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(logits), ref["logits"], atol=1e-4)
    assert sorted(ref["chosen"]) == MIXTURES
    for name, want in ref["chosen"].items():
        clear = ref["margin"][name] > 1e-5
        assert clear.mean() > 0.9
        got = np.sort(np.asarray(chosen[name])[:total], -1)
        assert (got[clear] == np.sort(want, -1)[clear]).all()
    rec = HybridMoELM.counter_record(counters)
    assert rec["tokens"] == total and rec["documents"] == len(lengths)
    assert rec["gqa.yarn_layers"] == 1.0
    assert rec["gqa.fused_layers"] == rec["moe.fused_combines"] == 0.0
    assert rec["moe.gather_combines"] == len(MIXTURES)      # by token
    for name in MIXTURES:            # every expert held: every assignment
        assert rec[f"{name}.assignments"] == 2 * total
        assert rec[f"{name}.unserved_tokens"] == 0.0


@pytest.mark.parametrize("factor", [16.0, None])
def test_yarn_follows_transformers_at_the_published_numbers(factor):
    """head 128, theta 5e5, factor 16 from 8192, beta 32 / 1: the ramp runs
    from column 18 to 35, so columns 0-18 keep their frequency, 35-63 are a
    sixteenth of it, and those between lie between; the attention factor
    is the file's 1.27726, or 0.1 ln 16 + 1 where the file gives none."""
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1}
    if factor:
        rope["attention_factor"] = 1.2772588722239782
    want, (low, high) = yarn_by_transformers(128, 5e5, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    freqs, scale = hybrid_lm.rope_of_kind(128, rope)
    np.testing.assert_allclose(freqs, want, rtol=1e-12)
    assert scale == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    ref_freqs, ref_scale = reference_mellum.rope(128, rope)
    np.testing.assert_allclose(ref_freqs, want, rtol=1e-12)
    assert ref_scale == pytest.approx(scale, abs=1e-12)
    plain = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(want[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(want[35:], plain[35:] / 16, rtol=1e-12)
    between = want[19:35] / plain[19:35]
    assert ((between < 1) & (between > 1 / 16)).all()
    assert (np.diff(between) < 0).all()


def test_sliding_layers_turn_plainly_and_full_layers_by_yarn(model, params):
    """Each kind its own rotation: the program with the full layers on
    plain RoPE is the reference's ``plain_rope_full`` control, and with the
    attention factor at 1 its ``no_attention_factor`` control; the sound
    reference is neither."""
    freqs, scale, scope = model.rotations["sliding_attention"]
    assert scope == "gqa/rope" and scale == 1.0
    np.testing.assert_allclose(freqs, 100.0 ** (-np.arange(8) / 8),
                               rtol=1e-12)
    freqs, scale, scope = model.rotations["full_attention"]
    assert scope == "gqa/rope_yarn"
    assert scale == pytest.approx(YARN["attention_factor"])
    want, _ = yarn_by_transformers(16, 100, 4, 32, 32, 1)
    np.testing.assert_allclose(freqs, want, rtol=1e-12)
    lengths = TEMPLATES["mixed"]
    batch, host = make_batch(lengths)
    run = lambda c: reference_mellum.Reference(ARCH, c).run(   # noqa: E731
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    sound = run(None)
    for control, rope in (
            ("plain_rope_full", {"rope_type": "default", "rope_theta": 100}),
            ("no_attention_factor", dict(YARN, attention_factor=1.0))):
        other = HybridMoELM(dict(ARCH, rope_parameters=dict(
            ARCH["rope_parameters"], full_attention=rope)))
        got = np.asarray(other.forward(params, batch))[:len(lengths)]
        np.testing.assert_allclose(got, run(control), atol=2e-5)
        assert np.abs(got - sound).max() > 1e-3


def test_softmax_routing_renormalises_the_chosen():
    """The k largest of the softmax over all E logits in float32, weighed
    by their share of the chosen; no bias, no scale.  The sigmoid path is
    the sigmoid-of-the-logit formula it was, to the bit."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    chosen, weights = moe.route(x, router, None, 2, 1.0, scoring="softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    top = np.argsort(-prob, -1)[:, :2]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(top, -1)).all()
    picked = np.take_along_axis(prob, np.asarray(chosen), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    chosen, weights = moe.route(x, router, bias, 2, 2.5)
    s = jax.nn.sigmoid(jnp.dot(x, router, preferred_element_type=jnp.float32))
    _, want = jax.lax.top_k(s + bias, 2)
    picked = jnp.take_along_axis(s, want, axis=-1)
    assert (np.asarray(chosen) == np.asarray(want)).all()
    assert (np.asarray(weights)
            == np.asarray(2.5 * picked / picked.sum(-1, keepdims=True))).all()


@pytest.mark.parametrize("layer", ["layer_01", "layer_04"])
def test_the_shares_add_up_to_the_uncut_layer(params, layer):
    """Four holders of 2 of the 8 experts each (the blocked form): the
    routed parts their layers give add up to the uncut reference layer; no
    shared expert to count once."""
    rng = np.random.default_rng(7)
    t = 200
    x = jnp.asarray(rng.normal(size=(t, 64)), jnp.float32)
    live = jnp.ones(t, bool)
    whole = params[layer]
    total, loads = 0.0, 0
    for lo in range(0, 8, 2):
        share = HybridMoELM(dict(ARCH, held_experts=[lo, lo + 2]))
        mine = dict(whole, e_gu=whole["e_gu"][lo:lo + 2],
                    e_down=whole["e_down"][lo:lo + 2])
        out, counters, _ = share._moe(mine, x, live)
        total = total + np.asarray(out)
        loads += int(counters["assignments"])
    uncut, _, _ = reference_mellum.Reference(ARCH).moe(whole, x)
    np.testing.assert_allclose(total, np.asarray(uncut), atol=4e-5)
    assert loads == t * ARCH["num_experts_per_tok"]       # nothing dropped


@pytest.mark.parametrize("control", reference_mellum.CONTROLS[1:])
def test_each_control_of_the_reference_is_another_model(params, control):
    lengths = TEMPLATES["mixed"]
    _, host = make_batch(lengths)
    run = lambda c: reference_mellum.Reference(ARCH, c).run(   # noqa: E731
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    assert np.abs(run(control) - run(None)).max() > 1e-3


def test_canonical_reads_the_mellum_spelling():
    a = hybrid_lm.canonical(ARCH)
    assert a["num_experts_per_token"] == 2 and "num_experts_per_tok" not in a
    assert a["moe_renormalize"] is True and "norm_topk_prob" not in a
    assert a["first_k_dense_replace"] == 0 and "mlp_layer_types" not in a
    # what the file leaves out stays out: the class reads the plain block
    assert not {"moe_router_activation_func", "topk_method",
                "num_shared_experts", "routed_scaling_factor",
                *hybrid_lm.AFMOE_BLOCK} & set(a)
    m = HybridMoELM(a)
    assert m.scoring == "softmax" and not m.router_bias
    assert m.shared == 0 and m.route_scale == 1.0
    assert m.shapes() == HybridMoELM(ARCH).shapes()
    # leading dense layers are what first_k_dense_replace says
    dense = hybrid_lm.canonical(dict(
        ARCH, mlp_layer_types=["dense"] + ["sparse"] * 7))
    assert dense["first_k_dense_replace"] == 1


@pytest.mark.parametrize("key,value", [
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse"]),
    ("mlp_layer_types", ["sparse", "sparse", "moe", "sparse"]),
    ("mlp_layer_types", ["sparse"] * 3),
    ("rope_parameters", {"sliding_attention": {"rope_theta": 100}}),
    ("rope_parameters", {"rope_type": "default", "rope_theta": 100}),
    ("rope_parameters", dict(ARCH["rope_parameters"], full_attention=dict(
        YARN, rope_type="linear"))),
    ("rope_parameters", dict(ARCH["rope_parameters"], full_attention=dict(
        YARN, mscale=1.0))),
    ("topk_method", "noaux_tc"), ("norm_topk_prob", False),
    ("first_k_dense_replace", 1), ("rope_scaling", YARN),
    ("attention_bias", True), ("num_experts_per_tok", 9)])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        HybridMoELM(dict(ARCH, **{key: value}))


def test_the_other_architectures_keep_their_parts():
    """What the file does not name the class does not build: the ``afmoe``
    block keeps its gate, QK-norm and post-norms and its router bias, the
    latent-attention files their shared expert and router bias."""
    m = HybridMoELM(AFMOE_ARCH)
    assert m.gate and m.qk_norm and m.sandwich and m.router_bias
    assert m.rotations["full_attention"] is None and m.yarn_layers == 0
    first, mixture = m.shapes()["layer_01"], m.shapes()["layer_02"]
    assert {"w_gate", "q_head_norm", "k_head_norm", "post_norm1",
            "post_norm2"} <= set(first)
    assert {"router_bias", "s_gu", "s_down"} <= set(mixture)
    for arch in (KIMI_ARCH, DSV3_ARCH):
        m = HybridMoELM(arch)
        assert m.scoring == "sigmoid" and m.shared == 1
        assert {"router_bias", "s_gu", "s_down"} <= set(
            m.shapes()["layer_05" if arch is KIMI_ARCH else "layer_04"])
    # the keys decide, not the name the file gives its model type
    for arch in (ARCH, AFMOE_ARCH, KIMI_ARCH, DSV3_ARCH):
        renamed = dict(arch, model_type="renamed")
        assert HybridMoELM(renamed).shapes() == HybridMoELM(arch).shapes()


def test_the_reference_is_one_plain_file():
    with open(os.path.join(BENCH, "reference_mellum.py")) as f:
        text = f.read()
    assert "dmlc_core_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert not os.path.exists(os.path.join(HERE, "mellum_reference.py"))


def test_predict_scores_a_mellum_arch_file_through_the_cli(tmp_path):
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.telemetry import trace
    from dmlc_core_tpu.utils import CheckpointManager
    arch = tmp_path / "arch.json"
    arch.write_text(json.dumps(ARCH))
    docs = write_docs(tmp_path / "docs.libsvm",
                      [37, 9, 23, 1, 40, 12, 30, 2, 8, 19, 33])
    argv = predict_args(tmp_path, str(arch))
    p = cli.TrainParams()
    p.init(dict(a.split("=", 1) for a in argv))
    model = cli.MODEL_REGISTRY[p.model](p)
    params = spiced(model.init(jax.random.PRNGKey(p.seed)))
    CheckpointManager(p.ckpt_dir).save(3, {"params": params},
                                       meta={"model": p.model})
    assert cli.main(argv) == 0
    got = np.loadtxt(f"{tmp_path}/scores.txt")
    assert got.shape == (len(docs),)
    ref = reference_mellum.Reference(ARCH)
    for lo in range(0, len(docs), ROWS):
        part = docs[lo:lo + ROWS]
        rp = np.concatenate([[0], np.cumsum([len(d) for d in part])])
        want = ref.run(params, np.concatenate(part), rp)["scores"]
        np.testing.assert_allclose(got[lo:lo + len(part)], want, atol=2e-5)
    recs = [r["attrs"] for r in trace.recorder.snapshot()
            if r["name"] == "lm.batch"][-2:]
    assert sum(r["documents"] for r in recs) == len(docs)
    for r in recs:
        assert r["gqa.yarn_layers"] == 1 and r["gqa.fused_layers"] == 0
        assert 0 < r["attn.key_blocks_window"] <= r["attn.key_blocks_full"]
        for layer in MIXTURES:
            assert {f"{layer}.{c}" for c in (
                "assignments", "load_max", "load_mean", "unserved_tokens",
                "dispatch_rows")} <= set(r)
