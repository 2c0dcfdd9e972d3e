"""The document scorer on an ``afmoe``-type architecture at small widths on
the CPU (hidden 64, 4 query heads on 2 key heads of 16, a window of 16 under
documents of up to 600 tokens, sliding and full layers mixed, QK-norm, a
gated output, sandwich norms, the embedding times 8, 16 experts top-4 + 1
shared of which 3 are held, one dense layer and four mixtures; float32),
against the plain reference (``benchmarks/chip/reference_afmoe.py``, loaded
by its path)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference
from dmlc_core_tpu.models import hybrid_lm
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.ops import moe
from test_hybrid_lm import (ARCH as KIMI_ARCH, CAP, ROWS, TEMPLATES,
                            edges, make_batch, plain_runs, predict_args,
                            spiced, write_docs)
from test_hybrid_lm_dsv3 import ARCH as DSV3_ARCH

reference_afmoe = reference("afmoe")

WINDOW = 16
ARCH = {
    "model_type": "afmoe", "hidden_size": 64, "num_hidden_layers": 5,
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": WINDOW,
    "global_attn_every_n_layers": 2,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "rope_theta": 10000, "rope_scaling": None, "mup_enabled": True,
    "num_dense_layers": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "n_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "topk_group": 1, "score_func": "sigmoid",
    "route_norm": True, "route_scale": 2.448, "load_balance_coeff": 1e-3,
    "use_grouped_mm": True, "tie_word_embeddings": False,
    "vocab_size": 1024, "vocab_rows": 512, "held_experts": [0, 3],
    "dtype": "float32",
}
MIXTURES = ["layer_02", "layer_03", "layer_04", "layer_05"]


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(ARCH)


@pytest.fixture(scope="module")
def params(model):
    return spiced(model.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def scorer(model):
    return jax.jit(model.forward_counted), jax.jit(model.probe)


@pytest.fixture(scope="module")
def plain(params):
    return plain_runs(reference_afmoe.Reference(ARCH), params, TEMPLATES)


def test_the_layers_are_grouped_query_attention_with_four_norms(model):
    shapes = model.shapes()
    assert [model.mixer(n) for n in range(1, 6)] == ["gqa"] * 5
    assert model.sliding == {1, 3, 4} and model.window == WINDOW
    assert model.embed_scale == 8.0 and model.attn_scale == 0.25
    first = shapes["layer_01"]
    assert first["wq"] == first["w_gate"] == (64, 64)
    assert first["wk"] == first["wv"] == (64, 32) and first["wo"] == (64, 64)
    assert first["q_head_norm"] == first["k_head_norm"] == (16,)
    assert {"norm1", "post_norm1", "norm2", "post_norm2"} <= set(first)
    assert "wkv_a" not in first and "w_gu" in first
    assert shapes["layer_02"]["e_gu"] == (3, 64, 64)
    assert shapes["layer_02"]["router_bias"] == (16,)


def test_the_benchmark_s_configuration_is_the_issue_s_arithmetic():
    """``trinity_large_400b_ep8_l5``: 62 914 816 parameters of attention a
    layer, 176.17 M the dense layer, 998.00 M a mixture layer, 4 321 903 872
    in all; every width as the catalog's row has it."""
    arch = hybrid_lm.load_arch(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "chip", "configs", "trinity_large_400b_ep8_l5.json"))
    m = HybridMoELM(arch)
    count = lambda group: sum(int(np.prod(s)) for s in group.values())  # noqa: E731
    shapes = m.shapes()
    attention = {k: v for k, v in shapes["layer_01"].items()
                 if k in ("wq", "wk", "wv", "w_gate", "wo", "q_head_norm",
                          "k_head_norm")}
    assert count(attention) == 62_914_816
    assert count(shapes["layer_01"]) == 176_173_312
    assert count(shapes["layer_02"]) == count(shapes["layer_05"]) \
        == 997_995_008
    layers = {k: v for k, v in shapes.items() if isinstance(v, dict)}
    assert sum(count(g) for g in layers.values()) + count(
        {k: v for k, v in shapes.items() if k not in layers}) \
        == 4_321_903_872
    assert (m.heads, m.kv_heads, m.head_dim, m.window) == (48, 8, 128, 4096)
    assert m.sliding == {1, 2, 3, 4} and m.dense_layers == 1
    assert (m.top_k, m.experts, m.held, m.groups) == (4, 256, (0, 32), 1)
    assert m.route_scale == 2.448 and m.embed_scale == 3072 ** 0.5


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, plain,
                                           template):
    """float32 on both sides, so the gaps are the order of the sums: scores
    are means of log-probabilities (2e-5), logits single sums (1e-4); a
    choice is compared where the reference's margin is clear of that noise
    (1e-5)."""
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
    assert sorted(ref["chosen"]) == MIXTURES
    for name, want in ref["chosen"].items():
        clear = ref["margin"][name] > 1e-5
        got = np.sort(np.asarray(chosen[name])[:total], -1)
        assert (got[clear] == np.sort(want, -1)[clear]).all()
    rec = HybridMoELM.counter_record(counters)
    assert rec["tokens"] == total and rec["documents"] == len(lengths)
    assert rec["kda.fused_layers"] == rec["mla.fused_layers"] == 0.0
    assert rec["gqa.fused_layers"] == 0.0              # the CPU: jnp
    assert rec["moe.fused_combines"] == 0.0            # and the scatter
    # 640 tokens are three blocks of 256: a window of 16 walks a block and
    # the one before it, the one document longer than two blocks one more
    assert rec["attn.key_blocks_window"] == 5.0
    assert rec["attn.key_blocks_full"] == (6.0 if template == "one_long"
                                           else 5.0)
    for name in MIXTURES:            # 3 of 16 held: the blocked form
        assert rec[f"{name}.assignments"] <= rec[f"{name}.dispatch_rows"] \
            < rec[f"{name}.assignments"] + moe.DISPATCH_BLOCK


@pytest.mark.parametrize("control", reference_afmoe.CONTROLS[1:])
def test_each_control_of_the_reference_is_another_model(params, plain,
                                                        control):
    lengths = TEMPLATES["mixed"]
    _, host = make_batch(lengths)
    planted = reference_afmoe.Reference(ARCH, control).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    assert np.abs(planted - plain("mixed")["scores"]).max() > 1e-3


def test_a_window_over_every_document_is_the_no_window_control(model, params,
                                                               plain):
    """The program with ``sliding_window`` past the longest document is the
    reference that ignores the window, and no more the sound one."""
    lengths = TEMPLATES["one_long"]
    batch, host = make_batch(lengths)
    wide = HybridMoELM(dict(ARCH, sliding_window=4096))
    got = np.asarray(wide.forward(params, batch))[:1]
    wanted = reference_afmoe.Reference(ARCH, "no_window").run(
        params, host["ids"], host["row_ptr"][:2])["scores"]
    np.testing.assert_allclose(got, wanted, atol=2e-5)
    assert np.abs(got - plain("one_long")["scores"]).max() > 1e-3


def test_positions_and_the_window_restart_at_a_document_boundary(model,
                                                                 params):
    """A document scores the same alone and behind another: its tokens are
    rotated by their position in the document, and its window does not
    reach into the document before it."""
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 512, n) for n in (90, 131)]
    fwd = jax.jit(model.forward)

    def score(doc_list):
        from dmlc_core_tpu.data.row_block import RowBlock
        from dmlc_core_tpu.pipeline.packing import pack_flat
        lengths = [len(d) for d in doc_list]
        blk = RowBlock(offsets=np.concatenate([[0], np.cumsum(lengths)]),
                       labels=np.zeros(len(doc_list), np.float32),
                       indices=np.concatenate(doc_list).astype(np.uint64),
                       values=None)
        host = pack_flat(blk, ROWS, CAP, id_mod=512)
        return np.asarray(fwd(params, {k: jnp.asarray(v)
                                       for k, v in host.items()}))

    together = score(docs)
    assert together[1] == pytest.approx(score(docs[1:])[0], abs=2e-5)
    assert together[0] == pytest.approx(score(docs[:1])[0], abs=2e-5)
    moved = score([docs[1], docs[0]])
    assert moved[0] == pytest.approx(together[1], abs=2e-5)


@pytest.mark.parametrize("first", [0, 4090, 13_700])
def test_the_rotation_pairs_the_halves(first):
    """``x cos + rotate_half(x) sin`` at the published head size, up to the
    cell's longest document: the pair ``(x_i, x_{i + 64})`` as a complex
    number times ``exp(i p theta^(-2i/128))``, each where it was."""
    freqs, mscale = hybrid_lm.rope_frequencies(128, 1e4, None)
    assert mscale == 1.0
    np.testing.assert_allclose(freqs, 1e4 ** (-np.arange(64) / 64),
                               rtol=1e-12)
    rng = np.random.default_rng(first)
    x = rng.normal(size=(40, 3, 128))
    positions = first + np.arange(40)
    angle = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    got = hybrid_lm._rotate_halves(
        jnp.asarray(x, jnp.float32), jnp.cos(angle)[:, None],
        jnp.sin(angle)[:, None])
    z = (x[..., :64] + 1j * x[..., 64:]) * np.exp(
        1j * np.asarray(angle, np.float64))[:, None]
    np.testing.assert_allclose(
        np.asarray(got), np.concatenate([z.real, z.imag], -1), atol=2e-5)
    ref = reference_afmoe.Reference(dict(ARCH, head_dim=128))
    np.testing.assert_allclose(
        np.asarray(ref.rotate(jnp.asarray(x, jnp.float32),
                              jnp.asarray(positions))),
        np.asarray(got), atol=2e-5)


@pytest.mark.parametrize("layer", ["layer_02", "layer_05"])
def test_the_shares_add_up_to_the_uncut_layer(model, params, layer):
    """Eight holders of 2 of the 16 experts (an eighth each, the blocked
    form, as the benchmark's one chip of eight): the routed parts their
    layers give, with the shared expert (computed alike by all) counted
    once, add up to the uncut reference layer."""
    rng = np.random.default_rng(7)
    t = 200
    x = jnp.asarray(rng.normal(size=(t, 64)), jnp.float32)
    live = jnp.ones(t, bool)
    whole = dict(params[layer])
    key = jax.random.PRNGKey(11)
    whole["e_gu"] = jax.random.normal(key, (16, 64, 64)) / 8.0
    whole["e_down"] = jax.random.normal(key, (16, 32, 64)) / 5.6
    total, loads = 0.0, 0
    for lo in range(0, 16, 2):
        share = HybridMoELM(dict(ARCH, held_experts=[lo, lo + 2]))
        mine = dict(whole, e_gu=whole["e_gu"][lo:lo + 2],
                    e_down=whole["e_down"][lo:lo + 2])
        out, counters, _ = share._moe(mine, x, live)
        total = total + np.asarray(out)
        loads += int(counters["assignments"])
    ref = reference_afmoe.Reference(dict(ARCH, held_experts=[0, 16]))
    uncut, _, _ = ref.moe(whole, x)
    shared = np.asarray(ref.swiglu(x, whole["s_gu"], whole["s_down"]))
    np.testing.assert_allclose(total - 7 * shared, np.asarray(uncut),
                               atol=4e-5)
    assert loads == t * ARCH["num_experts_per_tok"]       # nothing dropped


def test_canonical_maps_afmoe_s_keys():
    a = hybrid_lm.canonical(ARCH)
    assert a["first_k_dense_replace"] == 1 and "num_dense_layers" not in a
    assert a["routed_scaling_factor"] == 2.448 and "route_scale" not in a
    assert a["moe_renormalize"] is True and "route_norm" not in a
    assert a["moe_router_activation_func"] == "sigmoid"
    assert a["num_experts_per_token"] == 4 and a["num_expert_group"] == 1
    assert a["topk_group"] == 1 and "num_limited_groups" not in a
    # the same architecture under the names the class reads is one model
    assert HybridMoELM(a).shapes() == HybridMoELM(ARCH).shapes()


@pytest.mark.parametrize("key,value,said", [
    ("first_k_dense_replace", 2, "first_k_dense_replace"),
    ("routed_scaling_factor", 2.5, "routed_scaling_factor"),
    ("moe_renormalize", False, "moe_renormalize"),
    ("num_expert_groups", 2, "num_expert_group"),
    ("num_experts_per_token", 8, "num_experts_per_token")])
def test_two_spellings_that_disagree_are_refused(key, value, said):
    with pytest.raises(ValueError, match=said):
        HybridMoELM(dict(ARCH, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("score_func", "softmax"), ("route_norm", False),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 64}),
    ("layer_types", ["sliding_attention"] * 4),
    ("layer_types", ["sliding_attention"] * 4 + ["chunked_attention"]),
    ("num_key_value_heads", 3), ("sliding_window", 0),
    ("num_experts_per_tok", 17), ("held_experts", [12, 20])])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        HybridMoELM(dict(ARCH, **{key: value}))


def test_the_other_two_architectures_read_as_before():
    """What the third mixer added is keyed off ``layer_types`` without
    ``kv_lora_rank``: the latent-attention files get no post-norm, no
    multiplier on the embedding and no window."""
    for arch in (KIMI_ARCH, DSV3_ARCH,
                 dict(DSV3_ARCH, layer_types=["full_attention"] * 4)):
        m = HybridMoELM(arch)
        assert m.attention == "mla" and not m.sandwich
        assert m.embed_scale == 1.0
        assert not any("post_norm" in k or k == "w_gate"
                       for k in m.shapes()["layer_01"])


def test_predict_scores_an_afmoe_arch_file_through_the_cli(tmp_path):
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.telemetry import trace
    from dmlc_core_tpu.utils import CheckpointManager
    arch = tmp_path / "arch.json"
    arch.write_text(json.dumps(ARCH))
    docs = write_docs(tmp_path / "docs.libsvm",
                      [70, 33, 129, 1, 90, 60, 200, 57, 12, 300, 41])
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
    ref = reference_afmoe.Reference(ARCH)
    for lo in range(0, len(docs), ROWS):
        part = docs[lo:lo + ROWS]
        rp = np.concatenate([[0], np.cumsum([len(d) for d in part])])
        want = ref.run(params, np.concatenate(part), rp)["scores"]
        np.testing.assert_allclose(got[lo:lo + len(part)], want, atol=2e-5)
    recs = [r["attrs"] for r in trace.recorder.snapshot()
            if r["name"] == "lm.batch"][-2:]
    assert sum(r["documents"] for r in recs) == len(docs)
    for r in recs:
        assert r["kda.fused_layers"] == r["mla.fused_layers"] == 0
        assert r["gqa.fused_layers"] == 0 and r["moe.fused_combines"] == 0
        assert 0 < r["attn.key_blocks_window"] <= r["attn.key_blocks_full"]
        for layer in MIXTURES:
            assert {f"{layer}.{c}" for c in (
                "assignments", "load_max", "load_mean", "unserved_tokens",
                "dispatch_rows")} <= set(r)
