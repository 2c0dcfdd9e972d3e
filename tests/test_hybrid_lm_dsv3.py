"""The document scorer on a ``deepseek_v3``-type architecture at small
widths on the CPU (hidden 64, 4 heads, ``q_lora_rank`` 32, ``kv_lora_rank``
16, 16 + 8 / 24 a head so that ``d_v != d_n != d_n + d_r``, 16 experts in 4
groups of which 2 are kept, top-4, 1 shared, one dense layer and three
mixtures, YaRN over an original context of 64; float32), against the plain
reference (``benchmarks/chip/reference_dsv3.py``, loaded by its path)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference
from dmlc_core_tpu.models import hybrid_lm
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.ops import doc_attention, moe
from test_hybrid_lm import (ARCH as KIMI_ARCH, CAP, ROWS, TEMPLATES,
                            edges, make_batch, plain_runs, predict_args,
                            spiced, write_docs)

reference_dsv3 = reference("dsv3")

ARCH = {
    "model_type": "deepseek_v3", "hidden_size": 64, "num_hidden_layers": 4,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "rope_type": "yarn"},
    "first_k_dense_replace": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "n_group": 4, "topk_group": 2, "topk_method": "noaux_tc",
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "attention_bias": False,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "vocab_size": 1024, "vocab_rows": 512, "held_experts": [0, 3],
    "dtype": "float32",
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


@pytest.fixture(scope="module")
def plain(params):
    return plain_runs(reference_dsv3.Reference(ARCH), params, TEMPLATES)


def test_the_layers_are_latent_attention_with_a_low_rank_query(model):
    shapes = model.shapes()
    assert [model.mixer(n) for n in range(1, 5)] == ["mla"] * 4
    first = shapes["layer_01"]
    assert first["wq_a"] == (64, 32) and first["q_norm"] == (32,)
    assert first["wq_b"] == (32, 4 * 24) and "wq" not in first
    assert first["wkv_b"] == (16, 4 * (16 + 24)) and first["wo"] == (96, 64)
    assert "w_gu" in first and "router" in shapes["layer_02"]
    assert shapes["layer_02"]["e_gu"] == (3, 64, 64)


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, plain,
                                           template):
    lengths = TEMPLATES[template]
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
    assert sorted(ref["chosen"]) == ["layer_02", "layer_03", "layer_04"]
    for name, want in ref["chosen"].items():
        clear = ref["margin"][name] > 1e-5
        got = np.sort(np.asarray(chosen[name])[:total], -1)
        assert (got[clear] == np.sort(want, -1)[clear]).all()
    rec = HybridMoELM.counter_record(counters)
    assert rec["tokens"] == total and rec["documents"] == len(lengths)
    assert rec["kda.fused_layers"] == 0.0              # no KDA layer at all
    assert rec["mla.fused_layers"] == 0.0              # the CPU: jnp
    assert rec["moe.fused_combines"] == 0.0            # and the scatter
    for name in ref["chosen"]:       # 3 of 16 held: the blocked form
        assert rec[f"{name}.assignments"] <= rec[f"{name}.dispatch_rows"] \
            < rec[f"{name}.assignments"] + moe.DISPATCH_BLOCK


@pytest.mark.parametrize("control", ["no_rope", "plain_rope", "ungrouped",
                                     "half_experts", "fp8"])
def test_each_control_of_the_reference_is_another_model(params, plain,
                                                        control):
    lengths = TEMPLATES["mixed"]
    _, host = make_batch(lengths)
    planted = reference_dsv3.Reference(ARCH, control).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1])["scores"]
    assert np.abs(planted - plain("mixed")["scores"]).max() > 1e-3


def complex_rotation(x, positions, freqs):
    """float64: the pairs ``(x_2i, x_2i+1)`` as complex numbers times
    ``exp(i p f_i)``, back as ``[re ; im]``, the order the program keeps."""
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * positions[:, None] * freqs[None, :])[:, None]
    return np.concatenate([z.real, z.imag], -1)


@pytest.mark.parametrize("first", [0, 4090, 250_000])
def test_the_rotation_is_a_complex_product(first):
    """Also past the original context (4096 at the published size) and near
    the published limit of 262 144, where float32 angles lose digits."""
    freqs, mscale = hybrid_lm.rope_frequencies(
        64, 1e5, dict(ARCH["rope_scaling"],
                      original_max_position_embeddings=4096))
    assert mscale == pytest.approx(0.1 * np.log(64) + 1)
    want_f, _, want_m = reference_dsv3.yarn(dict(
        qk_rope_head_dim=64, rope_theta=1e5, rope_scaling=dict(
            ARCH["rope_scaling"], original_max_position_embeddings=4096)))
    np.testing.assert_allclose(freqs, want_f, rtol=1e-12)
    assert mscale == pytest.approx(want_m)
    # the fastest columns keep their frequency, the slowest are 1/64 of it
    plain = 1e5 ** (-np.arange(32) / 32)
    assert freqs[0] == plain[0] and freqs[-1] == pytest.approx(plain[-1] / 64)
    rng = np.random.default_rng(first)
    x = rng.normal(size=(40, 3, 64))
    positions = first + np.arange(40)
    angle = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    got = hybrid_lm._rotate(jnp.asarray(x, jnp.float32),
                            jnp.cos(angle)[:, None], jnp.sin(angle)[:, None])
    exact = np.asarray(angle, np.float64)       # the program's own angles
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * exact)[:, None]
    np.testing.assert_allclose(
        np.asarray(got), np.concatenate([z.real, z.imag], -1), atol=2e-5)
    # and against float64 angles: float32 loses p * 6e-8 radians
    want = complex_rotation(x, positions.astype(np.float64),
                            np.asarray(freqs))
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-5 + 4e-7 * (first + 40))


def test_positions_restart_at_a_document_boundary(model, params):
    """A document scores the same alone and behind another: its tokens are
    rotated by their position in the document, not in the stream."""
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
    # without the restart the second document would read otherwise
    moved = score([docs[1], docs[0]])
    assert moved[0] == pytest.approx(together[1], abs=2e-5)


@pytest.mark.parametrize("heads,block", [(4, 512), (32, 512), (48, 512),
                                         (49, 256), (64, 256), (192, 256),
                                         (193, 128), (1024, 128)])
def test_attention_s_block_by_the_heads(heads, block):
    """32 heads keep 512 (a 32 MiB score tile was measured to stay on the
    chip), 64 take 256 (a 64 MiB tile was measured to spill); the limit is
    48 MiB, between the two."""
    assert doc_attention.default_block(heads) == block
    assert block == 128 or heads * block * block * 4 <= 48 << 20


def brute_force_choice(u, groups, kept, k):
    """One token: rank the groups by their two best ``u``, keep the best,
    take the ``k`` largest ``u`` inside them."""
    per = len(u) // groups
    rank = [sum(sorted(u[g * per:(g + 1) * per])[-2:]) for g in range(groups)]
    keep = sorted(range(groups), key=lambda g: -rank[g])[:kept]
    inside = [e for g in keep for e in range(g * per, (g + 1) * per)]
    return sorted(sorted(inside, key=lambda e: -u[e])[:k])


@pytest.mark.parametrize("experts,groups,kept,k", [
    (16, 4, 2, 4), (16, 4, 4, 4), (32, 8, 4, 8), (16, 2, 1, 4),
    (256, 8, 4, 8), (16, 1, 1, 4)])
def test_group_limited_choice_is_the_brute_force_loop(experts, groups, kept,
                                                      k):
    rng = np.random.default_rng(experts + groups + kept)
    t, h = 300, 24
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, experts)) / 3, jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=experts), jnp.float32)
    chosen, weights = jax.jit(moe.route, static_argnums=(3, 4, 5, 6))(
        x, router, bias, k, 2.5, groups, kept)
    s = np.asarray(jax.nn.sigmoid(x @ router))
    u = s + np.asarray(bias)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    for i in range(t):                      # random floats: no ties
        assert sorted(chosen[i]) == brute_force_choice(u[i], groups, kept, k)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    np.testing.assert_allclose(
        weights, 2.5 * np.take_along_axis(s, chosen, -1)
        / np.take_along_axis(s, chosen, -1).sum(-1, keepdims=True),
        rtol=1e-5)
    if kept < groups:       # the limit binds: some token's free choice differs
        free = np.sort(np.argsort(-u, -1)[:, :k], -1)
        assert (free != np.sort(chosen, -1)).any()


@pytest.mark.parametrize("layer", ["layer_02", "layer_04"])
@pytest.mark.parametrize("each", [4, 2])
def test_the_shares_add_up_to_the_uncut_layer(model, params, layer, each):
    """Four holders of 4 of the 16 experts, one group each (the whole
    buffer), or eight of 2 (the blocked form): the parts their layers give,
    with the shared expert (computed alike by all) counted once, add up to
    the uncut reference layer."""
    rng = np.random.default_rng(7)
    t = 200
    x = jnp.asarray(rng.normal(size=(t, 64)), jnp.float32)
    live = jnp.ones(t, bool)
    whole = dict(params[layer])
    key = jax.random.PRNGKey(11)
    whole["e_gu"] = jax.random.normal(key, (16, 64, 64)) / 8.0
    whole["e_down"] = jax.random.normal(key, (16, 32, 64)) / 5.6
    total, loads = 0.0, 0
    for lo in range(0, 16, each):
        share = HybridMoELM(dict(ARCH, held_experts=[lo, lo + each]))
        mine = dict(whole, e_gu=whole["e_gu"][lo:lo + each],
                    e_down=whole["e_down"][lo:lo + each])
        out, counters, _ = share._moe(mine, x, live)
        total = total + np.asarray(out)
        loads += int(counters["assignments"])
    ref = reference_dsv3.Reference(dict(ARCH, held_experts=[0, 16]))
    uncut, _, _ = ref.moe(whole, x)
    shared = np.asarray(ref.swiglu(x, whole["s_gu"], whole["s_down"]))
    np.testing.assert_allclose(total - (16 // each - 1) * shared,
                               np.asarray(uncut), atol=4e-5)
    assert loads == t * ARCH["num_experts_per_tok"]       # nothing dropped


def whole_buffer_dispatch(x, chosen, weights, live, e_gu, e_down, held):
    """The form the layer had: one sorted buffer of all ``T * k``
    assignments, the held ones first, gathered back by the inverse order."""
    t, k = chosen.shape
    lo, hi = held
    g = hi - lo
    mine = (chosen >= lo) & (chosen < hi) & live[:, None]
    key = jnp.where(mine, chosen - lo, g).reshape(-1)
    order = jnp.argsort(key)
    loads = jnp.zeros(g + 1, jnp.int32).at[key].add(1)[:g]
    xs = x[order // k]
    gate, up = jnp.split(jax.lax.ragged_dot(xs, e_gu, loads), 2, axis=-1)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, e_down, loads)
    back = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    y = jnp.where(mine[..., None], ys[back].reshape(t, k, -1), 0.0)
    return jnp.einsum("tk,tkh->th", jnp.where(mine, weights, 0.0), y), loads


# 150 tokens x 4 choices = 600 assignments, of which about a quarter held
@pytest.mark.parametrize("held", [(0, 4), (0, 16), (16, 20), (4, 12)],
                         ids=["a_group", "every_assignment", "none",
                              "two_groups"])
@pytest.mark.parametrize("block", [1024, 600, 64, 37, 1])
def test_blocked_dispatch_is_the_whole_buffer(block, held):
    """One block, exactly one, several and a last partial one, a row a
    block; every assignment held and none (experts 16-19 of 20, which the
    router never names)."""
    rng = np.random.default_rng(block)
    t, h, i, k = 150, 32, 16, 4
    g = held[1] - held[0]
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    chosen = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                   for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    live = jnp.asarray(np.arange(t) < 140)              # ten padding tokens
    e_gu = jnp.asarray(rng.normal(size=(g, h, 2 * i)) / 6, jnp.float32)
    e_down = jnp.asarray(rng.normal(size=(g, i, h)) / 4, jnp.float32)
    want, loads = whole_buffer_dispatch(x, chosen, weights, live, e_gu,
                                        e_down, held)
    def blocked(x, chosen, weights, live, e_gu, e_down):
        _, order, loads, w = moe._sorted_assignments(chosen, weights, live,
                                                     held)
        return moe._blocked_sum(x, order, loads, w, e_gu, e_down, block)[:2]

    got, dispatched = jax.jit(blocked)(x, chosen, weights, live, e_gu,
                                       e_down)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    rows = int(loads.sum())
    assert rows == {"every_assignment": 140 * k, "none": 0}.get(
        "every_assignment" if g == 16 else "none" if held[0] == 16 else "",
        rows)
    size = min(block, t * k)
    assert int(dispatched) == -(-rows // size) * size
    if rows == 0:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("held", [(0, 4), (0, 3), (16, 20), (17, 20)],
                         ids=["a_fifth", "under_a_fifth", "a_fifth_none",
                              "under_a_fifth_none"])
def test_the_layer_takes_one_form_at_every_share_held(held):
    """No caller chooses and no rule does: whatever share of the experts a
    holder has, it walks whole blocks of its held rows, and the sum and the
    counters are the whole buffer's."""
    rng = np.random.default_rng(5)
    t, h, i, k = 150, 32, 16, 4
    g = held[1] - held[0]
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    chosen = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                   for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    live = jnp.asarray(np.arange(t) < 140)
    e_gu = jnp.asarray(rng.normal(size=(g, h, 2 * i)) / 6, jnp.float32)
    e_down = jnp.asarray(rng.normal(size=(g, i, h)) / 4, jnp.float32)
    want, loads = whole_buffer_dispatch(x, chosen, weights, live, e_gu,
                                        e_down, held)
    got, counters = jax.jit(moe.held_experts_sum, static_argnums=(6, 7))(
        x, chosen, weights, live, e_gu, e_down, held, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    rows = int(loads.sum())
    assert int(counters["assignments"]) == rows
    assert int(counters["load_max"]) == int(loads.max())
    size = min(moe.DISPATCH_BLOCK, t * k)
    assert int(counters["dispatch_rows"]) == -(-rows // size) * size
    assert int(counters["fused_combine"]) == 0             # the CPU


# toy widths under the names the class reads (``hybrid_lm.canonical``)
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "sliding_window": 16,
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_token": 2, "num_expert_group": 1, "topk_group": 1,
    "held_experts": [0, 8], "vocab_size": 1024, "vocab_rows": 512,
    "dtype": "float32",
}


@pytest.mark.parametrize("config", [
    "kimi_linear_48b_ep2_l5", "gigachat31_702b_ep16_l5",
    "trinity_large_400b_ep8_l5"])
def test_each_benchmark_configuration_counts_its_fused_combines(config):
    """Each benchmark configuration, cut to toy widths: every mixture layer
    goes through the one form, and what ``forward_counted`` reports — the
    ``lm.batch`` event's keys — holds ``moe.fused_combines``, 0 on the CPU
    (4 on the chip: the combine kernel, chosen at lowering)."""
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "chip", "configs", config + ".json")) as f:
        cfg = hybrid_lm.canonical(json.load(f))
    cfg.update({k: v for k, v in TINY.items() if k in cfg})
    cfg["linear_attn_config"].update(head_dim=16, num_heads=4)
    m = HybridMoELM(cfg)
    assert m.layers - m.dense_layers == 4
    batch, _ = make_batch([35, 130, 64, 91])
    _, counters = jax.jit(m.forward_counted)(
        m.init(jax.random.PRNGKey(0)), batch)
    rec = HybridMoELM.counter_record(counters)
    assert rec["moe.fused_combines"] == 0.0
    mixtures = sorted(k[:-len(".dispatch_rows")] for k in rec
                      if k.endswith(".dispatch_rows"))
    assert len(mixtures) == 4
    for name in mixtures:
        assert f"{name}.fused_combine" not in rec
        assert rec[f"{name}.assignments"] <= rec[f"{name}.dispatch_rows"]


def test_both_spellings_load_to_one_model(params):
    """The same architecture under ``kimi_linear``'s key names (and no
    ``linear_attn_config``: no KDA layer) is the same model."""
    theirs_to_ours = {v: k for k, v in hybrid_lm.SPELLINGS.items()}
    respelled = {theirs_to_ours.get(k, k): v for k, v in ARCH.items()}
    assert set(respelled) != set(ARCH)
    assert hybrid_lm.canonical(ARCH) == hybrid_lm.canonical(respelled)
    a, b = HybridMoELM(ARCH), HybridMoELM(respelled)
    assert a.shapes() == b.shapes()
    batch, _ = make_batch(TEMPLATES["mixed"])
    np.testing.assert_array_equal(np.asarray(a.forward(params, batch)),
                                  np.asarray(b.forward(params, batch)))
    with pytest.raises(ValueError, match="num_experts"):
        HybridMoELM(dict(ARCH, num_experts=8))        # both, and unlike


def test_the_kimi_spelling_still_reads_as_before():
    m = HybridMoELM(KIMI_ARCH)
    assert m.rope_freqs is None and m.q_rank is None and m.groups == 1
    assert [m.mixer(n) for n in range(1, 6)] == ["kda"] * 3 + ["mla", "kda"]
    assert m.shapes()["layer_04"]["wq"] == (64, 4 * 24)
    assert m.attn_scale == 24 ** -0.5


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("norm_topk_prob", False), ("attention_bias", True),
    ("rope_scaling", dict(ARCH["rope_scaling"], rope_type="linear")),
    ("rope_scaling", dict(ARCH["rope_scaling"], mscale_all_dim=0)),
    ("topk_group", 5), ("n_group", 3), ("num_experts_per_tok", 9),
    ("held_experts", [12, 20])])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        HybridMoELM(dict(ARCH, **{key: value}))


def test_plain_rope_without_scaling_is_computed():
    m = HybridMoELM(dict(ARCH, rope_scaling=None))
    np.testing.assert_allclose(m.rope_freqs, 1e5 ** (-np.arange(4) / 4))
    assert m.attn_scale == pytest.approx(24 ** -0.5)


def test_predict_scores_a_deepseek_v3_arch_file_through_the_cli(tmp_path):
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
    ref = reference_dsv3.Reference(ARCH)
    for lo in range(0, len(docs), ROWS):
        part = docs[lo:lo + ROWS]
        rp = np.concatenate([[0], np.cumsum([len(d) for d in part])])
        want = ref.run(params, np.concatenate(part), rp)["scores"]
        np.testing.assert_allclose(got[lo:lo + len(part)], want, atol=2e-5)
    recs = [r["attrs"] for r in trace.recorder.snapshot()
            if r["name"] == "lm.batch"][-2:]
    assert sum(r["documents"] for r in recs) == len(docs)
    for r in recs:
        assert r["kda.fused_layers"] == 0 and r["mla.fused_layers"] == 0
        assert r["moe.fused_combines"] == 0
        for layer in ("layer_02", "layer_03", "layer_04"):
            assert {f"{layer}.{c}" for c in (
                "assignments", "load_max", "load_mean", "unserved_tokens",
                "dispatch_rows")} <= set(r)


def test_the_benchmark_s_balance_rule_evens_the_experts_loads(model, params):
    """``benchmarks/chip/router_balance.py``, the set-up step of the
    benchmark's cell: on a stream of a dozen token ids the drawn bias loads
    some expert several times the mean; ``noaux_tc``'s rule, a batch a
    step, brings every mixture layer's largest load down on batches it has
    not met, drops no assignment and moves nothing but ``router_bias``."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path.insert(0, bench)
    try:
        import router_balance
    finally:
        sys.path.remove(bench)
    lengths = TEMPLATES["full"]
    batches = [make_batch(lengths, seed=i, vocab=12)[0] for i in range(40)]
    met = iter(batches[:32])
    rule = {"batches": 32, "step_first": 0.02, "step_last": 0.001}
    balanced, worst = router_balance.balance(model, params,
                                             lambda: next(met), rule)
    assert len(worst) == 32
    loads = router_balance.loads_fn(model)
    before = sum(np.asarray(loads(params, b)) for b in batches[32:])
    after = sum(np.asarray(loads(balanced, b)) for b in batches[32:])
    assert before.shape == after.shape == (3, 16)
    every = 8 * sum(lengths) * ARCH["num_experts_per_tok"]
    assert (before.sum(-1) == every).all() and (after.sum(-1) == every).all()
    skew = lambda x: x.max(-1) / x.mean(-1)               # noqa: E731
    assert (skew(before) > 1.5).all() and (skew(after) < 1.3).all(), (
        skew(before), skew(after))
    flat = lambda p: jax.tree_util.tree_flatten_with_path(p)[0]  # noqa: E731
    for (path, was), (_, now) in zip(flat(params), flat(balanced)):
        same = np.array_equal(np.asarray(was), np.asarray(now))
        assert same != (path[-1].key == "router_bias"), path
        assert was.dtype == now.dtype
