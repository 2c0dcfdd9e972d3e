"""The hybrid linear/latent-attention mixture-of-experts document scorer at
small widths on the CPU (hidden 64, 4 heads of 16, 8 experts top-2 + 1
shared, 5 layers in the published pattern, 512 vocabulary rows; float32),
against the plain reference (``tests/lm_reference.py``, the same text as
the benchmark's ``reference_lm.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_reference
from dmlc_core_tpu.data.row_block import RowBlock
from dmlc_core_tpu.models.hybrid_lm import HybridMoELM
from dmlc_core_tpu.ops.doc_attention import doc_causal_attention
from dmlc_core_tpu.ops import doc_attention, kda
from dmlc_core_tpu.ops.kda import kda_chunked
from dmlc_core_tpu.pipeline.packing import pack_flat

ARCH = {
    "model_type": "kimi_linear", "hidden_size": 64, "num_hidden_layers": 5,
    "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7],
                           "full_attn_layers": [4, 8], "head_dim": 16,
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
    "mla_use_nope": True, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "tie_word_embeddings": False, "vocab_size": 1024, "vocab_rows": 512,
    "held_experts": [0, 4], "dtype": "float32",
}
ROWS, CAP = 8, 640
# document lengths of one batch: boundaries inside 64-token chunks, a
# one-token document, a document longer than several chunks, a full stream
TEMPLATES = {
    "mixed": [70, 33, 129, 1, 90, 60],
    "one_long": [600],
    "many_short": [5, 1, 2, 64, 63, 65, 3, 128],
    "full": [100, 28, 200, 312],
}


def make_batch(lengths, seed=1, vocab=512):
    """A loader batch (``pack_flat``'s own dict) of token documents."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, vocab, n) for n in lengths]
    blk = RowBlock(
        offsets=np.concatenate([[0], np.cumsum(lengths)]),
        labels=np.zeros(len(docs), np.float32),
        indices=np.concatenate(docs).astype(np.uint64), values=None)
    host = pack_flat(blk, ROWS, CAP, id_mod=vocab)
    return {k: jnp.asarray(v) for k, v in host.items()}, host


def spiced(params, seed=5):
    """``init``'s zeros and ones made random, so the decay rates, the decay
    and router biases and the norm weights are exercised."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for key, (path, leaf) in zip(keys, flat):
        name = path[-1].key
        noise = jax.random.normal(key, leaf.shape, leaf.dtype)
        if "norm" in name:
            leaf = leaf + 0.2 * noise
        elif name == "router_bias":
            leaf = 0.05 * noise
        elif name == "decay_rate":
            leaf = jnp.abs(noise)
        elif name == "decay_bias":
            leaf = leaf + noise
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(ARCH)


@pytest.fixture(scope="module")
def params(model):
    return spiced(model.init(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def scorer(model):
    return jax.jit(model.forward_counted), jax.jit(model.probe)


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_forward_agrees_with_the_reference(model, params, scorer, template):
    lengths = TEMPLATES[template]
    batch, host = make_batch(lengths)
    total = sum(lengths)
    positions = sorted({0, total - 1} | set(np.cumsum(lengths)[:-1].tolist())
                       | set((np.cumsum(lengths) - 1).tolist()))
    ref = lm_reference.Reference(ARCH).run(
        params, host["ids"], host["row_ptr"][:len(lengths) + 1], positions)
    scores, counters = scorer[0](params, batch)
    scores = np.asarray(scores)
    np.testing.assert_allclose(scores[:len(lengths)], ref["scores"],
                               atol=2e-5)
    assert (scores[len(lengths):] == 0).all()          # padding rows
    logits, chosen = scorer[1](params, batch, jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(logits), ref["logits"], atol=1e-4)
    for name, want in ref["chosen"].items():
        clear = ref["margin"][name] > 1e-5
        got = np.sort(np.asarray(chosen[name])[:total], -1)
        assert (got[clear] == np.sort(want, -1)[clear]).all()
    assert int(counters["tokens"]) == total
    assert int(counters["documents"]) == len(lengths)


def recurrence(q, k, v, g, beta, first):
    """The delta rule token by token, state zeroed at a document's first
    token (float64 on the host)."""
    t, h, dk = q.shape
    out = np.zeros((t, h, v.shape[-1]))
    s = np.zeros((h, dk, v.shape[-1]))
    for i in range(t):
        if first[i]:
            s[:] = 0
        s = np.exp(g[i])[:, :, None] * s
        s = s + beta[i][:, None, None] * k[i][:, :, None] * (
            v[i] - np.einsum("hd,hde->he", k[i], s))[:, None, :]
        out[i] = np.einsum("hde,hd->he", s, q[i])
    return out


def kda_inputs(lengths, h, d, seed):
    """Unit ``k``, scaled unit ``q``, decays from almost none to e^-30 a
    token, float64."""
    t = sum(lengths)
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(t, h, d))) * d ** -0.5
    k = unit(rng.normal(size=(t, h, d)))
    v = rng.normal(size=(t, h, d))
    g = -np.exp(rng.uniform(-6, 3.4, size=(t, h, d)))
    beta = rng.uniform(0.05, 0.95, size=(t, h))
    seg = np.repeat(np.arange(len(lengths)), lengths)
    return q, k, v, g, beta, seg


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("template", ["mixed", "many_short", "one_long"])
def test_chunked_kda_is_the_recurrence(chunk, template):
    lengths = TEMPLATES[template]
    q, k, v, g, beta, seg = kda_inputs(lengths, 3, 16,
                                       seed=chunk + sum(lengths))
    first = np.concatenate([[True], seg[1:] != seg[:-1]])
    f32 = lambda x: jnp.asarray(x, jnp.float32)            # noqa: E731
    got = kda_chunked(f32(q), f32(k), f32(v), f32(g), f32(beta),
                      jnp.asarray(seg, jnp.int32), chunk)
    want = recurrence(q, k, v, g, beta, first)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)


# the templates at kernel-eligible shapes: as they are (T no multiple of the
# chunk, boundaries in the middle of chunks), and cut so that T is a multiple
# of 64 and documents start on a chunk's first (64, 256, 320) and last (127)
# token
KERNEL_TEMPLATES = {
    "mixed": (TEMPLATES["mixed"], [64, 63, 129, 1, 63, 64]),
    "many_short": (TEMPLATES["many_short"], [5, 1, 2, 56, 63, 65, 3, 125]),
    "one_long": ([300], [320]),
}


@pytest.mark.parametrize("head_blocks", [1, 2])
@pytest.mark.parametrize("whole", [False, True],
                         ids=["ragged_t", "whole_chunks"])
@pytest.mark.parametrize("template", sorted(KERNEL_TEMPLATES))
def test_kda_kernel_is_the_recurrence(template, whole, head_blocks,
                                      monkeypatch):
    """The Pallas kernel, interpreted on the CPU, at shapes the selection
    rule sends to it on a TPU (128-wide heads, chunks of 64)."""
    lengths = KERNEL_TEMPLATES[template][whole]
    t, h, d = sum(lengths), 2, 128
    assert (t % 64 == 0) == whole
    if head_blocks == 2:        # room for one head's tiles and state only
        monkeypatch.setattr(kda, "_VMEM_BUDGET", 400_000)
    assert h // kda._head_block(h, d, d, 64, 4) == head_blocks
    q, k, v, g, beta, seg = kda_inputs(lengths, h, d, seed=t + head_blocks)
    first = np.concatenate([[True], seg[1:] != seg[:-1]])
    pad = -t % 64
    f32 = lambda x: jnp.asarray(                               # noqa: E731
        np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)), jnp.float32)
    seg_p = np.concatenate([seg, np.full(pad, np.iinfo(np.int32).max)])
    assert kda._kernel_fits(f32(q), f32(v), 64)
    got = kda._kda_kernel(f32(q), f32(k), f32(v), f32(g), f32(beta),
                          jnp.asarray(seg_p, jnp.int32), 64, interpret=True)
    want = recurrence(q, k, v, g, beta, first)
    np.testing.assert_allclose(np.asarray(got)[:t], want, atol=3e-5)


def test_kda_kernel_and_jnp_path_agree_in_bfloat16():
    """``q, k, v`` in bfloat16 as the benchmark's cell has them: both paths
    round the same products' operands to bfloat16, so they differ where a
    float32 rounding of what is cast moves a bfloat16 step (1.1e-4 here, of
    outputs of 6e-3 rms), and either is as far (1.9e-4) from the float64
    recurrence over the same bfloat16 inputs."""
    lengths = KERNEL_TEMPLATES["mixed"][1]
    t, h, d = sum(lengths), 2, 128
    q, k, v, g, beta, seg = kda_inputs(lengths, h, d, seed=3)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)               # noqa: E731
    args = (bf(q), bf(k), bf(v), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32), jnp.asarray(seg, jnp.int32))
    got = kda._kda_kernel(*args, 64, interpret=True)
    want = kda._kda_jnp(*args, 64)
    assert got.dtype == want.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=5e-4)
    f64 = lambda x: np.asarray(                               # noqa: E731
        bf(x).astype(jnp.float32), np.float64)
    first = np.concatenate([[True], seg[1:] != seg[:-1]])
    exact = recurrence(f64(q), f64(k), f64(v), g, beta, first)
    assert np.abs(got - exact).max() <= 1.25 * np.abs(want - exact).max()


@pytest.mark.parametrize("d,chunk,dtype,fits", [
    (128, 64, "bfloat16", True), (128, 64, "float32", True),
    (256, 128, "bfloat16", True), (16, 64, "float32", False),
    (128, 8, "float32", False), (128, 24, "bfloat16", False),
    (64, 64, "bfloat16", False)])
def test_kda_kernel_rule_is_a_function_of_the_shapes(d, chunk, dtype, fits):
    q = jax.ShapeDtypeStruct((chunk * 2, 4, d), jnp.dtype(dtype))
    assert kda._kernel_fits(q, q, chunk) == fits


def test_kda_head_block_is_sized_from_vmem():
    # the cell: 32 heads of 128, bfloat16 -> 288 KiB a head, 16 heads a step
    assert kda._head_block(32, 128, 128, 64, 2) == 16
    assert kda._head_block(32, 128, 128, 64, 4) == 16
    assert kda._head_block(3, 128, 128, 64, 2) == 3
    assert kda._head_block(7, 512, 512, 64, 4) == 1       # always a block


def test_eligible_shapes_take_the_jnp_path_on_the_cpu():
    """The selection is made at lowering: the same call that holds the
    kernel in a program for a TPU runs ``jnp`` here, and says so."""
    lengths = [70, 33, 26]
    q, k, v, g, beta, seg = kda_inputs(lengths, 1, 128, seed=7)
    first = np.concatenate([[True], seg[1:] != seg[:-1]])
    f32 = lambda x: jnp.asarray(x, jnp.float32)            # noqa: E731
    got, fused = jax.jit(kda.kda_chunked_counted)(
        f32(q), f32(k), f32(v), f32(g), f32(beta), jnp.asarray(seg, jnp.int32))
    assert int(fused) == 0
    np.testing.assert_allclose(
        np.asarray(got), recurrence(q, k, v, g, beta, first), atol=3e-5)


def test_counters_say_how_many_layers_took_the_kernel(model, params, scorer):
    batch, _ = make_batch(TEMPLATES["mixed"])
    _, counters = scorer[0](params, batch)
    rec = HybridMoELM.counter_record(counters)
    assert rec["kda.fused_layers"] == 0.0                  # the CPU: none
    assert rec["mla.fused_layers"] == 0.0
    assert rec["moe.fused_combines"] == 0.0                # nor the combine
    assert rec["tokens"] == sum(TEMPLATES["mixed"])


def test_a_holder_of_every_expert_counts_its_combines_by_token(
        model, params, scorer):
    """Every mixture layer of a holder of all 8 experts sums its rows by
    token (``moe.gather_combines``, on the CPU as on a TPU); a holder of
    part of them has no such key."""
    batch, _ = make_batch(TEMPLATES["mixed"])
    whole = HybridMoELM(dict(ARCH, held_experts=[0, 8]))
    _, counters = jax.jit(whole.forward_counted)(
        spiced(whole.init(jax.random.PRNGKey(2))), batch)
    rec = HybridMoELM.counter_record(counters)
    assert rec["moe.gather_combines"] == whole.layers - whole.dense_layers \
        == 4.0
    assert rec["moe.fused_combines"] == 0.0                # the CPU: none
    _, counters = scorer[0](params, batch)
    rec = HybridMoELM.counter_record(counters)
    assert not [name for name in rec if "gather_combine" in name]


def attention_inputs(lengths, h, dqk, dv, seed):
    """``q`` (scaled), ``k``, ``v`` float64, the document ids and every
    token's document start."""
    t = sum(lengths)
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(t, h, dqk)) * dqk ** -0.5
    k = rng.normal(size=(t, h, dqk))
    v = rng.normal(size=(t, h, dv))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    seg = np.repeat(np.arange(len(lengths)), lengths)
    return q, k, v, jnp.asarray(seg, jnp.int32), \
        jnp.asarray(bounds[seg], jnp.int32)


def one_document_at_a_time(q, k, v, lengths):
    """Causal softmax attention document by document (float64 on the
    host)."""
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    want = np.zeros(v.shape)
    for s, e in zip(bounds[:-1], bounds[1:]):
        sc = np.einsum("qhd,khd->hqk", q[s:e], k[s:e])
        sc = np.where(np.tril(np.ones((e - s, e - s), bool)), sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[s:e] = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                              v[s:e])
    return want


@pytest.mark.parametrize("block", [16, 64, 512])
@pytest.mark.parametrize("template", ["mixed", "many_short", "full"])
def test_block_diagonal_attention_is_one_document_at_a_time(block, template):
    lengths = TEMPLATES[template]
    q, k, v, seg, start = attention_inputs(lengths, 2, 24, 16, seed=block)
    f32 = lambda x: jnp.asarray(x, jnp.float32)            # noqa: E731
    got = doc_causal_attention(f32(q), f32(k), f32(v), seg, start, block)
    np.testing.assert_allclose(
        np.asarray(got), one_document_at_a_time(q, k, v, lengths), atol=2e-5)


# the templates at kernel-eligible shapes: as they are (T no multiple of the
# kernel's 256-token block, boundaries inside blocks), and cut so that T is a
# multiple of 256 and documents start on a block's first (256, 512) and last
# (255) token
ATTENTION_TEMPLATES = {
    "mixed": (TEMPLATES["mixed"], [255, 1, 256, 127, 129]),
    "many_short": (TEMPLATES["many_short"],
                   [5, 1, 2, 247, 1, 63, 65, 3, 125]),
    "full": (TEMPLATES["full"], [100, 28, 127, 1, 256]),
}


@pytest.mark.parametrize("head_blocks", [1, 2])
@pytest.mark.parametrize("dv", [128, 192])
@pytest.mark.parametrize("whole", [False, True],
                         ids=["ragged_t", "whole_blocks"])
@pytest.mark.parametrize("template", sorted(ATTENTION_TEMPLATES))
def test_attention_kernel_is_one_document_at_a_time(template, whole, dv,
                                                    head_blocks, monkeypatch):
    """The Pallas kernel, interpreted on the CPU, at the head widths the
    selection rule sends to it on a TPU (192-wide keys, 128- and 192-wide
    values), in float32 so that it is held to the ``jnp`` text's
    tolerance."""
    lengths = ATTENTION_TEMPLATES[template][whole]
    block = doc_attention._BLOCK
    t, h, dqk = sum(lengths), 2 * head_blocks, 192
    assert (t % block == 0) == whole and t > block
    if head_blocks == 2:        # room for two heads' tiles and state only
        monkeypatch.setattr(doc_attention, "_VMEM_BUDGET", 4_000_000)
    assert doc_attention._head_block(h, h, dqk, dv, block, 4) == 2
    q, k, v, seg, start = attention_inputs(lengths, h, dqk, dv,
                                           seed=t + dv + head_blocks)
    f32 = lambda x: jnp.asarray(x, jnp.float32)            # noqa: E731
    # the jitted wrapper read _VMEM_BUDGET when it was traced
    got = jax.jit(doc_attention._attention_kernel.__wrapped__,
                  static_argnames="interpret")(
        f32(q), f32(k), f32(v), seg, start, interpret=True)
    assert got.shape == v.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), one_document_at_a_time(q, k, v, lengths), atol=2e-5)


@pytest.mark.parametrize("dv", [128, 192])
def test_attention_kernel_and_jnp_path_agree_in_bfloat16(dv):
    """``q, k, v`` in bfloat16 as the benchmark's cells have them: both
    paths round the same operands at the same points (``p`` to bfloat16
    before the second product, everything else float32), so they differ by
    the order of their sums only, and the kernel is no farther than
    ``jnp`` from the float64 softmax over the same bfloat16 inputs."""
    lengths = ATTENTION_TEMPLATES["mixed"][1]
    q, k, v, seg, start = attention_inputs(lengths, 2, 192, dv, seed=3)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)               # noqa: E731
    args = (bf(q), bf(k), bf(v), seg, start)
    assert doc_attention._kernel_fits(*args[:3])
    got = doc_attention._attention_kernel(*args, interpret=True)
    want = doc_attention._attention_jnp(*args, doc_attention._BLOCK)
    assert got.dtype == want.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=5e-6)
    f64 = lambda x: np.asarray(                               # noqa: E731
        bf(x).astype(jnp.float32), np.float64)
    exact = one_document_at_a_time(f64(q), f64(k), f64(v), lengths)
    assert np.abs(got - exact).max() <= 1.05 * np.abs(want - exact).max()


@pytest.mark.parametrize("t,dqk,dv,dtype,fits", [
    (16384, 192, 192, "bfloat16", True),      # gigachat31_702b_ep16_l5
    (32768, 192, 128, "bfloat16", True),      # kimi_linear_48b_ep2_l5
    (256, 128, 128, "bfloat16", True), (1000, 256, 64, "bfloat16", True),
    (255, 192, 192, "bfloat16", False),       # under a block of tokens
    (640, 192, 192, "float32", False), (640, 192, 192, "float16", False),
    (640, 24, 16, "bfloat16", False), (640, 64, 128, "bfloat16", False),
    (640, 320, 128, "bfloat16", True), (640, 192, 24, "bfloat16", False)])
def test_attention_kernel_rule_is_a_function_of_the_shapes(t, dqk, dv, dtype,
                                                           fits):
    q = jax.ShapeDtypeStruct((t, 4, dqk), jnp.dtype(dtype))
    v = jax.ShapeDtypeStruct((t, 4, dv), jnp.dtype(dtype))
    assert doc_attention._kernel_fits(q, q, v) == fits
    # three heads of 192: no head block's keys are whole tiles of lanes
    odd = jax.ShapeDtypeStruct((t, 3, dqk), jnp.dtype(dtype))
    assert doc_attention._kernel_fits(odd, odd, odd) == (
        fits and dqk % 128 == 0)


def test_attention_head_block_is_sized_from_vmem():
    block = doc_attention._BLOCK
    # the cells: 64 heads of 192 / 192 and 32 of 192 / 128, bfloat16
    assert doc_attention._head_block(64, 64, 192, 192, block, 2) == 8
    assert doc_attention._head_block(32, 32, 192, 128, block, 2) == 16
    assert doc_attention._head_block(3, 3, 256, 192, block, 2) == 3
    assert doc_attention._head_block(3, 3, 192, 192, block, 2) is None
    assert doc_attention._head_block(7, 7, 4096, 4096, block, 2) is None


def test_eligible_attention_takes_the_jnp_path_on_the_cpu():
    """The selection is made at lowering: the same call that holds the
    kernel in a program for a TPU runs ``jnp`` here, and says so."""
    lengths = ATTENTION_TEMPLATES["many_short"][0]
    q, k, v, seg, start = attention_inputs(lengths, 2, 192, 128, seed=7)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)               # noqa: E731
    assert doc_attention._kernel_fits(bf(q), bf(k), bf(v))
    got, fused = jax.jit(doc_attention.doc_causal_attention_counted)(
        bf(q), bf(k), bf(v), seg, start)
    assert int(fused) == 0
    f64 = lambda x: np.asarray(                               # noqa: E731
        bf(x).astype(jnp.float32), np.float64)
    np.testing.assert_allclose(
        np.asarray(got),
        one_document_at_a_time(f64(q), f64(k), f64(v), lengths), atol=2e-2)


@pytest.mark.parametrize("layer", ["layer_02", "layer_04"])
@pytest.mark.parametrize("split", [4, 2, 6])
def test_the_shares_add_up_to_the_uncut_layer(model, params, layer, split):
    """Two holders of ``split`` and ``8 - split`` of the 8 experts: the parts
    their layers give, with the shared expert (computed alike by both)
    counted once, add up to the uncut reference layer."""
    rng = np.random.default_rng(split)
    t = 200
    x = jnp.asarray(rng.normal(size=(t, 64)), jnp.float32)
    live = jnp.ones(t, bool)
    p = params[layer]
    whole = dict(p)                     # the holder [0, 4) keeps 4: make 8
    key = jax.random.PRNGKey(split)
    whole["e_gu"] = jax.random.normal(key, (8, 64, 64)) / 8.0
    whole["e_down"] = jax.random.normal(key, (8, 32, 64)) / 5.6
    parts, loads = [], 0
    for lo, hi in ((0, split), (split, 8)):
        share = HybridMoELM(dict(ARCH, held_experts=[lo, hi]))
        mine = dict(whole, e_gu=whole["e_gu"][lo:hi],
                    e_down=whole["e_down"][lo:hi])
        out, counters, _ = share._moe(mine, x, live)
        parts.append(np.asarray(out))
        loads += int(counters["assignments"])
    ref = lm_reference.Reference(dict(ARCH, held_experts=[0, 8]))
    uncut, _, _ = ref.moe(whole, x)
    shared = np.asarray(ref.swiglu(x, p["s_gu"], p["s_down"]))
    np.testing.assert_allclose(parts[0] + parts[1] - shared,
                               np.asarray(uncut), atol=2e-5)
    assert loads == t * ARCH["num_experts_per_token"]     # nothing dropped


@pytest.mark.parametrize("template", ["mixed", "full"])
def test_sliced_vocabulary_is_log_softmax_over_its_columns(template):
    """A holder of rows 0..511 of a 1024-row vocabulary scores with the
    log-softmax over its own 512 columns of the full logits."""
    lengths = TEMPLATES[template]
    full = HybridMoELM(dict(ARCH, vocab_rows=1024))
    sliced = HybridMoELM(ARCH)
    p_full = spiced(full.init(jax.random.PRNGKey(3)))
    p_slice = dict(p_full, embed=p_full["embed"][:512],
                   head=p_full["head"][:, :512])
    batch, host = make_batch(lengths)
    total = sum(lengths)
    logits, _ = jax.jit(full.probe)(p_full, batch, jnp.arange(total))
    logp = jax.nn.log_softmax(np.asarray(logits)[:, :512], axis=-1)
    ids, rp = host["ids"], host["row_ptr"]
    want = [np.mean([logp[t, ids[t + 1]]
                     for t in range(rp[r], rp[r + 1] - 1)] or [0.0])
            for r in range(len(lengths))]      # a one-token document: 0
    got = np.asarray(jax.jit(sliced.forward)(p_slice, batch))
    np.testing.assert_allclose(got[:len(lengths)], want, atol=2e-5)


@pytest.mark.parametrize("template", ["mixed", "many_short"])
def test_padding_tokens_reach_no_expert(model, params, scorer, template):
    lengths = TEMPLATES[template]
    batch, _ = make_batch(lengths)
    _, counters = scorer[0](params, batch)
    total, k = sum(lengths), ARCH["num_experts_per_token"]
    other = HybridMoELM(dict(ARCH, held_experts=[4, 8]))
    _, theirs = jax.jit(other.forward_counted)(params, batch)
    for name in ("layer_02",):          # same input up to the first mixture
        assert int(counters[name]["assignments"]) \
            + int(theirs[name]["assignments"]) == total * k
    for name, c in counters.items():
        if isinstance(c, dict):
            assert 0 <= int(c["unserved_tokens"]) <= total
            assert int(c["load_max"]) * 4 >= int(c["assignments"])
            assert float(c["load_mean"]) * 4 == pytest.approx(
                float(c["assignments"]))


@pytest.mark.parametrize("key,value", [
    ("moe_router_activation_func", "softmax"), ("topk_method", "greedy"),
    ("moe_renormalize", False), ("num_expert_group", 3), ("topk_group", 2),
    ("held_experts", [4, 12])])
def test_an_architecture_it_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        HybridMoELM(dict(ARCH, **{key: value}))


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "lm_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmarks", "chip",
                           "reference_lm.py")) as f:
        assert f.read() == mine


# -- the normal path: registry, loader, predict -----------------------------

def write_docs(path, lengths, seed=9):
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, 512, n) for n in lengths]
    with open(path, "w") as f:
        for d in docs:
            f.write("0 " + " ".join(map(str, d)) + "\n")
    return docs


@pytest.fixture()
def run_dir(tmp_path):
    arch = tmp_path / "arch.json"
    arch.write_text(json.dumps(ARCH))
    lengths = [70, 33, 129, 1, 90, 60, 200, 57, 12, 300, 41]
    docs = write_docs(tmp_path / "docs.libsvm", lengths)
    return tmp_path, str(arch), docs


def predict_args(tmp, arch_file, **over):
    args = dict(mode="predict", model="hybrid_moe_lm", arch=arch_file,
                task="score", features=512, batch_rows=ROWS, nnz_cap=CAP,
                data=f"file://{tmp}/docs.libsvm", ckpt_dir=f"{tmp}/ckpt",
                output=f"{tmp}/scores.txt")
    args.update(over)
    return [f"{k}={v}" for k, v in args.items()]


def test_predict_scores_documents_through_the_cli(run_dir):
    from dmlc_core_tpu.models import cli
    from dmlc_core_tpu.utils import CheckpointManager
    tmp, arch, docs = run_dir
    p = cli.TrainParams()
    p.init(dict(a.split("=", 1) for a in predict_args(tmp, arch)))
    model = cli.MODEL_REGISTRY[p.model](p)
    params = spiced(model.init(jax.random.PRNGKey(p.seed)))
    CheckpointManager(p.ckpt_dir).save(7, {"params": params},
                                       meta={"model": p.model})
    assert cli.main(predict_args(tmp, arch)) == 0
    got = np.loadtxt(f"{tmp}/scores.txt")
    assert got.shape == (len(docs),)
    ref = lm_reference.Reference(ARCH)
    for lo in range(0, len(docs), ROWS):
        part = docs[lo:lo + ROWS]
        rp = np.concatenate([[0], np.cumsum([len(d) for d in part])])
        want = ref.run(params, np.concatenate(part), rp)["scores"]
        np.testing.assert_allclose(got[lo:lo + len(part)], want, atol=2e-5)
    # the counters of every batch are in the span ring
    from dmlc_core_tpu.telemetry import trace
    recs = [r for r in trace.recorder.snapshot() if r["name"] == "lm.batch"]
    assert len(recs) >= 2
    assert sum(r["attrs"]["documents"] for r in recs[-2:]) == len(docs)
    assert all(r["attrs"]["kda.fused_layers"] == 0 for r in recs[-2:])
    assert all(r["attrs"]["mla.fused_layers"] == 0 for r in recs[-2:])
    assert all(r["attrs"]["moe.fused_combines"] == 0 for r in recs[-2:])


@pytest.mark.parametrize("over,why", [
    ({"task": "binary"}, "task=score"), ({"features": 1024}, "features=512"),
    ({"arch": ""}, "arch="), ({"mode": "train"}, "forward only")])
def test_the_cli_refuses_what_the_model_is_not(run_dir, capsys, over, why):
    from dmlc_core_tpu.models import cli
    tmp, arch, _ = run_dir
    assert cli.main(predict_args(tmp, arch, **over)) == 2
    assert why in capsys.readouterr().err
