"""``ops.doc_attention`` with grouped keys and a first-visible-key row: the
``jnp`` formulation and the Pallas kernel (interpreted on the CPU) against a
dense masked softmax in float64, at ``H_kv = H`` and ``H_kv < H``, with a
window shorter and longer than the documents."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.ops import doc_attention
from dmlc_core_tpu.ops.doc_attention import (doc_causal_attention,
                                             doc_causal_attention_counted,
                                             doc_diff_attention_counted,
                                             walk_blocks)

# documents of one stream: boundaries inside the kernel's 256-token blocks,
# a one-token document, documents longer than two blocks; 1024 tokens
LENGTHS = [255, 1, 300, 127, 341]
# shorter than every document but one, shorter than a block, longer than a
# block, and longer than every document
WINDOWS = [1, 16, 300, 4096]


def inputs(h, hkv, dqk, dv, seed, lengths=LENGTHS):
    """``q`` (scaled) on ``h`` heads, ``k``, ``v`` on ``hkv``, float64; the
    document ids and every token's document start."""
    t = sum(lengths)
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(t, h, dqk)) * dqk ** -0.5
    k = rng.normal(size=(t, hkv, dqk))
    v = rng.normal(size=(t, hkv, dv))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    seg = np.repeat(np.arange(len(lengths)), lengths)
    return q, k, v, seg, bounds[seg]


def first_keys(doc_start, window):
    """The row the program hands over: a token's first visible key."""
    t = np.arange(len(doc_start))
    return np.maximum(doc_start, t - (window - 1))


def dense_masked_softmax(q, k, v, seg, window):
    """One ``[T, T]`` matrix a head (float64 on the host): a key of the
    query's document, not ahead of it and under ``window`` behind it; keys
    and values repeated to the query heads."""
    t, h, _ = q.shape
    grp = h // k.shape[1]
    k, v = np.repeat(k, grp, axis=1), np.repeat(v, grp, axis=1)
    behind = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (seg[:, None] == seg[None, :]) & (behind >= 0) & (behind < window)
    s = np.where(seen[None], np.einsum("qhd,khd->hqk", q, k), -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


def as_args(q, k, v, seg, first, dtype=jnp.float32):
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(seg, jnp.int32),
            jnp.asarray(first, jnp.int32))


@pytest.mark.parametrize("block", [64, 512])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (6, 1)])
def test_jnp_walk_is_the_dense_masked_softmax(h, hkv, window, block):
    q, k, v, seg, start = inputs(h, hkv, 24, 16, seed=h + hkv + window)
    got = doc_causal_attention(
        *as_args(q, k, v, seg, first_keys(start, window)), block)
    assert got.shape == (len(seg), h, 16) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), dense_masked_softmax(q, k, v, seg, window),
        atol=2e-5)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("h,hkv,head_blocks", [(2, 2, 1), (4, 4, 2),
                                               (4, 2, 1), (12, 2, 2)])
def test_kernel_is_the_dense_masked_softmax(h, hkv, head_blocks, window,
                                            monkeypatch):
    """The Pallas kernel, interpreted, at the head width the grouped model
    sends it on a TPU (128 / 128), in float32 so that it is held to the
    ``jnp`` text's tolerance; one head block and two (whole groups)."""
    block = doc_attention._BLOCK
    grp = h // hkv
    per_group = block * (grp * (2 * 128 * 4 + 2 * 128 * 4)
                         + 4 * 128 * 4 + grp * (128 + 16) * 4)
    monkeypatch.setattr(doc_attention, "_VMEM_BUDGET",
                        per_group * hkv // head_blocks)
    assert doc_attention._head_block(h, hkv, 128, 128, block, 4) \
        == h // head_blocks
    q, k, v, seg, start = inputs(h, hkv, 128, 128, seed=h * hkv + window)
    # the jitted wrapper read _VMEM_BUDGET when it was traced
    got = jax.jit(doc_attention._attention_kernel.__wrapped__,
                  static_argnames="interpret")(
        *as_args(q, k, v, seg, first_keys(start, window)), interpret=True)
    assert got.shape == (len(seg), h, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), dense_masked_softmax(q, k, v, seg, window),
        atol=2e-5)


@pytest.mark.parametrize("lengths", [LENGTHS, [70, 33, 129, 1, 90, 60, 200]],
                         ids=["whole_blocks", "ragged_t"])
def test_kernel_and_jnp_agree_in_bfloat16_with_grouped_keys(lengths):
    """Both formulations round the same operands at the same points, so
    they differ by the order of their sums only, and the kernel is no
    farther than ``jnp`` from the float64 softmax over the same bfloat16
    inputs; windowed."""
    q, k, v, seg, start = inputs(12, 2, 128, 128, seed=3, lengths=lengths)
    args = as_args(q, k, v, seg, first_keys(start, 100), jnp.bfloat16)
    assert doc_attention._kernel_fits(*args[:3])
    got = np.asarray(doc_attention._attention_kernel(*args, interpret=True))
    want = np.asarray(doc_attention._attention_jnp(*args,
                                                   doc_attention._BLOCK))
    np.testing.assert_allclose(got, want, atol=5e-6)
    f64 = lambda x: np.asarray(                               # noqa: E731
        jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), np.float64)
    exact = dense_masked_softmax(f64(q), f64(k), f64(v), seg, 100)
    assert np.abs(got - exact).max() <= 1.05 * np.abs(want - exact).max()


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2)])
def test_a_window_longer_than_every_document_changes_no_bit(h, hkv):
    """The row is then the documents' starts, entry for entry, so the
    program is the unwindowed one: bit for bit, in either formulation."""
    q, k, v, seg, start = inputs(h, hkv, 128, 128, seed=9)
    rows = first_keys(start, max(LENGTHS)), first_keys(start, 1 << 20)
    assert np.array_equal(rows[0], start) and np.array_equal(rows[1], start)
    assert not np.array_equal(first_keys(start, max(LENGTHS) - 1), start)
    plain = as_args(q, k, v, seg, start)
    wide = as_args(q, k, v, seg, rows[0])
    np.testing.assert_array_equal(
        np.asarray(doc_causal_attention(*wide, 64)),
        np.asarray(doc_causal_attention(*plain, 64)))
    np.testing.assert_array_equal(
        np.asarray(doc_attention._attention_kernel(*wide, interpret=True)),
        np.asarray(doc_attention._attention_kernel(*plain, interpret=True)))


@pytest.mark.parametrize("h,hkv,dqk,dv,heads", [
    (48, 8, 128, 128, 24),       # trinity_large_400b_ep8_l5: four groups
    (64, 64, 192, 192, 8),       # gigachat31_702b_ep16_l5, as before
    (32, 32, 192, 128, 16),      # kimi_linear_48b_ep2_l5, as before
    (48, 48, 128, 128, 16),      # ungrouped, the keys counted a head
    (32, 4, 128, 128, 16), (6, 1, 128, 128, 6),
    (6, 2, 192, 192, 6),         # a group's keys alone are 1.5 tiles
    (9, 3, 192, 192, None)])
def test_head_block_counts_a_group_s_keys_once(h, hkv, dqk, dv, heads):
    got = doc_attention._head_block(h, hkv, dqk, dv, doc_attention._BLOCK, 2)
    assert got == heads
    if heads:
        assert h % heads == 0 and heads % (h // hkv) == 0
        assert (heads * hkv // h) * dqk % 128 == 0


def test_heads_that_are_no_whole_groups_are_refused():
    q, k, v, seg, start = inputs(4, 3, 24, 16, seed=1)
    with pytest.raises(ValueError, match="4 query heads"):
        doc_causal_attention_counted(*as_args(q, k, v, seg, start))
    with pytest.raises(ValueError, match="4 query heads"):
        doc_causal_attention_counted(*as_args(q, k[:, :2], v, seg, start))


def test_the_grouped_shape_takes_the_kernel_on_a_tpu_and_jnp_here():
    q, k, v, seg, start = inputs(12, 2, 128, 128, seed=5)
    args = as_args(q, k, v, seg, first_keys(start, 64), jnp.bfloat16)
    assert doc_attention._kernel_fits(*args[:3])
    got, fused = jax.jit(doc_causal_attention_counted)(*args)
    assert int(fused) == 0 and got.shape == (len(seg), 12, 128)


@pytest.mark.parametrize("lengths,window,blocks", [
    # two blocks of one document: 1 + 2; a window inside a block: 1 + 2 (the
    # second block's first query still sees the first block's last keys)
    ([512], 1 << 20, 3), ([512], 16, 3), ([512], 1, 2),
    # four blocks, each its own document: every walk is its own block
    ([256] * 4, 1 << 20, 4),
    # one document of four blocks: 1 + 2 + 3 + 4, under a window of 257
    # tokens 1 + 2 + 2 + 2, of 256 the same (the block's first query sees
    # the block before from its second key on)
    ([1024], 1 << 20, 10), ([1024], 257, 7), ([1024], 256, 7),
    ([1024], 513, 9),
    # a ragged end is one block more, walked from its first token's bound
    ([1024, 10], 1 << 20, 11), ([1030], 257, 9)])
def test_walk_blocks_counts_what_the_kernel_walks(lengths, window, blocks):
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    start = bounds[np.repeat(np.arange(len(lengths)), lengths)]
    row = jnp.asarray(first_keys(start, window), jnp.int32)
    assert int(walk_blocks(row)) == blocks


# -- differential heads (``doc_diff_attention_counted``): new cases only ------

def dense_differential(q, k, v, seg, window, lam):
    """``(A1 - lam A2) V`` a pair, float64 on the host: query heads ``2j``,
    ``2j + 1`` on key heads ``2g``, ``2g + 1``, ``V_g = [v_2g | v_2g+1]``."""
    t, h, d = q.shape
    pairs, groups = h // 2, k.shape[1] // 2
    behind = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (seg[:, None] == seg[None, :]) & (behind >= 0) & (behind < window)
    out = np.zeros((t, pairs, 2 * d))
    for j in range(pairs):
        g = j // (pairs // groups)
        maps = []
        for half in (0, 1):
            s = np.where(seen, q[:, 2 * j + half] @ k[:, 2 * g + half].T,
                         -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            maps.append(p / p.sum(-1, keepdims=True))
        out[:, j] = (maps[0] - lam * maps[1]) @ np.concatenate(
            [v[:, 2 * g], v[:, 2 * g + 1]], -1)
    return out


def diff_args(q, k, v, seg, first, lam, dtype=jnp.float32):
    q, k, v, seg, first = as_args(q, k, v, seg, first, dtype)
    return q, k, v, jnp.float32(lam), seg, first


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("h,hkv", [(4, 2), (8, 4), (8, 2)])
def test_differential_jnp_walk_is_the_dense_maps(h, hkv, window):
    q, k, v, seg, start = inputs(h, hkv, 16, 16, seed=h + hkv + window)
    v = np.random.default_rng(window).normal(size=k.shape)
    got = doc_attention._diff_attention_jnp(
        *diff_args(q, k, v, seg, first_keys(start, window), 0.37), 64)
    assert got.shape == (len(seg), h // 2, 32)
    np.testing.assert_allclose(
        np.asarray(got), dense_differential(q, k, v, seg, window, 0.37),
        atol=2e-5)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("h,hkv,pair_blocks", [(4, 2, 1), (8, 4, 2),
                                               (8, 2, 1)])
def test_differential_kernel_is_the_dense_maps(h, hkv, pair_blocks, window,
                                               monkeypatch):
    """The kernel, interpreted, at the model's head width (64: a value pair
    of 128 lanes), float32; one pair block and two (whole groups)."""
    block = doc_attention._BLOCK
    pairs, groups = h // 2, hkv // 2
    grp = pairs // groups
    per_group = block * (grp * (2 * 2 * 128 * 4 + 2 * 128 * 4)
                         + 2 * 2 * 128 * 4 + grp * 2 * (128 + 16) * 4)
    monkeypatch.setattr(doc_attention, "_VMEM_BUDGET",
                        per_group * groups // pair_blocks)
    assert doc_attention._diff_pair_block(pairs, groups, 64, block, 4) \
        == pairs // pair_blocks
    q, k, v, seg, start = inputs(h, hkv, 64, 64, seed=h * hkv + window)
    v = np.random.default_rng(window).normal(size=k.shape)
    got = jax.jit(doc_attention._diff_attention_kernel.__wrapped__,
                  static_argnames="interpret")(
        *diff_args(q, k, v, seg, first_keys(start, window), 0.61),
        interpret=True)
    assert got.shape == (len(seg), h // 2, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), dense_differential(q, k, v, seg, window, 0.61),
        atol=2e-5)


@pytest.mark.parametrize("lengths", [LENGTHS, [70, 33, 129, 1, 90, 60, 200]],
                         ids=["whole_blocks", "ragged_t"])
def test_differential_kernel_and_jnp_agree_in_bfloat16(lengths):
    """The same rounding points (operands bfloat16, ``p`` cast to ``v``'s
    type, float32 sums, one division a map): the two differ by the order of
    their sums, and the kernel is no farther than ``jnp`` from the float64
    maps over the same bfloat16 inputs; windowed."""
    q, k, v, seg, start = inputs(8, 4, 64, 64, seed=11, lengths=lengths)
    v = np.random.default_rng(2).normal(size=k.shape)
    args = diff_args(q, k, v, seg, first_keys(start, 100), 0.5, jnp.bfloat16)
    assert doc_attention._diff_kernel_fits(args[0], args[1])
    got = np.asarray(doc_attention._diff_attention_kernel(*args,
                                                          interpret=True))
    want = np.asarray(doc_attention._diff_attention_jnp(
        *args, doc_attention._BLOCK))
    np.testing.assert_allclose(got, want, atol=5e-6)
    f64 = lambda x: np.asarray(                               # noqa: E731
        jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), np.float64)
    exact = dense_differential(f64(q), f64(k), f64(v), seg, 100, 0.5)
    assert np.abs(got - exact).max() <= 1.05 * np.abs(want - exact).max()


@pytest.mark.parametrize("h,hkv,d,dtype,fits", [
    (40, 20, 64, jnp.bfloat16, True),     # phi4_mini_flash_3.8b
    (40, 20, 64, jnp.float32, False), (40, 20, 32, jnp.bfloat16, False),
    (8, 4, 128, jnp.bfloat16, True), (4, 2, 64, jnp.bfloat16, True)])
def test_differential_kernel_rule_is_a_function_of_the_shapes(h, hkv, d,
                                                              dtype, fits):
    q = jnp.zeros((512, h, d), dtype)
    k = jnp.zeros((512, hkv, d), dtype)
    assert doc_attention._diff_kernel_fits(q, k) is fits
    # the 64-wide heads the plain kernel leaves to jnp (half zeros there)
    if d == 64:
        assert not doc_attention._kernel_fits(q, k, k)


def test_differential_pair_block_is_sized_from_vmem():
    # phi4_mini_flash_3.8b: 20 pairs on 10 groups, half of them a step
    assert doc_attention._diff_pair_block(20, 10, 64, 256, 2) == 10
    assert doc_attention._diff_pair_block(2, 1, 64, 256, 2) == 2


def test_differential_heads_take_the_kernel_on_a_tpu_and_jnp_here():
    q, k, v, seg, start = inputs(8, 4, 64, 64, seed=5)
    args = diff_args(q, k, k, seg, first_keys(start, 64), 0.2, jnp.bfloat16)
    got, fused = jax.jit(doc_diff_attention_counted)(*args)
    assert int(fused) == 0 and got.shape == (len(seg), 4, 128)


def test_heads_that_are_no_whole_pairs_are_refused():
    q, k, v, seg, start = inputs(6, 3, 16, 16, seed=1)
    with pytest.raises(ValueError, match="no whole pairs"):
        doc_diff_attention_counted(*diff_args(q, k, k, seg, start, 0.1))
