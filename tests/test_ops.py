"""Device op tests: CSR primitives vs dense references; Pallas kernel
(interpret mode) vs XLA reference."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.ops import csr_dense_matvec, csr_embed_sum, fm_pairwise  # noqa: E402


def make_batch(rng, B=6, F=40, max_nnz=5, pad=7):
    rows = []
    ids, vals, segs = [], [], []
    dense = np.zeros((B, F), np.float32)
    for b in range(B):
        n = int(rng.integers(1, max_nnz))
        idx = rng.choice(F, n, replace=False)
        v = rng.random(n).astype(np.float32)
        dense[b, idx] = v
        ids.extend(idx.tolist())
        vals.extend(v.tolist())
        segs.extend([b] * n)
    target = len(ids) + pad
    while len(ids) < target:
        ids.append(0)
        vals.append(0.0)
        segs.append(B)
    return (jnp.array(ids, jnp.int32), jnp.array(vals, jnp.float32),
            jnp.array(segs, jnp.int32), dense)


def test_csr_dense_matvec_matches_dense():
    rng = np.random.default_rng(0)
    ids, vals, segs, dense = make_batch(rng)
    w = jnp.array(rng.random(40), jnp.float32)
    out = csr_dense_matvec(ids, vals, segs, w, dense.shape[0])
    np.testing.assert_allclose(out, dense @ np.asarray(w), rtol=1e-5)


def test_csr_embed_sum_matches_dense():
    rng = np.random.default_rng(1)
    ids, vals, segs, dense = make_batch(rng)
    table = jnp.array(rng.random((40, 8)), jnp.float32)
    out = csr_embed_sum(ids, vals, segs, table, dense.shape[0])
    np.testing.assert_allclose(out, dense @ np.asarray(table), rtol=1e-5)


def test_fm_pairwise_matches_bruteforce():
    rng = np.random.default_rng(2)
    ids, vals, segs, dense = make_batch(rng)
    table = np.asarray(rng.random((40, 8)), np.float32)
    out = fm_pairwise(ids, vals, segs, jnp.array(table), dense.shape[0])
    # brute force: sum_{i<j} <v_i, v_j> x_i x_j
    expect = []
    for b in range(dense.shape[0]):
        s = 0.0
        nz = np.nonzero(dense[b])[0]
        for ii in range(len(nz)):
            for jj in range(ii + 1, len(nz)):
                i, j = nz[ii], nz[jj]
                s += float(table[i] @ table[j]) * dense[b, i] * dense[b, j]
        expect.append(s)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_pallas_embed_bag_interpret_matches_reference():
    from dmlc_core_tpu.ops.pallas_embed import (embed_bag_pallas,
                                                embed_bag_reference)
    rng = np.random.default_rng(3)
    B, K, F, D = 4, 8, 64, 128
    ids = jnp.array(rng.integers(0, F, (B, K)), jnp.int32)
    vals = jnp.array(rng.random((B, K)), jnp.float32)
    table = jnp.array(rng.random((F, D)), jnp.float32)
    ref = embed_bag_reference(ids, vals, table)
    out = embed_bag_pallas(ids, vals, table, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5)


def test_engine_dispatch_deterministic(monkeypatch):
    """Default dispatch is a pure function of shape (ADVICE r3: every host
    on a shared mesh must pick the same engine) and always XLA: the DMA
    kernel is not measured on this round's chip; default XLA, so pallas is
    opt-in via DMLC_EMBED_ENGINE=pallas or DMLC_EMBED_AUTOTUNE=1."""
    from dmlc_core_tpu.ops import pallas_embed as pe

    monkeypatch.delenv("DMLC_EMBED_AUTOTUNE", raising=False)
    for shape in ((1024, 32, 64), (1024, 32, 8), (8, 32, 512)):
        assert pe._pallas_profitable(*shape, fused=False) is False
        # same inputs, same verdict — repeat-call determinism
        assert pe._pallas_profitable(*shape, fused=False) is False


def test_pallas_embed_chunked_matches_reference(monkeypatch):
    """Batches whose flat ids/vals exceed the SMEM scalar-prefetch budget
    split into independent row-chunk pallas_calls (1MB+ scalar operands
    overflow v5e's SMEM; not measured on this round's chip; default XLA).
    Force a tiny cap so
    the chunk path runs at test scale; a non-multiple tail chunk included."""
    from dmlc_core_tpu.ops import pallas_embed as pe

    monkeypatch.setenv("DMLC_PALLAS_SMEM_SCALARS", "64")   # → 8-row chunks
    rng = np.random.default_rng(5)
    B, K, F, D = 44, 8, 64, 128          # 5 full chunks + 4-row tail
    assert pe._chunk_rows(K) == 8
    ids = jnp.array(rng.integers(0, F, (B, K)), jnp.int32)
    vals = jnp.array(rng.random((B, K)), jnp.float32)
    table = jnp.array(rng.random((F, D)), jnp.float32)
    ref = pe.embed_bag_reference(ids, vals, table)
    out = pe.embed_bag_pallas(ids, vals, table, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    s1, s2 = pe.fm_terms_pallas(ids, vals, table, interpret=True)
    g = table[ids]
    np.testing.assert_allclose(
        s1, jnp.einsum("bk,bkd->bd", vals, g), rtol=1e-5)
    np.testing.assert_allclose(
        s2, jnp.einsum("bk,bkd->bd", vals * vals, g * g), rtol=1e-5)


def test_engine_env_pin(monkeypatch):
    """DMLC_EMBED_ENGINE pins the engine regardless of auto heuristics —
    the multi-host escape hatch."""
    from dmlc_core_tpu.ops import pallas_embed as pe

    monkeypatch.setenv("DMLC_EMBED_ENGINE", "xla")
    assert pe._resolve_engine("auto", 512) == "xla"
    assert pe._resolve_engine("pallas", 512) == "xla"   # pin beats explicit
    monkeypatch.setenv("DMLC_EMBED_ENGINE", "bogus")
    import pytest as _pytest
    with _pytest.raises(ValueError):
        pe._resolve_engine("auto", 512)


def test_engine_autotune_logic(monkeypatch):
    """Opt-in timed autotune (DMLC_EMBED_AUTOTUNE=1): picks by measured
    time, caches per shape, and a kernel failure degrades to XLA instead of
    raising — exercised on CPU since the real gate only opens on TPU."""
    from dmlc_core_tpu.ops import pallas_embed as pe

    pe._engine_time_cache.clear()
    # kernel raises (CPU without interpret) → False, no exception
    assert pe._pallas_faster_timed(64, 4, 8, fused=False) is False
    assert pe._engine_time_cache[(4, 8, False)] is False

    # substitute engines with controllable speeds: pallas wins.  The slow
    # engine must be slow when COMPILED (the autotuner jits the xla side),
    # so it carries real FLOPs, not a python sleep that traces away.
    def fast(ids, vals, table):
        return jnp.zeros((ids.shape[0], table.shape[1]), jnp.float32)

    def slow(ids, vals, table, square=False):
        x = jnp.ones((400, 400), jnp.float32)
        for _ in range(30):
            x = (x @ x) * 1e-3
        return jnp.zeros((ids.shape[0], table.shape[1]),
                         jnp.float32) + x[0, 0]

    monkeypatch.setattr(pe, "embed_bag_pallas", fast)
    monkeypatch.setattr(pe, "embed_bag_reference", slow)
    pe._engine_time_cache.clear()
    assert pe._pallas_faster_timed(64, 5, 8, fused=False) is True
    # cached: flipping the implementations does not change the verdict
    monkeypatch.setattr(pe, "embed_bag_pallas", slow)
    assert pe._pallas_faster_timed(64, 5, 8, fused=False) is True
    # DMLC_EMBED_AUTOTUNE=1 routes _pallas_profitable through the timer
    monkeypatch.setenv("DMLC_EMBED_AUTOTUNE", "1")
    assert pe._pallas_profitable(64, 5, 8, fused=False) is True
    pe._engine_time_cache.clear()
