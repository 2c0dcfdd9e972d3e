"""Device op tests: CSR primitives vs dense references."""

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
