"""Sparse CSR ops on device — the TPU-native replacement for the reference's
CPU-side ``Row::SDot`` consumer loop (`data.h:134`).

Batches arrive from the pipeline layer in **flat padded CSR** form (see
:mod:`dmlc_core_tpu.pipeline.packing`): ``ids[nnz]``, ``vals[nnz]``,
``segments[nnz]`` (row id per value, padding rows = batch_size).  All ops are
jit-friendly: static shapes, no data-dependent control flow.

* :func:`csr_dense_matvec` — x·w for a weight vector (logistic regression).
* :func:`csr_embed_sum`    — Σ_k vals·E[ids] per row (embedding bag / FM).
* :func:`fm_pairwise`      — factorization-machine second-order term via the
  (Σ)²−Σ() identity, MXU/VPU-friendly.

Every gather runs under ``jax.named_scope("csr_gather")`` and every row
reduction under ``csr_segment_sum``: the names ride each HLO op's
``op_name`` (the backward scatter carries ``transpose(jvp(csr_gather))``),
so a device trace is read by them and not by ``fusion.N``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["csr_dense_matvec", "csr_embed_sum", "fm_pairwise"]


def csr_dense_matvec(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                     w: jax.Array, num_rows: int) -> jax.Array:
    """Per-row sparse dot with a dense vector: out[r] = Σ vals[i]·w[ids[i]]
    over i with segments[i]==r.  Padding entries must carry vals==0."""
    with jax.named_scope("csr_gather"):
        picked = w[ids] * vals
    with jax.named_scope("csr_segment_sum"):
        return jax.ops.segment_sum(picked, segments,
                                   num_segments=num_rows + 1)[:num_rows]


def csr_embed_sum(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                  table: jax.Array, num_rows: int) -> jax.Array:
    """Weighted embedding bag: out[r, :] = Σ vals[i]·table[ids[i], :].

    ``table``: [num_features, dim].  Output [num_rows, dim].
    """
    with jax.named_scope("csr_gather"):
        gathered = table[ids] * vals[:, None]
    with jax.named_scope("csr_segment_sum"):
        return jax.ops.segment_sum(gathered, segments,
                                   num_segments=num_rows + 1)[:num_rows]


def fm_pairwise(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                table: jax.Array, num_rows: int) -> jax.Array:
    """Factorization-machine 2nd-order term per row:
    0.5·Σ_d [(Σ_i v_i x_i)² − Σ_i (v_i x_i)²].

    Uses the classic O(nnz·d) identity; both segment sums fuse into one pass
    under XLA.  Returns [num_rows]."""
    with jax.named_scope("csr_gather"):
        vx = table[ids] * vals[:, None]                    # [nnz, d]
    with jax.named_scope("csr_segment_sum"):
        s1 = jax.ops.segment_sum(vx, segments,
                                 num_segments=num_rows + 1)[:num_rows]
        s2 = jax.ops.segment_sum(vx * vx, segments,
                                 num_segments=num_rows + 1)[:num_rows]
    return 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
