"""Field-aware factorization machine over libfm batches.

The libfm format's third coordinate (`field:index:value`, reference parser
`src/data/libfm_parser.h:36-93`, field array `include/dmlc/data.h:168`) has
no consumer inside the reference — it exists for downstream FFM trainers.
This model closes that loop TPU-natively: a jittable FFM whose batches come
straight off ``DeviceLoader(..., fields=True)``.

Model.  ŷ = w0 + Σᵢ wᵢxᵢ + Σ_{i<j} ⟨v[idᵢ, fⱼ], v[idⱼ, fᵢ]⟩ xᵢxⱼ with one
latent vector **per (feature, field) pair**: v is ``[F, nf, d]``.

TPU formulation.  The O(K²)-pair sum is reshaped into field-bucket sums so
it runs as dense einsum/segment-sum work on the VPU/MXU instead of a pair
loop: with G[b,g,f,:] = Σ_{k: f_k=g} x_k · v[id_k, f, :],

    Σ_{i≠j} x_i x_j ⟨v_i[f_j], v_j[f_i]⟩ = Σ_{g,h} ⟨G[b,g,h], G[b,h,g]⟩
                                            − Σ_k x_k² ‖v[id_k, f_k]‖²

and the pairwise term is half that.  Cost: one [·, nf, d] gather of the
factor table plus an einsum over [B, nf, nf, d] — choose ``num_fields``
accordingly (G is B·nf²·d floats; typical CTR data has nf ≲ 40).

Batches are flat CSR like the rest of the model family's
(``ids/vals/fields[nnz] + segments``).  Padding entries carry id 0, val 0,
field 0 — zero value means they contribute nothing to any sum.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .sparse import Params, task_loss
from ..ops.csr import csr_dense_matvec

__all__ = ["FieldAwareFM"]


def _check_fields(batch: Dict[str, jax.Array]) -> jax.Array:
    if "fields" not in batch:
        raise KeyError(
            "FieldAwareFM needs a 'fields' batch array — construct the "
            "DeviceLoader with fields=True over libfm-format data")
    return batch["fields"]


class FieldAwareFM:
    """FFM with per-(feature, field) latent vectors ``v[F, nf, d]``.

    ``num_fields`` must cover every field id in the data (ids ≥ num_fields
    are clipped into the last field rather than indexing out of bounds —
    XLA gathers clamp, which would silently alias; the explicit clip makes
    the behavior deterministic and documented).
    """

    def __init__(self, num_features: int, num_fields: int, dim: int = 4,
                 l2: float = 0.0, init_scale: float = 0.01,
                 task: str = "binary"):
        self.num_features = num_features
        self.num_fields = num_fields
        self.dim = dim
        self.l2 = l2
        self.init_scale = init_scale
        self.task = task

    def init(self, rng: jax.Array) -> Params:
        return {
            "w0": jnp.zeros((), jnp.float32),
            "w": jnp.zeros((self.num_features,), jnp.float32),
            "v": self.init_scale * jax.random.normal(
                rng, (self.num_features, self.num_fields, self.dim),
                jnp.float32),
        }

    # -- pairwise term ----------------------------------------------------
    def _pair_flat(self, params: Params, ids, vals, fields, segments,
                   num_rows: int) -> jax.Array:
        nf = self.num_fields
        f = jnp.clip(fields, 0, nf - 1)
        V = params["v"][ids]                       # [nnz, nf, d]
        # scatter each value's [nf, d] contribution into its (row, field)
        # bucket; padding values land in the scratch row (segment ==
        # num_rows) and are dropped with it
        target = segments * nf + f                 # [nnz]
        G = jax.ops.segment_sum(vals[:, None, None] * V, target,
                                num_segments=(num_rows + 1) * nf)
        G = G.reshape(num_rows + 1, nf, nf, -1)[:num_rows]   # [B, nf, nf, d]
        cross = jnp.einsum("bgfd,bfgd->b", G, G)
        own = jnp.take_along_axis(
            V, f[:, None, None], axis=1)[:, 0, :]            # [nnz, d]
        diag = jax.ops.segment_sum(
            vals * vals * jnp.sum(own * own, axis=-1), segments,
            num_segments=num_rows + 1)[:num_rows]
        return 0.5 * (cross - diag)

    # -- public surface ---------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        fields = _check_fields(batch)
        num_rows = batch["labels"].shape[0]
        linear = csr_dense_matvec(batch["ids"], batch["vals"],
                                  batch["segments"], params["w"], num_rows)
        pair = self._pair_flat(params, batch["ids"], batch["vals"], fields,
                               batch["segments"], num_rows)
        return params["w0"] + linear + pair

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return task_loss(self.forward(params, batch), batch, self.task,
                         self.l2, params["w"], params["v"])
