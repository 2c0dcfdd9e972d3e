"""Deep & Cross Network v2 over sparse streaming batches.

Completes the CTR model family (logreg → FM → FFM → DeepFM → DCNv2): where
FM fixes the feature-interaction form to a rank-1 inner product, the cross
network LEARNS the interaction weights layer by layer —

    x_{l+1} = x_0 ⊙ (W_l x_l + b_l) + x_l,          x_0 = Σ_k v_k·E[id_k]

(Wang et al., "DCN V2", 2021) — each layer adds one more multiplicative
order of x_0 while the residual keeps lower orders intact.  The reference
library has no model zoo (it is the data/runtime backbone under xgboost);
this model exists because its [D,D] cross matmuls are exactly what the MXU
wants: the sparse gather happens once, every cross layer is dense compute.

TPU formulation: the L cross layers run as one ``lax.scan`` over stacked
``[L, D, D]`` weights (same compiled-once pattern as DeepFM's tower —
``deep.py _tower_sequential``), so depth never unrolls into L XLA ops.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .sparse import Params, task_loss
from ..ops.csr import csr_dense_matvec, csr_embed_sum

__all__ = ["DCNv2"]


class DCNv2:
    """Cross network (v2, full-matrix) + linear wide term.

    ``layers`` is the cross depth (each layer captures one higher
    interaction order).
    """

    def __init__(self, num_features: int, dim: int = 16, layers: int = 3,
                 l2: float = 0.0, init_scale: float = 0.01,
                 task: str = "binary"):
        self.num_features = num_features
        self.dim = dim
        self.layers = layers
        self.l2 = l2
        self.init_scale = init_scale
        self.task = task

    def init(self, rng: jax.Array) -> Params:
        k1, k2, k3 = jax.random.split(rng, 3)
        d, L = self.dim, self.layers
        return {
            "w0": jnp.zeros((), jnp.float32),
            "w": jnp.zeros((self.num_features,), jnp.float32),
            "v": self.init_scale * jax.random.normal(
                k1, (self.num_features, d), jnp.float32),
            "cross": {
                # ~1/sqrt(d) keeps x_l's scale stable through depth: the
                # elementwise x0 product already multiplies magnitudes
                "w": jax.random.normal(k2, (L, d, d), jnp.float32)
                     * (1.0 / jnp.sqrt(d)),
                "b": jnp.zeros((L, d), jnp.float32),
            },
            "head": {
                "w": jax.random.normal(k3, (d,), jnp.float32)
                     * (1.0 / jnp.sqrt(d)),
                "b": jnp.zeros((), jnp.float32),
            },
        }

    def _embed(self, params: Params, batch: Dict[str, jax.Array]):
        """(linear[B], x0[B,D]) — one sparse gather; everything after is
        dense."""
        num_rows = batch["labels"].shape[0]
        ids, vals, segs = batch["ids"], batch["vals"], batch["segments"]
        linear = csr_dense_matvec(ids, vals, segs, params["w"], num_rows)
        x0 = csr_embed_sum(ids, vals, segs, params["v"], num_rows)
        return linear, x0

    @staticmethod
    def _cross(cross: Dict[str, jax.Array], x0: jax.Array) -> jax.Array:
        def layer(x, wb):
            w, b = wb
            return x0 * (x @ w + b) + x, None

        with jax.named_scope("dcn_cross"):
            out, _ = jax.lax.scan(layer, x0, (cross["w"], cross["b"]))
        return out

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        linear, x0 = self._embed(params, batch)
        xL = self._cross(params["cross"], x0)
        with jax.named_scope("dcn_head"):
            return (params["w0"] + linear + xL @ params["head"]["w"]
                    + params["head"]["b"])

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return task_loss(self.forward(params, batch), batch, self.task,
                         self.l2, params["w"], params["v"],
                         params["cross"]["w"], params["head"]["w"])
