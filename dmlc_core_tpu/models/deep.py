"""DeepFM: factorization machine + deep MLP tower over embedded features.

Extends the sparse family (logreg → FM → FFM) with the deep-CTR shape:
``ŷ = w0 + Σ wᵢxᵢ + ½Σ_d[(Σ vx)² − Σ v²x²] + MLP(Σ vx)``.  The tower input
is the FM's first-order embedding reduction ``s1[B, D]`` — already computed
for the pairwise term, so the deep half costs no extra gather.

The tower is a uniform-width stack (D → D per layer, tanh) applied with
``lax.scan`` over stacked layer params ``[L, D, D]`` — exactly the layout
:mod:`dmlc_core_tpu.parallel.pipeline` consumes, so the same parameters run
either sequentially (single chip) or pipeline-parallel over a 'pp' mesh
axis (``with_pipelined_tower``), bit-for-tolerance identical.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .sparse import Params, task_loss
from ..ops.csr import csr_dense_matvec, csr_embed_sum

__all__ = ["DeepFM"]


def _tower_sequential(tower: Dict[str, jax.Array], h: jax.Array) -> jax.Array:
    def layer(carry, wb):
        w, b = wb
        return jnp.tanh(carry @ w + b), None
    out, _ = jax.lax.scan(layer, h, (tower["w"], tower["b"]))
    return out


class DeepFM:
    """FM + L-layer deep tower on the embedded features.

    ``layers`` is the tower depth; the tower width equals ``dim`` (the
    pipeline contract: stages preserve shape).  ``with_pipelined_tower``
    returns a copy whose tower runs GPipe-style over a 'pp' mesh axis —
    ``layers`` must equal the axis size, and the batch must divide by
    ``microbatches``.
    """

    def __init__(self, num_features: int, dim: int = 16, layers: int = 2,
                 l2: float = 0.0, init_scale: float = 0.01,
                 task: str = "binary"):
        self.num_features = num_features
        self.dim = dim
        self.layers = layers
        self.l2 = l2
        self.init_scale = init_scale
        self.task = task
        self._tower = _tower_sequential

    def with_pipelined_tower(self, mesh, axis: str = "pp",
                             microbatches: int = 4) -> "DeepFM":
        from ..parallel.pipeline import make_pipeline, split_microbatches
        if mesh.shape[axis] != self.layers:
            raise ValueError(
                f"pipelined tower needs layers == mesh['{axis}'] "
                f"({self.layers} != {mesh.shape[axis]})")
        run = make_pipeline(
            mesh, axis, lambda p, x: jnp.tanh(x @ p["w"] + p["b"]))

        def tower_pp(tower, h):
            xs = split_microbatches(h, microbatches)
            return run(tower, xs).reshape(h.shape)

        clone = DeepFM(self.num_features, self.dim, self.layers, self.l2,
                       self.init_scale, self.task)
        clone._tower = tower_pp
        return clone

    def init(self, rng: jax.Array) -> Params:
        k1, k2, k3 = jax.random.split(rng, 3)
        d, L = self.dim, self.layers
        return {
            "w0": jnp.zeros((), jnp.float32),
            "w": jnp.zeros((self.num_features,), jnp.float32),
            "v": self.init_scale * jax.random.normal(
                k1, (self.num_features, d), jnp.float32),
            "tower": {
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

    def _terms(self, params: Params, batch: Dict[str, jax.Array]):
        """(linear[B], s1[B,D], s2[B,D])."""
        num_rows = batch["labels"].shape[0]
        ids, vals, segs = batch["ids"], batch["vals"], batch["segments"]
        linear = csr_dense_matvec(ids, vals, segs, params["w"], num_rows)
        s1 = csr_embed_sum(ids, vals, segs, params["v"], num_rows)
        s2 = csr_embed_sum(ids, vals * vals, segs,
                           params["v"] * params["v"], num_rows)
        return linear, s1, s2

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        linear, s1, s2 = self._terms(params, batch)
        pair = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
        deep = self._tower(params["tower"], s1) @ params["head"]["w"] \
            + params["head"]["b"]
        return params["w0"] + linear + pair + deep

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return task_loss(self.forward(params, batch), batch, self.task,
                         self.l2, params["w"], params["v"],
                         params["tower"]["w"], params["head"]["w"])
