"""Streaming sparse models: logistic regression and factorization machine.

These are the framework's flagship models (SURVEY §7 phase 4: "train a
streaming model (logistic regression / FM on a1a) end-to-end"): wide sparse
feature spaces consumed directly from the ingest pipeline's flat-CSR batches
(``pipeline.packing.pack_flat``).

Functional JAX style: a model is ``init(rng) -> params`` (a pytree of
``jax.Array``) plus pure ``forward(params, batch)`` / ``loss(params, batch)``
— trivially jittable, shardable and optax-compatible.  Sharding recipes live
in :mod:`dmlc_core_tpu.models.train`.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..ops.csr import csr_dense_matvec, csr_embed_sum, fm_pairwise

__all__ = ["SparseLogReg", "FactorizationMachine", "weighted_bce",
           "weighted_mse", "task_loss"]

Params = Dict[str, jax.Array]


def weighted_bce(logits: jax.Array, labels: jax.Array,
                 weights: jax.Array) -> jax.Array:
    """Per-example-weighted binary cross-entropy on {0,1} or {-1,1} labels.
    Padding rows carry weight 0 and drop out of both numerator and count."""
    y = jnp.where(labels > 0, 1.0, 0.0)
    ls = jax.nn.log_sigmoid(logits)
    nls = jax.nn.log_sigmoid(-logits)
    per = -(y * ls + (1.0 - y) * nls)
    wsum = jnp.maximum(weights.sum(), 1e-9)
    return (per * weights).sum() / wsum


def weighted_mse(pred: jax.Array, labels: jax.Array,
                 weights: jax.Array) -> jax.Array:
    wsum = jnp.maximum(weights.sum(), 1e-9)
    return (weights * (pred - labels) ** 2).sum() / wsum


def task_loss(out: jax.Array, batch: Dict[str, jax.Array], task: str,
              l2: float, *regs: jax.Array) -> jax.Array:
    """Shared loss tail of the factorization-model family: task dispatch
    (binary BCE / regression MSE) + l2 on the given parameter arrays."""
    with jax.named_scope("loss"):
        if task == "binary":
            base = weighted_bce(out, batch["labels"], batch["weights"])
        else:
            base = weighted_mse(out, batch["labels"], batch["weights"])
        if l2:
            base = base + l2 * sum(jnp.sum(r ** 2) for r in regs)
    return base


class SparseLogReg:
    """w·x + b over flat-CSR batches (the reference ecosystem's
    canonical linear-model consumer — xgboost/mxnet read RowBlocks the same
    way)."""

    def __init__(self, num_features: int, l2: float = 0.0):
        self.num_features = num_features
        self.l2 = l2

    def init(self, rng: jax.Array) -> Params:
        return {
            "w": jnp.zeros((self.num_features,), jnp.float32),
            "b": jnp.zeros((), jnp.float32),
        }

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        num_rows = batch["labels"].shape[0]
        z = csr_dense_matvec(batch["ids"], batch["vals"], batch["segments"],
                             params["w"], num_rows)
        return z + params["b"]

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        logits = self.forward(params, batch)
        reg = self.l2 * jnp.sum(params["w"] ** 2) if self.l2 else 0.0
        return weighted_bce(logits, batch["labels"], batch["weights"]) + reg


class FactorizationMachine:
    """Second-order FM: w0 + Σ w_i x_i + ½Σ_d[(Σ v_id x_i)² − Σ v_id² x_i²].

    ``dim`` is the factor dimension; the factor table ``v`` [F, dim] is the
    model-parallel shard target (dim axis over the mesh 'mp' axis — gathers
    stay local, only the final per-row reduction crosses chips).
    """

    def __init__(self, num_features: int, dim: int = 16, l2: float = 0.0,
                 init_scale: float = 0.01, task: str = "binary"):
        self.num_features = num_features
        self.dim = dim
        self.l2 = l2
        self.init_scale = init_scale
        self.task = task

    def init(self, rng: jax.Array) -> Params:
        return {
            "w0": jnp.zeros((), jnp.float32),
            "w": jnp.zeros((self.num_features,), jnp.float32),
            "v": self.init_scale * jax.random.normal(
                rng, (self.num_features, self.dim), jnp.float32),
        }

    @property
    def row_tables(self) -> tuple:
        """The leaves :meth:`loss` reads only at ``batch["ids"]`` rows, so
        that their gradient is zero on every other row (the train step's
        fused table update, ``models/train.py``); ``l2`` reads them whole."""
        return () if self.l2 else ("v", "w")

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        num_rows = batch["labels"].shape[0]
        linear = csr_dense_matvec(batch["ids"], batch["vals"],
                                  batch["segments"], params["w"], num_rows)
        pair = fm_pairwise(batch["ids"], batch["vals"], batch["segments"],
                           params["v"], num_rows)
        return params["w0"] + linear + pair

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return task_loss(self.forward(params, batch), batch, self.task,
                         self.l2, params["w"], params["v"])
