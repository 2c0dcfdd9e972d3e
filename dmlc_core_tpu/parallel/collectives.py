"""Rabit-compatible collective API lowered to XLA mesh collectives.

The reference ecosystem's collective surface is rabit's ``Allreduce(op)`` /
``Broadcast(root)`` executed over tracker-brokered TCP trees (SURVEY §2.5,
`tracker.py:166-252`).  On TPU the same API lowers to ``lax.psum``-family ops
over ICI/DCN — XLA routes them; the tree/ring computation disappears.

Two tiers:

* **In-jit** (:func:`allreduce`, :func:`broadcast`, :func:`allgather`):
  shard_map-based, for use *inside* jitted step functions over a Mesh.
* **Eager host-level** (:class:`MeshCollectives`): one-call collectives on
  full arrays — the literal rabit API (``allreduce(x, op='sum')``), backed by
  a tiny jitted program per (shape, op).

The socket-based host collective for non-JAX processes (the tracker data
path) lives in :mod:`dmlc_core_tpu.parallel.rabit`.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import DMLCError, check

__all__ = ["allreduce", "broadcast", "allgather", "reduce_scatter",
           "all_to_all", "MeshCollectives", "OPS"]

OPS: Dict[str, Callable] = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def allreduce(x: jax.Array, axis_name: str, op: str = "sum") -> jax.Array:
    """In-jit allreduce over a mesh axis (use under shard_map/jit)."""
    fn = OPS.get(op)
    if fn is None:
        raise DMLCError(f"unknown allreduce op {op!r}; have {list(OPS)}")
    return fn(x, axis_name)


def broadcast(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """In-jit broadcast from mesh coordinate ``root`` along ``axis_name``."""
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def allgather(x: jax.Array, axis_name: str, axis: int = 0,
              tiled: bool = True) -> jax.Array:
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def all_to_all(x: jax.Array, axis_name: str, split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = True) -> jax.Array:
    """In-jit all-to-all over a mesh axis: split ``split_axis`` into
    ``world`` chunks, send chunk *d* to coordinate *d*, concatenate the
    received chunks along ``concat_axis``.  This is the mapped-primitive
    lowering of the sharded-embedding exchange (DrJAX's mapped
    ``all_to_all``, PAPERS.md: arxiv 2403.07128): when table shards and
    batch ids live on one process's mesh, the same shuffle the
    cross-process exchange does over TCP lowers to a single XLA
    collective over ICI."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


class MeshCollectives:
    """Eager rabit-style collectives over one mesh axis.

    >>> coll = MeshCollectives(mesh, "dp")
    >>> y = coll.allreduce(x)             # sum over the dp axis
    >>> z = coll.broadcast(x, root=0)
    """

    def __init__(self, mesh: Mesh, axis_name: str = "dp"):
        check(axis_name in mesh.axis_names,
              f"axis {axis_name!r} not in mesh {mesh.axis_names}")
        self.mesh = mesh
        self.axis_name = axis_name
        self._cache: Dict[Tuple, Callable] = {}

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis_name]

    def _spec_in(self) -> P:
        # input arrays are sharded on their leading dim over the axis
        return P(self.axis_name)

    def _jitted(self, kind: str, op: str, root: int,
                shape: Tuple[int, ...], dtype) -> Callable:
        key = (kind, op, root, shape, dtype)
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        axis = self.axis_name

        if kind == "allreduce":
            # each rank contributes its row; result identical on all ranks
            def body(x):
                return allreduce(x, axis, op)
        elif kind == "broadcast":
            def body(x):
                return broadcast(x, axis, root)
        elif kind == "allgather":
            def body(x):
                return allgather(x, axis)
        elif kind == "all_to_all":
            # local block is [1, world, ...]: exchange the second axis,
            # then restore the leading layout so rank r's block is the
            # column in[:, r] — i.e. out[d] = in[:, d] globally
            def body(x):
                y = all_to_all(x, axis, split_axis=1, concat_axis=0)
                return jnp.swapaxes(y, 0, 1)
        else:
            raise DMLCError(f"unknown collective {kind!r}")

        out_spec = P() if kind == "allgather" else P(axis)

        def run(stacked):
            return jax.shard_map(body, mesh=self.mesh,
                             in_specs=P(axis), out_specs=out_spec,
                             check_vma=False)(stacked)
        fn = jax.jit(run)
        self._cache[key] = fn
        return fn

    def _stack(self, per_rank: np.ndarray) -> jax.Array:
        """per_rank: [world, ...] array, row r = rank r's contribution."""
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return jax.device_put(per_rank, sharding)

    def allreduce(self, per_rank: np.ndarray, op: str = "sum") -> np.ndarray:
        """Rabit Allreduce: per_rank[world, ...] → reduced [...] (same on all)."""
        per_rank = np.asarray(per_rank)
        check(per_rank.shape[0] == self.world_size,
              f"leading dim {per_rank.shape[0]} != world {self.world_size}")
        x = self._stack(per_rank)
        fn = self._jitted("allreduce", op, 0, per_rank.shape, per_rank.dtype)
        out = np.asarray(fn(x))
        return out[0]  # all rows identical post-allreduce

    def broadcast(self, per_rank: np.ndarray, root: int = 0) -> np.ndarray:
        per_rank = np.asarray(per_rank)
        x = self._stack(per_rank)
        fn = self._jitted("broadcast", "sum", root, per_rank.shape,
                          per_rank.dtype)
        return np.asarray(fn(x))[0]

    def allgather(self, per_rank: np.ndarray) -> np.ndarray:
        """Returns the full [world, ...] stack on host."""
        per_rank = np.asarray(per_rank)
        x = self._stack(per_rank)
        fn = self._jitted("allgather", "sum", 0, per_rank.shape,
                          per_rank.dtype)
        return np.asarray(fn(x))

    def all_to_all(self, per_rank: np.ndarray) -> np.ndarray:
        """Rabit-style all-to-all: ``per_rank[src, dst, ...]`` (row *src*
        = rank *src*'s outbox, entry *dst* = its chunk for rank *dst*)
        → ``out[dst, src, ...]`` where ``out[d]`` is rank *d*'s inbox —
        ``out[d, s] == per_rank[s, d]``.  One XLA collective; this is the
        in-mesh lowering of the sharded-embedding id/row shuffle."""
        per_rank = np.asarray(per_rank)
        check(per_rank.ndim >= 2
              and per_rank.shape[0] == self.world_size
              and per_rank.shape[1] == self.world_size,
              f"all_to_all wants [world, world, ...], got {per_rank.shape}")
        x = self._stack(per_rank)
        fn = self._jitted("all_to_all", "sum", 0, per_rank.shape,
                          per_rank.dtype)
        return np.asarray(fn(x))