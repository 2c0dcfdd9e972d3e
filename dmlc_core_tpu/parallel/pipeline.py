"""Pipeline parallelism over a named 'pp' mesh axis (GPipe-style).

The reference has no pipeline-across-devices concept — its pipelining is
producer/consumer prefetch threads inside one process (SURVEY §2.5
"Parallelism strategies", `threadediter.h:46`).  On a TPU mesh the same
capability — stages of a computation running concurrently on different
hardware — is expressed as a schedule over a mesh axis: device *s* along
'pp' owns stage *s*'s parameters, microbatches stream through the stages,
and stage hand-offs ride ICI via ``lax.ppermute``.

Schedule.  Fill-and-drain (GPipe): with S stages and M microbatches the
scan runs ``T = M + S − 1`` ticks; at tick *t* stage *s* processes
microbatch ``t − s`` (bubble ticks compute on zeros and are masked out of
the collected output).  Everything is a single ``lax.scan`` inside one
``shard_map`` — no Python-level per-tick dispatch, one compiled program.

Contract.  ``stage_fn(stage_params, x) -> y`` must preserve the microbatch
shape (uniform-width tower; put input/output projections outside the
pipeline).  ``stage_params`` leaves are stacked on a leading stage axis of
size S and sharded ``P('pp')``, so each device holds exactly its stage's
slice — the parameter-memory win pipeline parallelism exists for.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_pipeline", "split_microbatches", "stack_stage_params",
           "stage_sharding"]


def split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] → [M, B/M, ...] (B must divide evenly)."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by M={num_microbatches}")
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])


def stack_stage_params(per_stage: list) -> dict:
    """[{leaf: array}, ...] per stage → {leaf: array[S, ...]} stacked."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def make_pipeline(mesh: Mesh, axis: str,
                  stage_fn: Callable) -> Callable:
    """Build ``run(stage_params, xs) -> ys``: microbatches ``xs[M, mb, F]``
    through S = mesh.shape[axis] stages of ``stage_fn``.

    Returns outputs ``[M, mb, F]`` replicated over the axis.  Stage
    parameters are consumed sharded ``P(axis)`` on their stacked leading
    axis; inputs/outputs are replicated (shard the batch over 'dp', not
    'pp' — the two axes compose in a 2-D mesh).
    """
    num_stages = mesh.shape[axis]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P())
    def run(stage_params, xs):
        # my slice of the stacked stage axis has length 1 — drop it
        params_me = jax.tree.map(lambda a: a[0], stage_params)
        s = jax.lax.axis_index(axis)
        num_m = xs.shape[0]
        ticks = num_m + num_stages - 1
        # stage i hands its activation to stage i+1; the last stage's
        # output leaves the ring (collected below), stage 0's input comes
        # from the microbatch stream
        perm = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(carry, t):
            cur, outs = carry
            y = stage_fn(params_me, cur)
            handed = jax.lax.ppermute(y, axis, perm)
            inject = xs[jnp.clip(t + 1, 0, num_m - 1)]
            cur = jnp.where(s == 0, inject, handed)
            # the last stage finished microbatch t-(S-1) this tick
            oidx = t - (num_stages - 1)
            ok = jnp.logical_and(oidx >= 0, s == num_stages - 1)
            ci = jnp.clip(oidx, 0, num_m - 1)
            outs = outs.at[ci].set(jnp.where(ok, y, outs[ci]))
            return (cur, outs), None

        cur0 = jnp.where(s == 0, xs[0], jnp.zeros_like(xs[0]))
        # the carry becomes device-varying over 'pp' inside the loop, so
        # the initial value must carry the same varying-manual-axes type
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, (cur0, outs0),
                                    jnp.arange(ticks))
        # only the last stage holds real outputs; psum replicates them so
        # the caller sees an ordinary (unsharded-over-pp) result
        return jax.lax.psum(
            jnp.where(s == num_stages - 1, outs, jnp.zeros_like(outs)),
            axis)

    return run


def stage_sharding(mesh: Mesh, axis: str = "pp") -> NamedSharding:
    """Sharding for stacked stage params (leading stage axis over 'pp')."""
    return NamedSharding(mesh, P(axis))
