"""Ring attention: sequence/context parallelism over a mesh axis.

Net-new TPU-native capability (SURVEY §5 "long-context"): the reference's
closest analogue is partitioning an unbounded 1-D byte stream across ranks
with correct boundary handling (`input_split_base.cc:30-64`); the same shape
on a sequence of tokens is ring attention — each device owns a sequence
shard, and K/V shards rotate around the mesh axis via ``lax.ppermute`` while
a running (online-softmax) accumulator keeps the computation exact.

Properties:

* exact — matches full attention to float tolerance (tested on the virtual
  CPU mesh against a single-device reference);
* memory O(T/N) per device for any sequence length T over N devices;
* comm = N-1 ppermute hops of the local K/V block, riding ICI neighbors;
* causal masking uses global positions, so shards need no halo exchange.

API: :func:`ring_attention` is the inside-shard_map building block;
:func:`make_ring_attention` wraps it in shard_map over a named axis for use
on ``[batch, seq, heads, dim]`` arrays sharded on ``seq``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "make_ring_attention", "reference_attention"]


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False) -> jax.Array:
    """Single-device exact attention. q,k,v: [B, T, H, D] → [B, T, H, D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_update(q, k_blk, v_blk, m, l, o, q_pos, k_pos, causal, scale):
    """Online-softmax accumulate one K/V block into (m, l, o)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale  # [B,H,Tq,Tk]
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]               # [Tq, Tk]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    blk_max = jnp.max(scores, axis=-1, keepdims=True)          # [B,H,Tq,1]
    blk_max = jnp.maximum(blk_max, -1e30)  # fully-masked rows stay finite
    new_m = jnp.maximum(m, blk_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m)                                # [B,H,Tq,Tk]
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    new_l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
    new_o = o * jnp.moveaxis(correction, 1, 2) + pv
    return new_m, new_l, new_o


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False) -> jax.Array:
    """Blockwise-exact attention with K/V rotating over ``axis_name``.

    Call inside shard_map; q,k,v are the LOCAL sequence shards
    [B, T_local, H, D].  Shard i initially holds K/V block i; at step s it
    processes block (i - s) mod N received via ppermute.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    b, t_local, h, d = q.shape
    q_pos = idx * t_local + jnp.arange(t_local)

    m = jnp.full((b, h, t_local, 1), -jnp.inf, q.dtype)
    l = jnp.zeros((b, h, t_local, 1), q.dtype)
    o = jnp.zeros_like(q)

    def body(s, carry):
        m, l, o, k_blk, v_blk = carry
        src_block = (idx - s) % n           # owner of the block we now hold
        k_pos = src_block * t_local + jnp.arange(t_local)
        m, l, o = _block_update(q, k_blk, v_blk, m, l, o,
                                q_pos, k_pos, causal, scale)
        # rotate K/V to the next device (neighbor ring over ICI)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(0, n, body, (m, l, o, k, v))
    l = jnp.maximum(l, 1e-30)
    return o / jnp.moveaxis(l, 1, 2)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        causal: bool = False):
    """shard_map-wrapped ring attention on [B, T, H, D] arrays sharded on T.

    Returns a jitted fn(q, k, v) → out with the same sharding.
    """
    spec = P(None, axis_name, None, None)

    @jax.jit
    def fn(q, k, v):
        return jax.shard_map(
            functools.partial(ring_attention, axis_name=axis_name,
                              causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return fn
