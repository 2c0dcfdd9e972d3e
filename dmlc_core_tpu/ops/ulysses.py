"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head/sequence
resharding attention.

The second long-context strategy alongside :mod:`.ring_attention`
(SURVEY §5 "long-context"): instead of rotating K/V blocks around a ring,
**re-shard with two all-to-alls** —

1. inputs arrive sharded on sequence ``[B, T/N, H, D]``;
2. an all-to-all over the sequence axis converts them to head-sharded
   ``[B, T, H/N, D]`` (each device now holds the FULL sequence for H/N
   heads);
3. plain exact attention runs locally per head group — no masking halo, no
   online-softmax bookkeeping;
4. a second all-to-all converts the output back to sequence-sharded.

Trade-offs vs ring attention (why a framework ships both):

* comm volume: 2 all-to-alls of activation size vs N-1 ppermute hops of
  K/V; on a TPU torus the all-to-all is a single fused XLA collective over
  ICI, usually cheaper for moderate N;
* constraint: requires ``num_heads % axis_size == 0`` (head sharding);
  ring attention has no head constraint and O(T/N) K/V memory, so it wins
  at extreme sequence lengths or few heads;
* Ulysses keeps the exact math of dense attention trivially (it IS dense
  attention locally), so any attention variant (bias, dropout, windows)
  drops in unchanged.

API mirrors ring attention: :func:`ulysses_attention` is the inside-
shard_map building block; :func:`make_ulysses_attention` wraps it for
``[B, T, H, D]`` arrays sharded on T over a named mesh axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import reference_attention

__all__ = ["ulysses_attention", "make_ulysses_attention"]


def _seq_to_heads(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """[B, T/N, H, D] local → [B, T, H/N, D] local via all-to-all.

    The local head axis is split into N groups; group j is sent to device j,
    and the N received sequence chunks concatenate into the full sequence.
    """
    b, t_loc, h, d = x.shape
    # [B, T/N, N, H/N, D]: axis 2 enumerates destination devices
    x = x.reshape(b, t_loc, n, h // n, d)
    # all_to_all: scatter axis 2 (dest), gather a new leading concat axis
    x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=0,
                           tiled=False)
    # x: [N, B, T/N, H/N, D] — N received chunks, in source-device order
    x = jnp.moveaxis(x, 0, 1)                 # [B, N, T/N, H/N, D]
    return x.reshape(b, n * t_loc, h // n, d)  # [B, T, H/N, D]


def _heads_to_seq(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """[B, T, H/N, D] local → [B, T/N, H, D] local (inverse all-to-all)."""
    b, t, h_loc, d = x.shape
    t_loc = t // n
    # [B, N, T/N, H/N, D]: axis 1 enumerates destination devices (seq chunk)
    x = x.reshape(b, n, t_loc, h_loc, d)
    x = jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=0,
                           tiled=False)
    # x: [N, B, T/N, H/N, D] — head groups from every device
    x = jnp.moveaxis(x, 0, 3)                 # [B, T/N, H/N, N, D]
    b2, tl, hl, n2, d2 = x.shape
    # interleave back: head group g from source device s is global head
    # s * (H/N) + g → order (N, H/N) then flatten
    x = jnp.moveaxis(x, 3, 2)                 # [B, T/N, N, H/N, D]
    return x.reshape(b2, tl, n2 * hl, d2)     # [B, T/N, H, D]


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = False) -> jax.Array:
    """All-to-all resharded exact attention (inside shard_map).

    q,k,v: LOCAL sequence shards [B, T/N, H, D] with H % N == 0.

    Differentiable: the backward is supplied via ``custom_vjp`` built from
    FORWARD-direction collectives only — the two reshardings are inverse
    permutations, so each one's adjoint IS the other (``all_to_all``'s
    autodiff transpose mislowers under this shard_map configuration, and
    the explicit adjoint pair is also the numerically obvious thing)."""
    n = jax.lax.axis_size(axis_name)

    @jax.custom_vjp
    def run(q, k, v):
        return _fwd(q, k, v)[0]

    def _fwd(q, k, v):
        qh = _seq_to_heads(q, axis_name, n)    # [B, T, H/N, D]
        kh = _seq_to_heads(k, axis_name, n)
        vh = _seq_to_heads(v, axis_name, n)
        out_h, att_vjp = jax.vjp(
            lambda a, b, c: reference_attention(a, b, c, causal=causal),
            qh, kh, vh)
        return _heads_to_seq(out_h, axis_name, n), att_vjp

    def _bwd(att_vjp, ct):
        ct_h = _seq_to_heads(ct, axis_name, n)   # adjoint of heads_to_seq
        dqh, dkh, dvh = att_vjp(ct_h)
        return tuple(_heads_to_seq(g, axis_name, n)  # adjoint of seq_to_heads
                     for g in (dqh, dkh, dvh))

    run.defvjp(_fwd, _bwd)
    return run(q, k, v)


def make_ulysses_attention(mesh: Mesh, axis_name: str = "sp",
                           causal: bool = False):
    """shard_map-wrapped Ulysses attention on [B, T, H, D] sharded on T.

    Returns a jitted fn(q, k, v) → out with the same sharding. Requires
    ``num_heads %% mesh.shape[axis_name] == 0``.
    """
    spec = P(None, axis_name, None, None)

    @jax.jit
    def fn(q, k, v):
        n = mesh.shape[axis_name]
        if q.shape[2] % n:
            raise ValueError(
                f"ulysses needs heads ({q.shape[2]}) divisible by mesh axis "
                f"{axis_name!r} size ({n}); use ring attention instead")
        return jax.shard_map(
            functools.partial(ulysses_attention, axis_name=axis_name,
                              causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return fn
