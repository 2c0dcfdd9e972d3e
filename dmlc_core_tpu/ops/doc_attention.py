"""Causal softmax attention inside each document of one packed stream.

``T`` tokens, document after document; a query sees the keys of its own
document up to itself.  The ``[T, T]`` score matrix never exists: queries go
block by block, and a block of queries walks only the key blocks from the
one that holds its first token's document start up to its own, with the
running maximum and sum of a streaming softmax in float32.  The walk's
bounds are read from the batch, so the work follows the documents' lengths
(the sum of their squares) and not ``T`` squared.

A block is 512 tokens, halved while the float32 scores of one pair of
blocks, ``[H, block, block]``, pass 48 MiB (256 at 64 heads).  Measured on a
v5e (PERF.md, PR 37): a tile of 32 MiB (32 heads) stays on the chip in every
layer; one of 64 MiB (64 heads) the TPU compiler keeps in HBM for some
layers of a program and not for others, 64 ms a layer against 21.  The limit
lies between the two, nearer neither; nothing between them was measured.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["doc_causal_attention"]

_NEG = -1e30
_SCORE_TILE_BYTES = 48 << 20


def default_block(heads: int) -> int:
    """512, halved while ``[heads, block, block]`` float32 passes the
    tile."""
    block = 512
    while block > 128 and heads * block * block * 4 > _SCORE_TILE_BYTES:
        block //= 2
    return block


def doc_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         segments: jax.Array, doc_start: jax.Array,
                         block: Optional[int] = None) -> jax.Array:
    """``q, k [T, H, d_qk]`` (``q`` already scaled), ``v [T, H, d_v]``,
    ``segments [T]`` document ids (non-decreasing), ``doc_start [T]`` the
    stream position of each token's document start; ``block`` tokens a
    block (None: ``default_block``).  Returns ``[T, H, d_v]`` float32."""
    t, h, _ = q.shape
    block = block or default_block(h)
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = -t % block
    if pad:
        zp = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))  # noqa: E731
        q, k, v = zp(q), zp(k), zp(v)
        segments = jnp.concatenate(
            [segments, jnp.full((pad,), jnp.iinfo(jnp.int32).max,
                                segments.dtype)])
        doc_start = jnp.concatenate(
            [doc_start, jnp.full((pad,), t, doc_start.dtype)])
    nb = (t + pad) // block
    qb = q.reshape(nb, block, h, -1).transpose(0, 2, 1, 3)    # [nb,H,B,d]
    kb = k.reshape(nb, block, h, -1).transpose(0, 2, 1, 3)
    vb = v.reshape(nb, block, h, -1).transpose(0, 2, 1, 3)
    segb = segments.reshape(nb, block)
    first = doc_start.reshape(nb, block)[:, 0] // block       # [nb]
    pos = jnp.arange(block)

    def one_query_block(args):
        i, q_i, seg_q, lo = args

        def one_key_block(j, carry):
            m, l, acc = carry
            s = jnp.einsum("hqd,hkd->hqk", q_i, kb[j],
                           preferred_element_type=f32)
            ok = (seg_q[:, None] == segb[j][None, :]) & (
                (i * block + pos)[:, None] >= (j * block + pos)[None, :])
            s = jnp.where(ok[None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
            scale = jnp.exp(m - m_new)
            acc = acc * scale[..., None] + jnp.einsum(
                "hqk,hkd->hqd", p.astype(v.dtype), vb[j],
                preferred_element_type=f32)
            return m_new, l * scale + p.sum(-1), acc

        m, l, acc = jax.lax.fori_loop(
            lo, i + 1, one_key_block,
            (jnp.full((h, block), _NEG, f32), jnp.zeros((h, block), f32),
             jnp.zeros((h, block, dv), f32)))
        return acc / l[..., None]

    out = jax.lax.map(one_query_block,
                      (jnp.arange(nb), qb, segb, first))      # [nb,H,B,dv]
    return out.transpose(0, 2, 1, 3).reshape(nb * block, h, dv)[:t]
