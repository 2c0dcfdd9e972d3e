"""Gated delta-rule linear attention with a per-channel decay, over packed
documents, chunk by chunk.

One head keeps a state ``S [d_k, d_v]``, zero at a document's start:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in ``(0, 1)^{d_k}`` and ``b_t`` in ``(0, 1)``.  With
``u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the step is
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, so inside a chunk of ``C`` tokens,
with ``G_i = sum_{j<=i} g_j`` (per channel):

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i, same document)
    U    = (I + Diag(b) A)^{-1} Diag(b) (V - (K exp(G)) S_0)
    o_i  = (q_i exp(G_i))^T S_0 + sum_{j<=i} B_ij u_j,   B_ij as A_ij with q_i
    S_C  = Diag(exp(G_C)) S_0 + sum_j (k_j exp(G_C - G_j)) u_j^T

Everything but the hand-over of ``S`` from chunk to chunk is a batched
matrix product over all chunks at once; the hand-over is a ``lax.scan`` of
two small products a chunk.  A document boundary inside a chunk is a mask:
``A`` and ``B`` keep pairs of one document, ``S_0`` reaches only the tokens
of the document it was handed over from, and ``S_C`` keeps only what the
chunk's last document put in.

No exponent is ever positive, so no decay, however strong, overflows:
the chunk is cut into sub-blocks of ``SUB`` tokens; a pair in two different
sub-blocks is formed as ``exp(G_i - G_a) exp(G_a - G_j)`` around the first
token ``a`` of ``i``'s sub-block (``j < a <= i``, both factors at most 1,
two matrix products); a pair inside one sub-block takes ``exp(G_i - G_j)``
itself, channel by channel (``SUB * SUB * d_k`` terms a sub-block, on the
vector unit).

The unit lower-triangular inverse is the finite product
``(I - N)(I + N^2)(I + N^4)...`` of a nilpotent ``N``: ``log2 C`` batched
products of float32 operands split into three bfloat16 parts
(``Precision.HIGH``, ~1e-6 relative).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kda_chunked"]

SUB = 16
_HI = jax.lax.Precision.HIGH


def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I + n)^{-1}`` for strictly lower-triangular ``n [..., C, C]``."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)
    x = eye - n
    p = n
    span = 2                 # x inverts up to powers below ``span``
    while span < c:
        p = jnp.matmul(p, p, precision=_HI)
        x = x + jnp.matmul(x, p, precision=_HI)
        span *= 2
    return x


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, segments: jax.Array,
                chunk: int = 64) -> jax.Array:
    """``q, k [T, H, d_k]`` (normalised and scaled by the caller),
    ``v [T, H, d_v]``, ``g [T, H, d_k]`` float32 log-decay (<= 0),
    ``beta [T, H]`` float32, ``segments [T]`` non-negative document ids,
    non-decreasing.  Returns ``o [T, H, d_v]`` float32.  Matrix products
    take their operands in ``q``'s type and accumulate in float32."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    mm = q.dtype
    f32 = jnp.float32
    pad = -t % chunk
    if pad:
        zp = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))  # noqa: E731
        q, k, v, g, beta = zp(q), zp(k), zp(v), zp(g), zp(beta)
        # padding is a document of its own behind the last
        segments = jnp.concatenate(
            [segments, jnp.full((pad,), jnp.iinfo(jnp.int32).max,
                                segments.dtype)])
    n = (t + pad) // chunk

    def chunks(x):          # [T, H, d] -> [N, H, C, d]
        return x.reshape(n, chunk, h, -1).transpose(0, 2, 1, 3)

    qc, kc, vc = chunks(q).astype(f32), chunks(k).astype(f32), chunks(v)
    gc = jnp.cumsum(chunks(g.astype(f32)), axis=2)            # G
    bc = beta.astype(f32).reshape(n, chunk, h).transpose(0, 2, 1)[..., None]
    seg = segments.reshape(n, chunk)
    carried = jnp.concatenate([jnp.full((1,), -1, seg.dtype), seg[:-1, -1]])
    cont = (seg == carried[:, None])[:, None, :, None]        # S_0 reaches i
    tail = (seg == seg[:, -1:])[:, None, :, None]             # j reaches S_C
    same = (seg[:, :, None] == seg[:, None, :])[:, None]      # [N, 1, C, C]
    i = jnp.arange(chunk)
    strict = same & (i[:, None] > i[None, :])
    incl = same & (i[:, None] >= i[None, :])

    dot = lambda a, b, eq: jnp.einsum(                        # noqa: E731
        eq, a, b, preferred_element_type=f32)
    # pairs inside one sub-block: exp(G_i - G_j) itself, j <= i
    sub = min(SUB, chunk)
    ns = chunk // sub
    blocks = lambda x: x.reshape(n, h, ns, sub, dk)           # noqa: E731
    gs, ks, qs = blocks(gc), blocks(kc), blocks(qc)
    j_le_i = (jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :])[..., None]
    within = jnp.exp(jnp.where(
        j_le_i, gs[:, :, :, :, None] - gs[:, :, :, None, :], -jnp.inf))
    kj = ks[:, :, :, None, :] * within                        # [.., i, j, dk]
    here = jnp.eye(ns, dtype=f32)[:, None, :, None]           # block a == b
    place = lambda d: (d[:, :, :, :, None] * here).reshape(   # noqa: E731
        n, h, chunk, chunk)
    # pairs in two sub-blocks: around the first token of i's sub-block
    base = gs[:, :, :, :1]                                    # [N,H,ns,1,dk]
    toward = jnp.exp(gs - base)                               # <= 1
    away = jnp.exp(jnp.minimum(base - gc[:, :, None], 0.0))   # [N,H,ns,C,dk]
    k_away = (kc[:, :, None] * away).astype(mm)
    across = lambda x: dot((x * toward).astype(mm), k_away,   # noqa: E731
                           "nhaid,nhajd->nhaij").reshape(n, h, chunk, chunk)
    earlier = (i[:, None] // sub > i[None, :] // sub)
    a = jnp.where(earlier, across(ks), place((ks[:, :, :, :, None] * kj
                                              ).sum(-1)))
    b = jnp.where(earlier, across(qs), place((qs[:, :, :, :, None] * kj
                                              ).sum(-1)))
    inv = _unit_lower_inverse(jnp.where(strict, a, 0.0) * bc)
    decay = jnp.exp(gc)                                       # <= 1
    w = jnp.matmul(inv, jnp.where(cont, kc * decay, 0.0) * bc,
                   precision=_HI).astype(mm)
    u0 = jnp.matmul(inv, vc.astype(f32) * bc, precision=_HI)
    q0 = jnp.where(cont, qc * decay, 0.0).astype(mm)
    last = gc[:, :, -1:]
    k_out = jnp.where(tail, kc * jnp.exp(last - gc), 0.0).astype(mm)
    keep = jnp.where(cont[:, :, -1], jnp.exp(last[:, :, 0]), 0.0)  # [N,H,dk]

    def hand_over(s, xs):
        w_c, u0_c, q0_c, k_out_c, keep_c = xs
        s_mm = s.astype(mm)
        u = u0_c - dot(w_c, s_mm, "hid,hde->hie")
        o_state = dot(q0_c, s_mm, "hid,hde->hie")
        s = keep_c[..., None] * s + dot(k_out_c, u.astype(mm),
                                        "hid,hie->hde")
        return s, (u, o_state)

    _, (u, o_state) = jax.lax.scan(
        hand_over, jnp.zeros((h, dk, dv), f32), (w, u0, q0, k_out, keep))
    o = o_state + dot(jnp.where(incl, b, 0.0).astype(mm), u.astype(mm),
                      "nhij,nhje->nhie")
    return o.transpose(0, 2, 1, 3).reshape(n * chunk, h, dv)[:t]
