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

**On a TPU the whole chunk is one Pallas kernel** (``kda_chunk``) where the
shapes allow it.  The grid is (head blocks, chunks); the chunk axis is the
sequential one.  A step reads the ``[chunk, head_block * d]`` tiles of
``q, k, v, g`` straight out of the caller's ``[T, H, d]`` arrays (viewed
``[T, H * d]``: a head is a run of ``d`` lanes, so its ``[chunk, d]`` matrix
is a lane-aligned slice and nothing is transposed in HBM), ``beta``'s
``[chunk, H]`` rows and the chunk's document ids as a column and as a row,
and writes ``o``'s ``[chunk, head_block * d_v]`` tile.  Per head it forms
``G`` (a 0/1 triangle times ``g`` in three bfloat16 parts: exact products,
float32 sums), the sub-block pairs, ``A``, ``B``, the inverse, ``w``,
``u0``, ``q0``, ``k_out``, ``keep`` in VMEM and registers, and hands ``S``
over in VMEM scratch (``[head_block, d_v, d_k]`` float32, kept transposed so
that ``keep`` scales lanes; zeroed at chunk 0 beside the last document id
seen): ``u = u0 - w S``, ``o = q0 S + B u`` and the update of ``S`` happen
in the same step and only ``o`` goes back to HBM.  Precision is the text
above: the triangular inverse and ``inv @ (bK, bV)`` as ``hi*hi + hi*lo +
lo*hi`` of bfloat16 halves with float32 sums (what ``Precision.HIGH`` is on
a TPU), pairs inside a sub-block channel by channel in float32 on the
vector unit, every other product with operands in ``q``'s type and float32
sums.

Two things in the kernel's text are there for the machine alone.  One
head's chunk is a chain (``G``, pairs, five squarings, hand-over) that
waits on the matrix unit most of the time (3 400 cycles for 700 of work,
from the compiler's own schedule), and the matrix unit takes its products
in program order: so a step of the inner loop takes ``_TOGETHER`` heads and
every stage is written for all of them before the next (1 270 cycles a
head at four).  And a ``Precision.HIGH`` product is one product, not three
and two sums: the 64-wide matrices of the inverse are held doubled
``[x | x]`` in 128 lanes, so the halves of the left side lie side by side
(``[hi | hi | lo | lo]``) against the right side's stacked ``[hi; lo; hi;
0]``, and the doubled right side hands the product back doubled.

The head block is sized from VMEM, not from a knob: a head costs its
double-buffered tiles in and out plus its state (288 KiB at ``d = 128``,
``chunk = 64``, bfloat16), and the largest divisor of ``H`` that keeps the
block under ``_VMEM_BUDGET`` (half of the 16 MiB a kernel may use by
default; the rest is the body's own temporaries) is taken: 16 of 32 heads.

The kernel is taken when the program is **lowered for a TPU**
(``lax.platform_dependent``: decided at lowering, so a compile for a
described chip from a CPU process gets it) and ``d_k``, ``d_v`` are
multiples of 128 lanes and ``chunk`` is a multiple of ``SUB`` and of the
sublane tile of ``q``'s type; everywhere else (the CPU; small heads) the
``jnp`` formulation below runs.  ``kda_chunked_counted`` says which.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_chunked", "kda_chunked_counted"]

SUB = 16
_HI = jax.lax.Precision.HIGH
_VMEM_BUDGET = 8 << 20       # of the 16 MiB a kernel may use by default
_TOGETHER = 4                # heads (2^n) whose chains a loop step interleaves
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


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


def _kda_jnp(q, k, v, g, beta, segments, chunk):
    """The chunk algebra as batched ``jnp`` products over all chunks at
    once and a ``lax.scan`` for the hand-over; ``T`` a multiple of
    ``chunk``."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    mm = q.dtype
    f32 = jnp.float32
    n = t // chunk

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
    return o.transpose(0, 2, 1, 3).reshape(t, h, dv)


# -- the same chunk as one Pallas TPU kernel ----------------------------------

def _halves(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _hi_weights(hi, lo):
    """The bfloat16 halves of ``b [K, N]`` as the right side of
    ``_hi_times``: stacked ``[hi; lo; hi; 0]`` over ``4 K`` rows."""
    return jnp.concatenate([hi, lo, hi, jnp.zeros_like(hi)])


def _hi_times(a_hi, a_lo, weights):
    """``a @ b`` as ``Precision.HIGH`` is on a TPU, ``hi*hi + hi*lo +
    lo*hi`` of bfloat16 halves, as one product over ``4 K``: the halves of
    ``a`` doubled (``[a | a]``, ``[M, 2 K]`` each) side by side against
    ``_hi_weights`` of ``b``'s."""
    return jnp.dot(jnp.concatenate([a_hi, a_lo], 1), weights,
                   preferred_element_type=jnp.float32)


def _tril(c, strict=False):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row > col if strict else row >= col


def _prefix_sums(gs):
    """``cumsum(g, 0)`` of every float32 ``g [C, d]`` on the matrix unit,
    exact to float32: a 0/1 triangle times ``g`` in three bfloat16 parts."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    tri = _tril(gs[0].shape[0]).astype(bf16)
    dot = functools.partial(jnp.dot, tri, preferred_element_type=f32)

    def parts(g):
        g1 = g.astype(bf16)
        rest = g - g1.astype(f32)
        g2 = rest.astype(bf16)
        return g1, g2, (rest - g2.astype(f32)).astype(bf16)

    return [dot(g1) + (dot(g2) + dot(g3)) for g1, g2, g3 in map(parts, gs)]


def _unit_lower_inverses_hi(ns):
    """``_unit_lower_inverse`` of every ``n [C, C]`` with ``_hi_times``
    products, each matrix held doubled ``[x | x]`` (``[C, 2 C]``: at
    ``C = 64`` a full 128 lanes, and the doubled right side hands the
    product back doubled).  ``x (I + p)`` and ``p p`` share their right
    side, so one product of ``[x; p]`` makes both.  Returns the inverses
    doubled."""
    c = ns[0].shape[0]
    eye = jnp.where(_tril(c) ^ _tril(c, strict=True), 1.0, 0.0)
    twice = lambda x: jnp.concatenate([x, x], 1)              # noqa: E731
    ps = [twice(n) for n in ns]
    xs = [twice(eye) - p for p in ps]
    ps = [_hi_times(*h, _hi_weights(*h)) for h in map(_halves, ps)]   # N^2
    span = 2
    while span < c:                 # ps is N^span, xs inverts below it
        p_halves, x_halves = [_halves(p) for p in ps], [_halves(x) for x in xs]
        weights = [_hi_weights(*h) for h in p_halves]
        if 2 * span >= c:
            xs = [x + _hi_times(*h, w)
                  for x, h, w in zip(xs, x_halves, weights)]
        else:
            both = [_hi_times(jnp.concatenate([xh, ph]),
                              jnp.concatenate([xl, pl]), w)
                    for (xh, xl), (ph, pl), w in zip(x_halves, p_halves,
                                                     weights)]
            xs = [x + b[:c] for x, b in zip(xs, both)]
            ps = [b[c:] for b in both]
        span *= 2
    return xs


@jax.jit
def _sub_block_pairs(g_s, k_s, q_s, across, lane):
    """``A``'s and ``B``'s rows of one sub-block, in float32 tiles of eight
    rows: ``G``, ``k``, ``q`` of the sub-block's tokens ``[SUB, d_k]``,
    the pairs with earlier sub-blocks ``across [2 SUB, C]`` (``k``'s rows,
    then ``q``'s) and each column's distance from the sub-block's first
    token ``lane [8, C]``.  A pair inside the sub-block takes
    ``exp(G_i - G_j)`` itself, channel by channel, column by column (the
    rows above a column's own are masked by the caller's triangle).
    Jitted: one trace serves every sub-block of every head."""
    c = lane.shape[1]
    column = lambda x, k: lax.broadcast_in_dim(               # noqa: E731
        jnp.sum(x * k, 1), (8, c), (0,))
    a_rows, b_rows = [], []
    for r in range(0, SUB, 8):
        g_r, k_r, q_r = g_s[r:r + 8], k_s[r:r + 8], q_s[r:r + 8]
        zero = jnp.zeros((8, c), jnp.float32)
        a_r = lax.select(lane < 0, across[r:r + 8], zero)
        b_r = lax.select(lane < 0, across[SUB + r:SUB + r + 8], zero)
        for j in range(r + 8):
            kj = k_s[j:j + 1] * jnp.exp(
                jnp.minimum(g_r - g_s[j:j + 1], 0.0))
            a_r = lax.select(lane == j, column(k_r, kj), a_r)
            b_r = lax.select(lane == j, column(q_r, kj), b_r)
        a_rows.append(a_r)
        b_rows.append(b_r)
    return a_rows, b_rows


def _chunk_tiles(heads, seg_col, seg_row, carried):
    """Some heads' chunks.  A head is ``(q, k [C, d_k], v [C, d_v]`` in the
    products' type, ``g [C, d_k]``, ``beta [C, 1]`` float32, the state
    transposed ``st [d_v, d_k]`` float32``)``; beside them the chunk's
    document ids as ``[C, 1]`` and ``[1, C]`` and the last id of the chunk
    before ``[1, 1]``.  Returns a ``(o [C, d_v], st)`` a head.

    Every stage is written for all heads before the next: one head's
    chunk is a chain that waits on the matrix unit most of the time, the
    matrix unit takes its work in program order, and so only chains that
    alternate in the text overlap.  Two-dimensional values, static slices
    and iotas only: what Mosaic lowers, and plain ``jnp`` besides."""
    f32 = jnp.float32
    mm = heads[0][0].dtype
    c, dk = heads[0][0].shape
    dot = functools.partial(lax.dot_general, preferred_element_type=f32)
    qf = [h[0].astype(f32) for h in heads]
    kf = [h[1].astype(f32) for h in heads]
    beta = [h[4] for h in heads]
    big_g = _prefix_sums([h[3] for h in heads])               # G
    same = seg_col == seg_row                                 # [C, C]
    cont = seg_col == carried                                 # S_0 reaches i
    tail = seg_col == seg_row[:, c - 1:]                      # j reaches S_C
    # A and B a sub-block of rows at a time: a pair in an earlier sub-block
    # around the first token of i's sub-block on the matrix unit, a pair
    # inside the sub-block on the vector unit
    lane = lax.broadcasted_iota(jnp.int32, (8, c), 1)
    a_rows, b_rows = [[] for _ in heads], [[] for _ in heads]
    for lo in range(0, c, SUB):
        sub = slice(lo, lo + SUB)
        across = [jnp.zeros((2 * SUB, c), f32)] * len(heads)
        if lo:
            toward = [jnp.exp(g[sub] - g[lo:lo + 1]) for g in big_g]   # <= 1
            away = [jnp.exp(jnp.minimum(g[lo:lo + 1] - g, 0.0))
                    for g in big_g]
            across = [dot(jnp.concatenate([k[sub] * t, q[sub] * t]
                                          ).astype(mm),
                          (k * a).astype(mm), _NT)
                      for q, k, t, a in zip(qf, kf, toward, away)]
        for h, (q, k, g) in enumerate(zip(qf, kf, big_g)):
            a_s, b_s = _sub_block_pairs(g[sub], k[sub], q[sub], across[h],
                                        lane - lo)
            a_rows[h] += a_s
            b_rows[h] += b_s
    strict = same & _tril(c, strict=True)
    inv = _unit_lower_inverses_hi(
        [jnp.where(strict, jnp.concatenate(rows), 0.0) * b
         for rows, b in zip(a_rows, beta)])
    decay = [jnp.exp(g) for g in big_g]                       # <= 1
    wu = [_hi_times(*_halves(i), _hi_weights(*_halves(jnp.concatenate(
        [jnp.where(cont, k * d, 0.0) * b, h[2].astype(f32) * b], 1))))
        for i, k, d, b, h in zip(inv, kf, decay, beta, heads)]
    q0 = [jnp.where(cont, q * d, 0.0).astype(mm) for q, d in zip(qf, decay)]
    # the hand-over: (w; q0) S in one product, then u, o and the new S
    ws = [dot(jnp.concatenate([x[:, :dk].astype(mm), q]), h[5].astype(mm),
              _NT) for x, q, h in zip(wu, q0, heads)]
    u = [(x[:, dk:] - y[:c]).astype(mm) for x, y in zip(wu, ws)]
    incl = same & _tril(c)
    o = [y[c:] + dot(jnp.where(incl, jnp.concatenate(rows), 0.0).astype(mm),
                     x, (((1,), (0,)), ((), ())))
         for y, rows, x in zip(ws, b_rows, u)]
    last = [g[c - 1:] for g in big_g]
    k_out = [jnp.where(tail, k * jnp.exp(e - g), 0.0).astype(mm)
             for k, e, g in zip(kf, last, big_g)]
    keep = [jnp.where(cont[c - 1:], jnp.exp(e), 0.0) for e in last]  # [1,dk]
    st = [kp * h[5] + dot(x, ko, _TN)
          for kp, h, x, ko in zip(keep, heads, u, k_out)]
    return list(zip(o, st))


def _kda_kernel_body(seg_col_ref, seg_row_ref, beta_ref, q_ref, k_ref, v_ref,
                     g_ref, o_ref, st_ref, carried_ref, *, heads, together,
                     dk, dv):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)
        carried_ref[...] = jnp.full_like(carried_ref, -1)

    seg_col, seg_row = seg_col_ref[...], seg_row_ref[...]
    carried = carried_ref[...]
    betas = beta_ref[...]                                     # [C, H]
    head_of = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    first = pl.program_id(0) * heads

    def some_heads(i, _):
        hs = [i * together + u for u in range(together)]
        at_k = [pl.ds(pl.multiple_of(h * dk, 128), dk) for h in hs]
        at_v = [pl.ds(pl.multiple_of(h * dv, 128), dv) for h in hs]
        outs = _chunk_tiles(
            [(q_ref[:, ak], k_ref[:, ak], v_ref[:, av], g_ref[:, ak],
              jnp.sum(jnp.where(head_of == first + h, betas, 0.0), 1,
                      keepdims=True), st_ref[h])
             for h, ak, av in zip(hs, at_k, at_v)], seg_col, seg_row, carried)
        for h, av, (o, st) in zip(hs, at_v, outs):
            o_ref[:, av] = o
            st_ref[h] = st

    lax.fori_loop(0, heads // together, some_heads, None)
    carried_ref[...] = seg_row[:, seg_row.shape[1] - 1:]


def _head_block(h: int, dk: int, dv: int, chunk: int, itemsize: int) -> int:
    """Heads a kernel step takes: the largest divisor of ``h`` whose tiles
    (in and out, double-buffered) and state fit ``_VMEM_BUDGET``."""
    tiles = chunk * ((2 * dk + dv) * itemsize + dk * 4 + dv * 4)
    per_head = 2 * tiles + dk * dv * 4
    return max(d for d in range(1, h + 1)
               if h % d == 0 and (d == 1 or d * per_head <= _VMEM_BUDGET))


def _kernel_fits(q: jax.Array, v: jax.Array, chunk: int) -> bool:
    if q.dtype.itemsize not in (2, 4):
        return False
    sublanes = 8 * (4 // q.dtype.itemsize)        # rows of one tile
    return (q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and chunk % SUB == 0 and chunk % sublanes == 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _kda_kernel(q, k, v, g, beta, segments, chunk, interpret=False):
    """``_kda_jnp``'s contract through the ``kda_chunk`` kernel.  Jitted so
    that a program's layers share one trace of the kernel's text (1.9 s of
    tracing and lowering in every process, whatever the compile cache
    holds)."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    heads = _head_block(h, dk, dv, chunk, q.dtype.itemsize)
    seg = segments.astype(jnp.int32).reshape(n, chunk)
    flat = lambda x: x.reshape(t, -1)                         # noqa: E731
    tile = lambda d: pl.BlockSpec((chunk, heads * d),         # noqa: E731
                                  lambda j, i: (i, j))
    o = pl.pallas_call(
        functools.partial(_kda_kernel_body, heads=heads,
                          together=math.gcd(heads, _TOGETHER), dk=dk, dv=dv),
        grid=(h // heads, n),
        in_specs=[pl.BlockSpec((None, chunk, 1), lambda j, i: (i, 0, 0)),
                  pl.BlockSpec((None, 1, chunk), lambda j, i: (i, 0, 0)),
                  pl.BlockSpec((chunk, h), lambda j, i: (i, 0)),
                  tile(dk), tile(dk), tile(dv), tile(dk)],
        out_specs=tile(dv),
        out_shape=jax.ShapeDtypeStruct((t, h * dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_chunk",
    )(seg[:, :, None], seg[:, None, :], beta.astype(jnp.float32), flat(q),
      flat(k), flat(v), flat(g.astype(jnp.float32)))
    return o.reshape(t, h, dv)


def kda_chunked_counted(q: jax.Array, k: jax.Array, v: jax.Array,
                        g: jax.Array, beta: jax.Array, segments: jax.Array,
                        chunk: int = 64):
    """``q, k [T, H, d_k]`` (normalised and scaled by the caller),
    ``v [T, H, d_v]``, ``g [T, H, d_k]`` float32 log-decay (<= 0),
    ``beta [T, H]`` float32, ``segments [T]`` non-negative document ids,
    non-decreasing.  Returns ``(o [T, H, d_v] float32, fused)``: ``fused``
    is an int32 scalar, 1 where the program this was lowered into holds
    the kernel and 0 where the ``jnp`` formulation runs.  Matrix products
    take their operands in ``q``'s type and accumulate in float32."""
    t = q.shape[0]
    pad = -t % chunk
    if pad:
        zp = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))  # noqa: E731
        q, k, v, g, beta = zp(q), zp(k), zp(v), zp(g), zp(beta)
        # padding is a document of its own behind the last
        segments = jnp.concatenate(
            [segments, jnp.full((pad,), jnp.iinfo(jnp.int32).max,
                                segments.dtype)])
    args = (q, k, v, g, beta, segments)
    plain = lambda *a: (_kda_jnp(*a, chunk), jnp.int32(0))    # noqa: E731
    if _kernel_fits(q, v, chunk):
        o, fused = lax.platform_dependent(
            *args, default=plain,
            tpu=lambda *a: (_kda_kernel(*a, chunk), jnp.int32(1)))
    else:
        o, fused = plain(*args)
    return o[:t], fused


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, segments: jax.Array,
                chunk: int = 64) -> jax.Array:
    """``kda_chunked_counted``'s ``o``."""
    return kda_chunked_counted(q, k, v, g, beta, segments, chunk)[0]
