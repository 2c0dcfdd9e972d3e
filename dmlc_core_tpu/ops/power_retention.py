"""Gated, normalised power retention of degree 2 over packed documents,
chunk by chunk.

``H`` query heads share ``H_kv`` key heads of ``d`` columns (query head
``h`` reads key head ``h // (H / H_kv)``).  With ``Gamma_tj = prod_{l=j+1..t}
gamma_l`` of the key head's gates, and pairs of two documents masked out:

    o_t = sum_{j<=t} Gamma_tj (q_t . k_j)^2 v_j
          / (sum_{j<=t} Gamma_tj (q_t . k_j)^2 + eps)

The same output has a state form.  ``phi(u)`` is the symmetric square of
``u``: its ``D = d (d + 1) / 2`` entries ``u_a u_b`` for ``a <= b``, times
``sqrt 2`` off the diagonal, so that ``phi(q) . phi(k) = (q . k)^2``.  A key
head keeps ``S [D, d]`` and ``z [D]``, zero at a document's first token:

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T,   z_t = gamma_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

Inside a chunk of ``C`` tokens, with ``c_t`` the chunk's running sum of
``log gamma`` (so ``Gamma_tj = exp(c_t - c_j)``, an exponent never above 0
for ``j <= t``): the chunk's own pairs ``(q_t . k_j)^2 Gamma_tj`` as a
``[C, C]`` matrix, plus what the state handed over from the chunk before
gives, ``exp(c_t) phi(q_t)^T S_0`` — for the tokens of the document it was
handed over from only — and the hand-over
``S_C = exp(c_C) S_0 + sum_j exp(c_C - c_j) phi(k_j) v_j^T``, over the keys
of the chunk's last document (``S_0`` dropped where that document began in
the chunk).  ``z`` is kept as ``Z = sum_j w_j k_j k_j^T [d, d]``, which gives
``phi(q)^T z = q^T Z q`` without ``phi``.

**On a TPU the whole chunk is one Pallas kernel** (``power_retention``)
where the shapes allow it.  The grid is (key-head blocks, chunks); the chunk
axis is the sequential one.  A step reads the chunk's ``[C, G d]`` tile of
``q`` (the ``G = H / H_kv`` query heads of a key head side by side) and its
``[C, d]`` tiles of ``k`` and ``v`` straight from the caller's ``[T, H d]``
views, the chunk's running sums of ``log gamma`` and its document ids as a
column and as a row, and writes only ``o``'s ``[C, G d]`` tile.  ``phi`` is
never formed whole, not even in VMEM: it is taken ``d`` lanes at a time.
The ``d (d - 1) / 2`` products ``u_a u_b`` with ``a < b`` are rows ``a`` of
the upper triangle, ``d - 1 - a`` of them; row ``a`` and row ``d - 2 - a``
fill one ``d``-lane block exactly (``q_a`` times ``q`` turned by ``a + 1``
lanes, then ``q_{d-2-a}`` times ``q`` itself); row ``d/2 - 1`` fills half a
block alone, and the diagonal ``q_a^2`` is one more: ``d/2 + 1`` blocks in
all (65 at ``d = 128``, 8 320 lanes for ``D = 8 256``).  The state is held
the same way, ``S [d/2 + 1, d, d]`` float32 in VMEM scratch beside ``Z``;
the off-diagonal blocks carry ``k_a k_b`` once and the query side doubles
their product.  The query heads of a key head are stacked ``[G C, d]``, so
each state block is one ``[G C, d] @ [d, d]`` product.

Precision: ``gamma``, the running sums, ``Gamma``, the squares, the
numerator, the denominator, the state and every accumulation are float32;
products take their operands in ``q``'s type (the state too, read as such
an operand; it is kept and accumulated in float32).

The chunk and the key-head block are sized from VMEM, not from a knob: a
key head costs its state, its double-buffered tiles and the step's
temporaries, and the largest chunk of 256, 128, ... that keeps one key head
under ``_VMEM_BUDGET`` is taken, then the largest divisor of ``H_kv`` that
keeps the block under it (at ``d = 128``, ``G = 5``: chunk 256, one key head
a step, 13.7 MiB; the kernel asks for ``_VMEM_LIMIT``).  256 is the largest
chunk tried because a chunk's own pairs cost ``4 G C d`` operations a token
against the hand-over's ``2 G D d``: at 256 they are a sixteenth of it.

The kernel is taken when the program is **lowered for a TPU**
(``lax.platform_dependent``) and ``d`` is a multiple of 128 lanes; everywhere
else the ``jnp`` chunked form below runs.  ``power_retention_counted`` says
which, and how many operations the chosen schedule executes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["power_retention_counted", "needed_flops", "phi"]

EPS = 1e-6
_VMEM_BUDGET = 24 << 20      # one kernel step's bytes, at most
_VMEM_LIMIT = 48 << 20       # what the kernel asks of the 128 MiB VMEM
_CHUNKS = (256, 128, 64, 32, 16, 8)
_JNP_CHUNK = 64              # the ``jnp`` form's chunk
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def phi(x: jax.Array) -> jax.Array:
    """The symmetric square of the last axis: ``[..., d] -> [..., d(d+1)/2]``,
    ``x_a x_b`` for ``a <= b`` in row order, ``sqrt 2`` off the diagonal."""
    d = x.shape[-1]
    a, b = np.triu_indices(d)
    w = np.where(a == b, 1.0, np.sqrt(2.0)).astype(np.float32)
    return x[..., a] * x[..., b] * jnp.asarray(w, x.dtype)


def _masks(seg_col, seg_row, carried, c_col, c_row, d):
    """The chunk's pair weights ``Gamma [C, C]`` (same document, ``j <= t``),
    the state's weight on each query ``[C, 1]``, each key's weight in the
    hand-over ``[C, 1]`` and the old state's ``[1, d]``; every exponent at
    most 0."""
    c = seg_col.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    pair = (seg_col == seg_row) & (row >= col)
    gam = jnp.where(pair, jnp.exp(jnp.minimum(c_col - c_row, 0.0)), 0.0)
    # the last token's id and running sum are the row's largest and least
    # (ids do not fall, sums do not rise): reductions, not an unaligned
    # lane's broadcast
    last_seg = jnp.max(seg_row, 1, keepdims=True)
    last = jnp.min(c_row, 1, keepdims=True)
    cont = seg_col == carried                       # S_0 reaches token t
    tail = seg_col == last_seg                      # key j reaches S_C
    q_decay = jnp.where(cont, jnp.exp(jnp.minimum(c_col, 0.0)), 0.0)
    k_weight = jnp.where(tail, jnp.exp(jnp.minimum(last - c_col, 0.0)), 0.0)
    # the old state's weight as a row of d lanes, reduced from a [C, d]
    # tile: a [1, 1] value would have to be broadcast in both directions
    ones = jnp.ones((1, d), jnp.float32)
    keep = jnp.where(jnp.max(seg_col * ones.astype(jnp.int32), 0,
                             keepdims=True) == carried,
                     jnp.exp(jnp.minimum(jnp.min(c_col * ones, 0,
                                                 keepdims=True), 0.0)), 0.0)
    return gam, q_decay, k_weight, keep


def _pair_block(x, a, lane):
    """Block ``a`` (traced) of the products ``x_a x_b``, ``a < b``, of
    ``x [M, d]`` float32: row ``a`` of the upper triangle in lanes
    ``l < d - 1 - a`` (``x_a x_{l + a + 1}``), row ``d - 2 - a`` in the rest
    (``x_{d-2-a} x_l``).  Both columns come from turned copies' edge lanes."""
    d = x.shape[-1]
    turned = pltpu.roll(x, d - 1 - a, 1)    # lane l: x_{l + a + 1}; d-1: x_a
    other = pltpu.roll(x, a + 2, 1)         # lane 0: x_{d-2-a}
    return jnp.where(lane < d - 1 - a, turned[:, d - 1:] * turned,
                     other[:, :1] * x)


def _half_block(x, lane):
    """The last block: row ``d/2 - 1`` of the upper triangle alone, in lanes
    ``l < d/2``."""
    d = x.shape[-1]
    a = d // 2 - 1
    turned = pltpu.roll(x, d - 1 - a, 1)
    return jnp.where(lane < d - 1 - a, x[:, a:a + 1] * turned, 0.0)


def _key_head_chunk(q_tile, k, v, masks, s_ref, z_ref):
    """One key head's chunk: ``q_tile [C, G d]``, ``k, v [C, d]``; reads and
    updates the state in ``s_ref [d/2 + 1, d, d]`` (the ``d/2 - 1`` pair
    blocks, the half block, the diagonal) and ``z_ref [d, d]``.  Returns
    ``o [G C, d]`` float32, the query heads stacked."""
    f32 = jnp.float32
    mm = q_tile.dtype
    c, d = k.shape
    g = q_tile.shape[1] // d
    pairs, half, diag = d // 2 - 1, d // 2 - 1, d // 2
    gam, q_decay, k_weight, keep = masks
    dot = functools.partial(lax.dot_general, preferred_element_type=f32)
    mat = (((1,), (0,)), ((), ()))
    qs = jnp.concatenate([q_tile[:, h * d:(h + 1) * d] for h in range(g)])
    qf, kf = qs.astype(f32), k.astype(f32)
    # the chunk's own pairs, every query head at once
    s = dot(qs, k, _NT)                                       # [G C, C]
    a = s * s * jnp.concatenate([gam] * g)
    num = dot(a.astype(mm), v, mat)
    den = jnp.sum(a, 1, keepdims=True)
    # what the handed-over state gives: off-diagonal blocks, then diagonal
    lane_q = lax.broadcasted_iota(jnp.int32, qf.shape, 1)

    def from_pairs(n, old):
        return old + dot(_pair_block(qf, n, lane_q).astype(mm),
                         s_ref[n].astype(mm), mat)

    old = lax.fori_loop(0, pairs, from_pairs, jnp.zeros(qf.shape, f32))
    old = old + dot(_half_block(qf, lane_q).astype(mm),
                    s_ref[half].astype(mm), mat)
    old = 2.0 * old + dot((qf * qf).astype(mm), s_ref[diag].astype(mm), mat)
    zq = jnp.sum(dot(qs, z_ref[...].astype(mm), mat) * qf, 1, keepdims=True)
    decay = jnp.concatenate([q_decay] * g)
    o = (num + decay * old) / (den + decay * zq + EPS)
    # the hand-over
    lane_k = lax.broadcasted_iota(jnp.int32, kf.shape, 1)
    vw = (v.astype(f32) * k_weight).astype(mm)

    def into_pairs(n, _):
        s_ref[n] = keep * s_ref[n] + dot(
            _pair_block(kf, n, lane_k).astype(mm), vw, _TN)

    lax.fori_loop(0, pairs, into_pairs, None)
    s_ref[half] = keep * s_ref[half] + dot(
        _half_block(kf, lane_k).astype(mm), vw, _TN)
    s_ref[diag] = keep * s_ref[diag] + dot((kf * kf).astype(mm), vw, _TN)
    z_ref[...] = keep * z_ref[...] + dot((kf * k_weight).astype(mm), k, _TN)
    return o


def _kernel_body(seg_col_ref, seg_row_ref, c_col_ref, c_row_ref, q_ref,
                 k_ref, v_ref, o_ref, s_ref, z_ref, carried_ref, *, heads,
                 d):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)
        carried_ref[...] = jnp.full_like(carried_ref, -1)

    seg_col, seg_row = seg_col_ref[...], seg_row_ref[...]
    carried = carried_ref[...]
    c = seg_col.shape[0]
    g = q_ref.shape[1] // (heads * d)
    for j in range(heads):
        masks = _masks(seg_col, seg_row, carried, c_col_ref[j], c_row_ref[j],
                       d)
        o = _key_head_chunk(q_ref[:, j * g * d:(j + 1) * g * d],
                            k_ref[:, j * d:(j + 1) * d],
                            v_ref[:, j * d:(j + 1) * d], masks, s_ref.at[j],
                            z_ref.at[j])
        for h in range(g):
            at = (j * g + h) * d
            o_ref[:, at:at + d] = o[h * c:(h + 1) * c].astype(o_ref.dtype)
    carried_ref[...] = jnp.max(seg_row, 1, keepdims=True)


def _step_bytes(chunk: int, g: int, d: int, itemsize: int) -> int:
    """One key head's VMEM in a kernel step: its state, its tiles in and
    out (double-buffered) and the step's float32 temporaries."""
    blocks = d // 2 + 1
    state = (blocks + 1) * d * d * 4
    tiles = 2 * chunk * (2 * g * d + 2 * d) * itemsize
    side = 2 * 2 * chunk * 128 * 4 + 2 * 2 * 8 * chunk * 4
    temps = 6 * g * chunk * d * 4 + 3 * g * chunk * chunk * 4
    return state + tiles + side + temps


def _sizes(kv_heads: int, g: int, d: int, itemsize: int):
    """(chunk, key heads a step) from ``_VMEM_BUDGET``, or None where one
    key head does not fit at any chunk."""
    for chunk in _CHUNKS:
        one = _step_bytes(chunk, g, d, itemsize)
        if one <= _VMEM_BUDGET:
            heads = max(n for n in range(1, kv_heads + 1)
                        if kv_heads % n == 0 and n * one <= _VMEM_BUDGET)
            return chunk, heads
    return None


def _kernel_fits(q: jax.Array, k: jax.Array) -> bool:
    """Lane-aligned heads, whole groups, and a chunk of whole 128-lane rows
    that fits."""
    d = k.shape[-1]
    if (q.dtype.itemsize not in (2, 4) or d % 128
            or q.shape[1] % k.shape[1]):
        return False
    sized = _sizes(k.shape[1], q.shape[1] // k.shape[1], d, q.dtype.itemsize)
    return sized is not None and sized[0] % 128 == 0


def _chunk_sums(log_g, segments, chunk):
    """``log_g [T, H_kv]`` and ``segments [T]`` padded to whole chunks, the
    running sums ``c`` of each chunk ``[T', H_kv]`` float32 and the padded
    ids ``[T']`` (padding a document of its own behind the last)."""
    t = log_g.shape[0]
    pad = -t % chunk
    log_g = jnp.pad(log_g.astype(jnp.float32), ((0, pad), (0, 0)))
    seg = jnp.concatenate([segments.astype(jnp.int32), jnp.full(
        (pad,), jnp.iinfo(jnp.int32).max, jnp.int32)])
    n = (t + pad) // chunk
    sums = jnp.cumsum(log_g.reshape(n, chunk, -1), axis=1)
    return sums.reshape(t + pad, -1), seg


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "interpret"))
def _retention_kernel(q, k, v, log_g, segments, chunk=None, heads=None,
                      interpret=False):
    """``power_retention``'s contract through the ``power_retention``
    kernel; ``chunk`` and ``heads`` (key heads a step) from VMEM where not
    given.  Jitted, so that a program's layers share one trace."""
    t, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    if chunk is None or heads is None:
        sized = _sizes(hkv, g, d, q.dtype.itemsize)
        chunk, heads = chunk or sized[0], heads or sized[1]
    sums, seg = _chunk_sums(log_g, segments, chunk)
    tp = seg.shape[0]
    n = tp // chunk

    def pad(x):             # [T, heads, d] -> [T', heads * d]
        return jnp.pad(x, ((0, tp - t), (0, 0), (0, 0))).reshape(tp, -1)

    by_head = sums.T.reshape(hkv, tp)
    tile = lambda w: pl.BlockSpec((chunk, heads * w),          # noqa: E731
                                  lambda j, i: (i, j))
    o = pl.pallas_call(
        functools.partial(_kernel_body, heads=heads, d=d),
        grid=(hkv // heads, n),
        in_specs=[pl.BlockSpec((None, chunk, 1), lambda j, i: (i, 0, 0)),
                  pl.BlockSpec((None, 1, chunk), lambda j, i: (i, 0, 0)),
                  pl.BlockSpec((heads, chunk, 1), lambda j, i: (j, i, 0)),
                  pl.BlockSpec((heads, 1, chunk), lambda j, i: (j, 0, i)),
                  tile(g * d), tile(d), tile(d)],
        out_specs=tile(g * d),
        out_shape=jax.ShapeDtypeStruct((tp, h * d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((heads, d // 2 + 1, d, d), jnp.float32),
            pltpu.VMEM((heads, d, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="power_retention",
    )(seg.reshape(n, chunk, 1), seg.reshape(n, 1, chunk),
      by_head[:, :, None], by_head[:, None, :], pad(q), pad(k), pad(v))
    return o[:t].reshape(t, h, d)


@functools.partial(jax.jit, static_argnames="chunk")
def _retention_jnp(q, k, v, log_g, segments, chunk):
    """The chunk algebra with ``phi`` formed whole: the chunks' own pairs as
    batched products over all chunks at once, the hand-over of ``S`` and
    ``z`` a ``lax.scan``."""
    t, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    f32 = jnp.float32
    mm = q.dtype
    sums, seg = _chunk_sums(log_g, segments, chunk)
    tp = seg.shape[0]
    n = tp // chunk

    def chunks(x):          # [T, heads, w] -> [N, heads, C, w]
        x = jnp.pad(x, ((0, tp - t), (0, 0), (0, 0)))
        return x.reshape(n, chunk, x.shape[1], -1).transpose(0, 2, 1, 3)

    qc = chunks(q).reshape(n, hkv, g, chunk, d)               # by key head
    kc, vc = chunks(k), chunks(v)
    cc = sums.reshape(n, chunk, hkv).transpose(0, 2, 1)[..., None]
    # cc: [N, H_kv, C, 1]
    sc = seg.reshape(n, chunk)
    carried = jnp.concatenate([jnp.full((1,), -1, sc.dtype), sc[:-1, -1]])
    cont = (sc == carried[:, None])[:, None, :, None]         # [N,1,C,1]
    tail = (sc == sc[:, -1:])[:, None, :, None]
    i = jnp.arange(chunk)
    pair = ((sc[:, :, None] == sc[:, None, :])
            & (i[:, None] >= i[None, :]))[:, None]            # [N,1,C,C]
    gam = jnp.where(pair, jnp.exp(jnp.minimum(
        cc - cc.transpose(0, 1, 3, 2), 0.0)), 0.0)            # [N,Hk,C,C]
    last = cc[:, :, -1:]                                      # [N,Hk,1,1]
    q_decay = jnp.where(cont, jnp.exp(jnp.minimum(cc, 0.0)), 0.0)
    k_weight = jnp.where(tail, jnp.exp(jnp.minimum(last - cc, 0.0)), 0.0)
    keep = jnp.where(cont[:, :, -1:], jnp.exp(jnp.minimum(last, 0.0)), 0.0)

    dot = lambda eq, a, b: jnp.einsum(                        # noqa: E731
        eq, a, b, preferred_element_type=f32)
    s = dot("nkgid,nkjd->nkgij", qc, kc)
    a = s * s * gam[:, :, None]
    num = dot("nkgij,nkje->nkgie", a.astype(mm), vc)
    den = jnp.sum(a, -1, keepdims=True)
    phi_q = phi(qc.astype(f32)).astype(mm)                    # [N,Hk,G,C,D]
    phi_k = phi(kc.astype(f32))
    kv_in = dot("nkjD,nkje->nkDe", (phi_k * k_weight).astype(mm), vc)
    z_in = jnp.sum(phi_k * k_weight, 2)                       # [N,Hk,D]

    def hand_over(state, xs):
        st, z = state
        kv_c, z_c, keep_c = xs
        return (keep_c[..., None] * st + kv_c, keep_c * z + z_c), (st, z)

    dd = phi_k.shape[-1]
    _, (st, z) = lax.scan(
        hand_over, (jnp.zeros((hkv, dd, d), f32), jnp.zeros((hkv, dd), f32)),
        (kv_in, z_in, keep[:, :, 0]))
    old = dot("nkgiD,nkDe->nkgie", phi_q, st.astype(mm))
    zq = dot("nkgiD,nkD->nkgi", phi_q, z.astype(mm))[..., None]
    decay = q_decay[:, :, None]
    o = (num + decay * old) / (den + decay * zq + EPS)        # [N,Hk,G,C,d]
    o = o.transpose(0, 3, 1, 2, 4).reshape(tp, h, d)
    return o[:t].astype(mm)


def schedule_flops(t: int, heads: int, kv_heads: int, d: int, chunk: int,
                   width: int, den: int) -> float:
    """Operations a chunked schedule executes over ``t`` tokens (padded to
    whole chunks), a multiply-add two, masked pairs and padding included:
    the chunk's own pairs (``q k^T`` and the product with ``v`` a query
    head), the state's ``width`` features against each query head and into
    the hand-over, and the normaliser's ``den`` operations a query head and
    a key a token."""
    tp = -(-t // chunk) * chunk
    g = heads // kv_heads
    per_chunk = (4 * g * chunk * chunk * d + 2 * g * chunk * width * d
                 + 2 * width * chunk * d + (g + 1) * chunk * den)
    return float(kv_heads * (tp // chunk) * per_chunk)


def needed_flops(lengths: jax.Array, heads: int, kv_heads: int,
                 d: int) -> jax.Array:
    """Operations the outputs need, float32: per document of ``L`` tokens
    and key head, the cheaper exact form — the pairs, ``G L (L + 1) / 2``
    of ``4 d + 2`` each, or the state, ``L (G + 1)`` products of
    ``2 D d + 2 D`` (``phi``'s own products left out)."""
    g = heads // kv_heads
    dd = d * (d + 1) // 2
    n = lengths.astype(jnp.float32)
    pairs = g * n * (n + 1) / 2 * (4 * d + 2)
    state = n * (g + 1) * (2 * dd * d + 2 * dd)
    return kv_heads * jnp.sum(jnp.minimum(pairs, state))


def power_retention_counted(q: jax.Array, k: jax.Array, v: jax.Array,
                            log_g: jax.Array, segments: jax.Array
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``q [T, H, d]``, ``k, v [T, H_kv, d]``, ``log_g [T, H_kv]`` float32
    (at most 0), ``segments [T]`` non-negative document ids,
    non-decreasing.  Returns ``(o [T, H, d]`` in ``q``'s type, ``fused``,
    ``flops_done)``: ``fused`` an int32 1 where the program this was lowered
    into holds the kernel and 0 where the ``jnp`` form runs,
    ``flops_done`` float32, what that schedule executes."""
    t, h, d = q.shape
    hkv = k.shape[1]
    args = (q, k, v, log_g, segments)
    plain = lambda *a: (_retention_jnp(*a, chunk=_JNP_CHUNK),  # noqa: E731
                        jnp.int32(0), jnp.float32(schedule_flops(
                            t, h, hkv, d, _JNP_CHUNK, d * (d + 1) // 2,
                            2 * d * (d + 1) // 2)))
    if not _kernel_fits(q, k):
        return plain(*args)
    sized, _ = _sizes(hkv, h // hkv, d, q.dtype.itemsize)
    done = schedule_flops(t, h, hkv, d, sized, (d // 2 + 1) * d,
                          2 * d * d + 2 * d)
    return lax.platform_dependent(
        *args, default=plain,
        tpu=lambda *a: (_retention_kernel(*a), jnp.int32(1),
                        jnp.float32(done)))
