"""Causal softmax attention inside each document of one packed stream.

``T`` tokens, document after document; a query sees the keys of its own
document up to itself, from its **first visible key** on: the batch says,
a token, the stream position of the first key that token may see
(``first_key [T]``) — its document's start, or where a layer looks back
through a window ``max(document start, t - (window - 1))``.  Either is
non-decreasing along the stream, which is all the walk asks, so a window
is data and no text here knows the word.  The ``[T, T]`` score matrix never
exists: queries go block by block, and a block of queries walks only the
key blocks from the one that holds its first token's first visible key up
to its own, with the running maximum and sum of a streaming softmax in
float32.  The walk's bounds are read from the batch, so the work follows
the documents' lengths (the sum of their squares, or of length times
window) and not ``T`` squared.

**Grouped keys**: ``k, v`` come with ``H_kv`` heads, ``H_kv`` a divisor of
the ``H`` query heads, and query head ``h`` reads key head ``h // (H /
H_kv)``.  Nothing is repeated in HBM: the ``jnp`` formulation keeps the
group as an axis of the scores, the kernel's head block is whole groups
and copies its groups' keys and values once.

**On a TPU the walk is one Pallas kernel a call** (``doc_attention``) where
the shapes allow it.  The grid is (head blocks, query blocks of 256
tokens); a step keeps one query block of a block of heads and walks its key
blocks ``lo .. i`` in a loop of its own, ``lo`` read from the batch (scalar
prefetch), each key block brought from HBM by the kernel's own copies, two
in flight, the next step's first block started behind this step's last
products.  The score tile, the running maximum, the running sum and the
accumulator stay in VMEM from the first key block to the last; ``q``,
``k``, ``v`` tiles come in and one output tile goes out.  The rounding
points are the ``jnp`` formulation's: operands in the activations' type,
scores, maximum, sum and accumulator float32, ``p`` cast to ``v``'s type
before the second product, one division at the end, float32 out; the two
differ by the order of their sums.

The tile is held **keys on the sublanes, queries on the lanes**
(``s^T = k q^T``, ``acc^T += v^T p^T``): a query's maximum and sum are then
reductions over sublanes, whole registers combined on the vector unit,
where the other way round they are cross-lane reductions (measured on a
v5e, PR 38: 0.94 us a head and block pair against 0.59).  So ``q`` and
``v`` come with the tokens on the lanes (``[H, d, T]``) and the output
leaves so; ``k`` comes as it is (``[T, H * d_qk]``: a head block's keys are
a run of lanes of a token's row, a head a slice of it in VMEM).  No mask
array: a stream holds a document whole, so a key is one the query may see
exactly where ``first_key[query] <= key <= query``, two comparisons of
iotas with a row of the batch.

The head block is sized from VMEM, not from a knob: a query head costs its
double-buffered ``q`` and ``o`` tiles and its state, a key head its two key
blocks of ``k`` and ``v`` — counted once for the whole group that reads them
(1.2 MB a head at 192 / 192 ungrouped, bfloat16; 3.5 MB a group of six at
128 / 128) — and the largest divisor of ``H`` that is whole groups, keeps
the block under ``_VMEM_BUDGET`` and its keys whole tiles of 128 lanes is
taken: 8 of 64 heads, 16 of 32 at ``d_v`` = 128, 24 of 48 on 8 key heads.  What bounds the kernel on a v5e is the matrix unit's
weights: a 128 x 128 tile of weights costs 128 cycles to load and serves
256 rows of keys in the first product and ``d_v`` rows of ``v^T`` in the
second (PERF.md, PR 38).

The kernel is taken when the program is **lowered for a TPU**
(``lax.platform_dependent``: decided at lowering, so a compile for a
described chip from a CPU process gets it), the operands are bfloat16,
``d_qk`` and ``d_v`` are whole tiles of 16 sublanes, ``d_qk`` fills the
128-deep tiles the matrix unit rounds it up to by three quarters, ``T`` is a
block at least and a head block exists; everywhere else (the CPU; float32;
small heads; a caller that names ``block``) the ``jnp`` formulation below
runs.  ``doc_causal_attention_counted`` says which.

**Differential pairs** (``doc_diff_attention_counted``, PR 43): two
softmax maps a pair of query heads over the same value pair, ``(A1 -
lambda A2) V``.  On a TPU one Pallas kernel a call (``diff_attention``)
walks the same bounds with the same copies, keeps both maps' maximum, sum
and accumulator in VMEM and writes ``acc1 / l1 - lambda acc2 / l2`` as one
tile.  The heads are 64 wide, which the rule above leaves to ``jnp`` (half
of a 128-deep pass would be zeros): a group's two key heads are one run of
128 lanes of ``k``'s row, and each map's query comes zero-padded to the
pair's 128 rows, so one 128-deep product gives one map's scores — the
matrix unit's cost of a 64-deep product, which it pads to 128 anyway.

The ``jnp`` formulation is a ``lax.map`` over query blocks of a
``fori_loop`` over key blocks, all heads at once.  Its block is 512 tokens,
halved while the float32 scores of one pair of blocks, ``[H, block,
block]``, pass 48 MiB (256 at 64 heads).  That rule concerns this text
only, which a TPU program no longer reaches at the benchmark's shapes;
measured on a v5e (PERF.md, PR 37): a tile of 32 MiB (32 heads) stays on
the chip in every layer; one of 64 MiB (64 heads) the TPU compiler keeps in
HBM for some layers of a program and not for others, 64 ms a layer against
21.  The limit lies between the two, nearer neither; nothing between them
was measured.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["doc_causal_attention", "doc_causal_attention_counted",
           "doc_diff_attention_counted", "walk_blocks"]

_NEG = -1e30
_SCORE_TILE_BYTES = 48 << 20
_BLOCK = 256                 # tokens a query block and a key block (kernel)
_VMEM_BUDGET = 16 << 20      # a head block's tiles, copies and state
_VMEM_LIMIT = 48 << 20       # the kernel's whole: of 128 MiB on the chip


def default_block(heads: int) -> int:
    """512, halved while ``[heads, block, block]`` float32 passes the
    tile."""
    block = 512
    while block > 128 and heads * block * block * 4 > _SCORE_TILE_BYTES:
        block //= 2
    return block


def _padded(q, k, v, segments, first_key, block):
    """The arguments with ``T`` a multiple of ``block``: padding is a
    document of its own behind the last."""
    t = q.shape[0]
    pad = -t % block
    if pad:
        zp = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))  # noqa: E731
        q, k, v = zp(q), zp(k), zp(v)
        segments = jnp.concatenate(
            [segments, jnp.full((pad,), jnp.iinfo(jnp.int32).max,
                                segments.dtype)])
        first_key = jnp.concatenate(
            [first_key, jnp.full((pad,), t, first_key.dtype)])
    return q, k, v, segments, first_key


def _attention_jnp(q, k, v, segments, first_key, block):
    """A ``lax.map`` over query blocks of a ``fori_loop`` over key blocks,
    every step some XLA fusions over the ``[H_kv, G, block, block]``
    scores of the ``G`` query heads a key head serves."""
    t, h, _ = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    grp = h // hkv
    f32 = jnp.float32
    q, k, v, segments, first_key = _padded(q, k, v, segments, first_key,
                                           block)
    nb = q.shape[0] // block
    qb = q.reshape(nb, block, hkv, grp, -1).transpose(0, 2, 3, 1, 4)
    kb = k.reshape(nb, block, hkv, -1).transpose(0, 2, 1, 3)  # [nb,Hkv,B,d]
    vb = v.reshape(nb, block, hkv, -1).transpose(0, 2, 1, 3)
    segb = segments.reshape(nb, block)
    firstb = first_key.reshape(nb, block)
    lo_of = firstb[:, 0] // block                             # [nb]
    pos = jnp.arange(block)

    def one_query_block(args):
        i, q_i, seg_q, first_q, lo = args

        def one_key_block(j, carry):
            m, l, acc = carry
            s = jnp.einsum("hgqd,hkd->hgqk", q_i, kb[j],
                           preferred_element_type=f32)
            key = (j * block + pos)[None, :]
            ok = (seg_q[:, None] == segb[j][None, :]) & (
                (i * block + pos)[:, None] >= key) & (
                    key >= first_q[:, None])
            s = jnp.where(ok, s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            scale = jnp.exp(m - m_new)
            acc = acc * scale[..., None] + jnp.einsum(
                "hgqk,hkd->hgqd", p.astype(v.dtype), vb[j],
                preferred_element_type=f32)
            return m_new, l * scale + p.sum(-1), acc

        m, l, acc = jax.lax.fori_loop(
            lo, i + 1, one_key_block,
            (jnp.full((hkv, grp, block), _NEG, f32),
             jnp.zeros((hkv, grp, block), f32),
             jnp.zeros((hkv, grp, block, dv), f32)))
        return acc / l[..., None]

    out = jax.lax.map(one_query_block, (jnp.arange(nb), qb, segb, firstb,
                                        lo_of))        # [nb,Hkv,G,B,dv]
    return out.transpose(0, 3, 1, 2, 4).reshape(nb * block, h, dv)[:t]


# -- the same walk as one Pallas TPU kernel -----------------------------------

def _attention_kernel_body(lo_ref, start_ref, qt_ref, k_hbm, vt_hbm, o_ref,
                           k_buf, vt_buf, sem, m_ref, l_ref, acc_ref):
    """One block of queries of one block of heads, keys on the sublanes and
    queries on the lanes.  ``lo_ref [nb]`` (SMEM) the first key block of
    every query block; ``start_ref [1, block]`` each query's first visible
    key; ``qt_ref [heads, d_qk, block]``; ``k_hbm [T, H_kv * d_qk]`` and
    ``vt_hbm [H_kv, d_v, T]`` where they lie; ``o_ref [heads, d_v,
    block]``.  The block's ``heads`` query heads read its ``kv_heads`` key
    heads (``vt_buf``'s), ``heads / kv_heads`` to one.  Key blocks come in
    by the kernel's own copies, two in flight; the running maximum, sum
    and accumulator stay in VMEM scratch from the first key block to the
    last."""
    f32 = jnp.float32
    heads, dqk, block = qt_ref.shape
    kv_heads = vt_buf.shape[1]
    grp = heads // kv_heads
    g, i = pl.program_id(0), pl.program_id(1)
    lo = lo_ref[i]

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        ours = pl.ds(pl.multiple_of(g * kv_heads * dqk, 128), kv_heads * dqk)
        return (pltpu.make_async_copy(k_hbm.at[at, ours], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(
                    vt_hbm.at[pl.ds(g * kv_heads, kv_heads), :, at],
                    vt_buf.at[slot], sem.at[1, slot]))

    def fetch(j):
        for copy in copies(j, j % 2):
            copy.start()

    # a head block's first step fetches its own first key block; every
    # other step's was started by the step before it, behind its last
    # products, so no step but the first waits for HBM with nothing to do
    pl.when(i == 0)(lambda: fetch(lo))
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start = start_ref[...]                                    # [1, block]
    key = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    behind = key - lax.broadcasted_iota(jnp.int32, (block, block), 1)

    def one_key_block(j, _):
        pl.when(j < i)(lambda: fetch(j + 1))
        # a key the query may see (none before its first visible one)
        # and none ahead of the query: the second is all true but in the
        # query block's own key block
        ok = (key >= start - j * block) & (behind <= (i - j) * block)
        slot = j % 2
        for copy in copies(j, slot):
            copy.wait()
        # every head's chain in one text: the scheduler overlaps one
        # head's products with another's softmax
        for h in range(heads):
            kv = h // grp
            s = jnp.dot(k_buf[slot, :, kv * dqk:(kv + 1) * dqk], qt_ref[h],
                        preferred_element_type=f32)           # [keys, queries]
            # -inf where jnp writes _NEG and zeroes p afterwards: the
            # maximum starts at _NEG, so exp(s - m) is the same 0
            s = jnp.where(ok, s, -jnp.inf)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, 0, keepdims=True))
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m - m_new)
            m_ref[h] = m_new
            part = p[:8]                  # the sum as eight rows of partial
            for r in range(8, block, 8):  # sums: whole registers added
                part = part + p[r:r + 8]
            l_ref[h] = l_ref[h] * scale + part
            acc_ref[h] = acc_ref[h] * scale + jnp.dot(
                vt_buf[slot, kv], p.astype(vt_buf.dtype),
                preferred_element_type=f32)

    lax.fori_loop(lo, i + 1, one_key_block, None)
    pl.when(i + 1 < pl.num_programs(1))(lambda: fetch(lo_ref[i + 1]))
    o_ref[...] = acc_ref[...] / jnp.sum(l_ref[...], 1, keepdims=True)


def _head_block(h: int, hkv: int, dqk: int, dv: int, block: int,
                itemsize: int) -> Optional[int]:
    """Query heads a kernel step takes: the largest divisor of ``h`` that
    is whole groups of ``h / hkv``, whose tiles (``q`` in and ``o`` out
    double-buffered by the pipeline; two key blocks of ``k`` and ``v``, a
    group's counted once) and state fit ``_VMEM_BUDGET`` and whose keys are
    whole tiles of 128 lanes of ``k [T, H_kv * d_qk]``; None where no
    divisor is."""
    grp = h // hkv
    per_group = block * (
        grp * (2 * dqk * itemsize + 2 * dv * 4)               # q, o
        + 2 * (dqk + dv) * itemsize                           # k, v
        + grp * (dv + 8 + 8) * 4)                             # acc, m, l
    fit = [d * grp for d in range(1, hkv + 1)
           if hkv % d == 0 and d * dqk % 128 == 0
           and d * per_group <= _VMEM_BUDGET]
    return max(fit) if fit else None


def _kernel_fits(q: jax.Array, k: jax.Array, v: jax.Array) -> bool:
    """bfloat16; ``d_qk`` and ``d_v`` whole tiles of 16 sublanes, ``d_qk``
    filling the tiles of 128 the matrix unit rounds it up to by three
    quarters at least (192 of 256; 64 of 128 would be half zeros); a block
    of tokens; a head block."""
    t, h, dqk = q.shape
    dv = v.shape[-1]
    return (q.dtype == v.dtype == jnp.bfloat16
            and dqk % 16 == 0 and dv % 16 == 0
            and 4 * dqk >= 3 * (-(-dqk // 128) * 128) and t >= _BLOCK
            and _head_block(h, k.shape[1], dqk, dv, _BLOCK, 2) is not None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _attention_kernel(q, k, v, segments, first_key, interpret=False):
    """``_attention_jnp``'s contract through the ``doc_attention`` kernel,
    blocks of ``_BLOCK`` tokens.  Jitted so that a program's layers share
    one trace of the kernel's text."""
    t, h, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    block = _BLOCK
    q, k, v, _, first_key = _padded(q, k, v, segments, first_key, block)
    tp = q.shape[0]
    nb = tp // block
    heads = _head_block(h, hkv, dqk, dv, block, q.dtype.itemsize)
    kv_heads = heads * hkv // h
    start = first_key.astype(jnp.int32).reshape(nb, 1, block)
    o = pl.pallas_call(
        _attention_kernel_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h // heads, nb),
            in_specs=[
                pl.BlockSpec((None, 1, block), lambda g, i, lo: (i, 0, 0)),
                pl.BlockSpec((heads, dqk, block),
                             lambda g, i, lo: (g, 0, i)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((heads, dv, block),
                                   lambda g, i, lo: (g, 0, i)),
            scratch_shapes=[
                pltpu.VMEM((2, block, kv_heads * dqk), k.dtype),
                pltpu.VMEM((2, kv_heads, dv, block), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 1, block), jnp.float32),
                pltpu.VMEM((heads, 8, block), jnp.float32),
                pltpu.VMEM((heads, dv, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((h, dv, tp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="doc_attention",
    )(start[:, 0, 0] // block, start,
      # queries and values with the tokens on the lanes; keys as they are,
      # a head a run of d_qk lanes of its token's row
      q.transpose(1, 2, 0), k.reshape(tp, hkv * dqk), v.transpose(1, 2, 0))
    return o.transpose(2, 0, 1)[:t]


# -- differential heads: two maps over one value pair -------------------------

def _diff_kernel_body(lo_ref, lam_ref, start_ref, q1_ref, q2_ref, k_hbm,
                      vt_hbm, o_ref, k_buf, vt_buf, sem, m_ref, l_ref,
                      acc_ref):
    """``_attention_kernel_body``'s walk for differential pairs.  A pair
    ``h`` of the block reads group ``h // (pairs / groups)``, whose keys are
    ``w = 2 d`` lanes of ``k [T, G * w]`` (its two key heads side by side)
    and whose values are the ``[w, T]`` rows of ``vt`` (its two value heads
    stacked).  ``q1_ref``, ``q2_ref [pairs, w, block]`` hold the pair's two
    query heads, each beside ``d`` zero rows where the other key head lies,
    so one product over ``w`` gives a map's scores.  Each map keeps its own
    running maximum, sum and accumulator in VMEM; the two share every key
    and value tile, and ``o = acc1 / l1 - lam acc2 / l2`` leaves as one
    tile.  ``lam_ref [1, 1]`` (SMEM)."""
    f32 = jnp.float32
    pairs, width, block = q1_ref.shape
    groups = vt_buf.shape[1]
    grp = pairs // groups
    g, i = pl.program_id(0), pl.program_id(1)
    lo = lo_ref[i]

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        ours = pl.ds(pl.multiple_of(g * groups * width, 128), groups * width)
        return (pltpu.make_async_copy(k_hbm.at[at, ours], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(
                    vt_hbm.at[pl.ds(g * groups, groups), :, at],
                    vt_buf.at[slot], sem.at[1, slot]))

    def fetch(j):
        for copy in copies(j, j % 2):
            copy.start()

    pl.when(i == 0)(lambda: fetch(lo))
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start = start_ref[...]                                    # [1, block]
    key = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    behind = key - lax.broadcasted_iota(jnp.int32, (block, block), 1)

    def one_key_block(j, _):
        pl.when(j < i)(lambda: fetch(j + 1))
        ok = (key >= start - j * block) & (behind <= (i - j) * block)
        slot = j % 2
        for copy in copies(j, slot):
            copy.wait()
        for h in range(pairs):
            kv = h // grp
            keys = k_buf[slot, :, kv * width:(kv + 1) * width]
            for half, q_ref in enumerate((q1_ref, q2_ref)):
                s = jnp.dot(keys, q_ref[h], preferred_element_type=f32)
                s = jnp.where(ok, s, -jnp.inf)
                m = m_ref[half, h]
                m_new = jnp.maximum(m, jnp.max(s, 0, keepdims=True))
                p = jnp.exp(s - m_new)
                scale = jnp.exp(m - m_new)
                m_ref[half, h] = m_new
                part = p[:8]
                for r in range(8, block, 8):
                    part = part + p[r:r + 8]
                l_ref[half, h] = l_ref[half, h] * scale + part
                acc_ref[half, h] = acc_ref[half, h] * scale + jnp.dot(
                    vt_buf[slot, kv], p.astype(vt_buf.dtype),
                    preferred_element_type=f32)

    lax.fori_loop(lo, i + 1, one_key_block, None)
    pl.when(i + 1 < pl.num_programs(1))(lambda: fetch(lo_ref[i + 1]))
    lam = lam_ref[0, 0]
    o_ref[...] = (acc_ref[0] / jnp.sum(l_ref[0], 1, keepdims=True)
                  - lam * acc_ref[1] / jnp.sum(l_ref[1], 1, keepdims=True))


def _diff_pair_block(pairs: int, groups: int, d: int, block: int,
                     itemsize: int) -> Optional[int]:
    """Pairs a kernel step takes: the largest divisor of ``pairs`` that is
    whole groups and whose tiles (two query tiles in and ``o`` out,
    double-buffered; a group's two key blocks of ``k`` and ``v``) and the
    two maps' state fit ``_VMEM_BUDGET``; None where no divisor is."""
    grp, w = pairs // groups, 2 * d
    per_group = block * (
        grp * (2 * 2 * w * itemsize + 2 * w * 4)              # q1, q2, o
        + 2 * 2 * w * itemsize                                # k, v
        + grp * 2 * (w + 8 + 8) * 4)                          # acc, m, l
    fit = [n * grp for n in range(1, groups + 1)
           if groups % n == 0 and n * per_group <= _VMEM_BUDGET]
    return max(fit) if fit else None


def _diff_kernel_fits(q: jax.Array, k: jax.Array) -> bool:
    """bfloat16; a value pair ``2 d`` whole tiles of 128 lanes (the two
    ``d``-wide key heads of a group are one such run of ``k``'s row); a
    block of tokens; a pair block."""
    t, h, d = q.shape
    return (q.dtype == k.dtype == jnp.bfloat16 and (2 * d) % 128 == 0
            and t >= _BLOCK
            and _diff_pair_block(h // 2, k.shape[1] // 2, d, _BLOCK, 2)
            is not None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _diff_attention_kernel(q, k, v, lam, segments, first_key,
                           interpret=False):
    """``_diff_attention_jnp``'s contract through the ``diff_attention``
    kernel, blocks of ``_BLOCK`` tokens."""
    t, h, d = q.shape
    hkv = k.shape[1]
    pairs, groups, w = h // 2, hkv // 2, 2 * d
    block = _BLOCK
    q, k, v, _, first_key = _padded(q, k, v, segments, first_key, block)
    tp = q.shape[0]
    nb = tp // block
    per = _diff_pair_block(pairs, groups, d, block, q.dtype.itemsize)
    kv_groups = per * groups // pairs
    start = first_key.astype(jnp.int32).reshape(nb, 1, block)
    # a pair's query heads with the tokens on the lanes, each beside the
    # zero rows where the other map's key head lies
    qt = q.reshape(tp, pairs, 2, d).transpose(2, 1, 3, 0)     # [2,P,d,T]
    zero = jnp.zeros_like(qt[0])
    q1 = jnp.concatenate([qt[0], zero], 1)
    q2 = jnp.concatenate([zero, qt[1]], 1)
    q_spec = pl.BlockSpec((per, w, block), lambda g, i, lo: (g, 0, i))
    o = pl.pallas_call(
        _diff_kernel_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pairs // per, nb),
            in_specs=[
                pl.BlockSpec((1, 1), lambda g, i, lo: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, block), lambda g, i, lo: (i, 0, 0)),
                q_spec, q_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((per, w, block),
                                   lambda g, i, lo: (g, 0, i)),
            scratch_shapes=[
                pltpu.VMEM((2, block, kv_groups * w), k.dtype),
                pltpu.VMEM((2, kv_groups, w, block), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((2, per, 1, block), jnp.float32),
                pltpu.VMEM((2, per, 8, block), jnp.float32),
                pltpu.VMEM((2, per, w, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((pairs, w, tp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="diff_attention",
    )(start[:, 0, 0] // block, jnp.reshape(lam, (1, 1)).astype(jnp.float32),
      start, q1, q2, k.reshape(tp, hkv * d),
      v.reshape(tp, groups, w).transpose(1, 2, 0))
    return o.transpose(2, 0, 1)[:t]


def _diff_attention_jnp(q, k, v, lam, segments, first_key, block):
    """The two maps as two walks of ``_attention_jnp`` over the same value
    pairs, then ``o1 - lam o2``."""
    t, h, d = q.shape
    pairs = v.reshape(t, -1, 2 * d)
    o1 = _attention_jnp(q[:, 0::2], k[:, 0::2], pairs, segments, first_key,
                        block)
    o2 = _attention_jnp(q[:, 1::2], k[:, 1::2], pairs, segments, first_key,
                        block)
    return o1 - lam * o2


def doc_diff_attention_counted(q: jax.Array, k: jax.Array, v: jax.Array,
                               lam: jax.Array, segments: jax.Array,
                               first_key: jax.Array):
    """Differential attention (arXiv:2410.05258) inside each document.
    ``q [T, 2P, d]`` (already scaled), ``k, v [T, 2G, d]``, the heads
    interleaved: pair ``j``'s two query heads are ``2j`` and ``2j + 1``,
    group ``g``'s two key heads ``2g`` and ``2g + 1``, and its value pair
    ``V_g = [v_2g | v_2g+1]`` is ``2 d`` wide; pair ``j`` reads group ``j //
    (P / G)``.  ``lam`` a float32 scalar; ``segments`` and ``first_key`` as
    ``doc_causal_attention_counted`` has them.  Returns ``(o [T, P, 2 d]
    float32, fused)`` with ``o_j = (softmax(q_2j k_2g^T) - lam
    softmax(q_2j+1 k_2g+1^T)) V_g`` over the visible keys; ``fused`` 1
    where the program this was lowered into holds the ``diff_attention``
    kernel and 0 where the ``jnp`` formulation runs."""
    if q.shape[1] % 2 or k.shape[1] % 2 or q.shape[1] % k.shape[1] \
            or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads on keys {k.shape} and "
                         f"values {v.shape} are no whole pairs and groups")
    args = (q, k, v, jnp.asarray(lam, jnp.float32), segments, first_key)
    plain = lambda *a: (  # noqa: E731
        _diff_attention_jnp(*a, default_block(q.shape[1] // 2)), jnp.int32(0))
    if _diff_kernel_fits(q, k):
        return lax.platform_dependent(
            *args, default=plain,
            tpu=lambda *a: (_diff_attention_kernel(*a), jnp.int32(1)))
    return plain(*args)


def walk_blocks(first_key: jax.Array) -> jax.Array:
    """Key blocks the kernel's walk visits for these bounds, int32:
    ``sum_i (i - lo_i + 1)`` over the query blocks of ``_BLOCK`` tokens,
    ``lo_i`` the block of query block ``i``'s first token's first visible
    key.  A count of the batch, the same whichever formulation runs."""
    lo = first_key[::_BLOCK].astype(jnp.int32) // _BLOCK
    return jnp.sum(jnp.arange(lo.shape[0], dtype=jnp.int32) - lo + 1)


def doc_causal_attention_counted(q: jax.Array, k: jax.Array, v: jax.Array,
                                 segments: jax.Array, first_key: jax.Array,
                                 block: Optional[int] = None):
    """``q [T, H, d_qk]`` (already scaled), ``k [T, H_kv, d_qk]``,
    ``v [T, H_kv, d_v]`` with ``H_kv`` a divisor of ``H`` (query head ``h``
    reads key head ``h // (H / H_kv)``), ``segments [T]`` document ids
    (non-decreasing), ``first_key [T]`` the stream position of the first
    key each token may see: its document's start, or later (a window); it
    never decreases along the stream and never passes the token.
    ``block`` tokens a block of the ``jnp`` formulation (None:
    ``default_block``), which a caller that names it gets.  Returns
    ``(o [T, H, d_v] float32, fused)``: ``fused`` is an int32 scalar, 1
    where the program this was lowered into holds the kernel and 0 where
    the ``jnp`` formulation runs."""
    if q.shape[1] % k.shape[1] or k.shape[:2] != v.shape[:2]:
        raise ValueError(f"{q.shape[1]} query heads on keys {k.shape} and "
                         f"values {v.shape}")
    args = (q, k, v, segments, first_key)
    jnp_block = block or default_block(q.shape[1])
    plain = lambda *a: (_attention_jnp(*a, jnp_block), jnp.int32(0))  # noqa: E731
    if block is None and _kernel_fits(q, k, v):
        return lax.platform_dependent(
            *args, default=plain,
            tpu=lambda *a: (_attention_kernel(*a), jnp.int32(1)))
    return plain(*args)


def doc_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         segments: jax.Array, first_key: jax.Array,
                         block: Optional[int] = None) -> jax.Array:
    """``doc_causal_attention_counted``'s ``o``."""
    return doc_causal_attention_counted(q, k, v, segments, first_key,
                                        block)[0]
