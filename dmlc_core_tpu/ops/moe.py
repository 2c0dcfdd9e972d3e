"""A sigmoid- or softmax-routed mixture of SwiGLU experts, as one holder of
a slice of the experts computes it.

The router scores all ``E`` experts (a sigmoid a logit, or the softmax over
all of them); a token takes the ``k`` experts with the
largest ``score + bias`` — where the experts stand in groups, only among the
groups whose two best ``score + bias`` sum highest — and weighs them by
their scores, renormalised over the chosen and scaled.  The layer is told
which experts it holds (``held = (lo, hi)``): it sums the chosen experts
that lie in that range and leaves out what the others would add — the part
of the result this holder contributes.  On one chip there is no exchange
and nothing stands in for it.

Experts run as grouped matrix products (``lax.ragged_dot``) over the
assignments sorted by expert, so each expert multiplies only the tokens it
received; the held assignments sort first and a holder of part of the
experts carries them ``DISPATCH_BLOCK`` sorted rows at a time — gathered,
multiplied, weighed and added to their tokens' float32 sums — for as many
blocks as the batch's held assignments fill.  Nothing is dropped at any
load up to all ``T * k``, no buffer of activations is larger than a block
or ``[T, H]``, and the only parameter is the number of held rows the data
brings.

On a TPU the adding is one Pallas kernel a block (``moe_combine``, PR 40).
The sums are kept as ``[T, H / 128, 128]``, a token's sum one piece of
memory (Mosaic refuses to copy one row out of a tiled ``[T, H]`` array
wider than 128 lanes), and the block's weighted rows are laid out the same
way; the kernel copies each held row's sum into VMEM, adds, and copies it
back, sixteen copies in flight, the sums updated in place.  Everywhere
else the same statement is a scatter.  On a v5e the kernel adds a row for
0.04-0.08 us where XLA's scatter took 0.26 us a row of 2304 and ~1 us of
7168 (PERF.md, PR 37 and 40); that scatter was why a holder of half the
experts used to sort all ``T * k`` rows into one buffer instead.

A layer that holds every expert serves all ``T * k`` rows anyway, and
each live token has exactly ``k`` of them, so it sums by token instead
(PR 46): the loop stores each block's unweighted outputs, in the
activations' type, into a buffer of every row in sorted order, and after
the loop one ``moe_combine`` kernel reads each token's rows once and
writes its sum once, ``[T, H]`` in the activations' type — no float32 sums
and no read-modify-write a row.  The products and their order are the
blocked scatter's, so the sums are too, to the last bit.  A holder of part
of the experts keeps the blocked scatter: a ``T * k``-row buffer would
cost it 1.2-1.9 GB for rows it never serves.

Such a layer also carries larger blocks (PR 47): the least power of two
that holds an expert's mean run of rows, ``T * k / E`` (4 096 rows in the
Mellum cell, against ``DISPATCH_BLOCK``'s 512), so a run falls in one or
two blocks; and on a TPU each of its products is the grouped-matmul Pallas
kernel that ships with JAX (``megablox`` ``gmm``) with the whole depth in
a tile, which gives ``ragged_dot``'s bits.  ``ragged_dot`` slows as its
blocks grow; on a v5e, one layer alone, the kernel reaches ~87 % of the
chip's peak at 4 096 rows, where ``ragged_dot`` took 42 % at 512 (PERF.md,
PR 47).  Only the block's
extent and the products' kernel differ: the rows, their order, the store
and the combine by token are the same.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm

__all__ = ["route", "held_experts_sum", "swiglu", "DISPATCH_BLOCK"]

# the block of a holder of part of the experts (a holder of every expert
# derives its own: ``_every_expert_block``); by cost on a v5e, one layer
# alone at the three such scorer cells' shapes (PERF.md, PR 40, §6): a turn
# of the loop costs ~20 us beside its rows (the grouped products' metadata,
# the gather's and the kernel's starts), the padding behind the last held
# row next to nothing since the kernel skips it; 16, 32 and 128 held
# experts read 14.87, 18.23, 51.68 ms a layer at 256, 14.96, 18.58, 44.32
# at 512, 15.06, 18.41, 43.96 at 1 024
DISPATCH_BLOCK = 512


def swiglu(x: jax.Array, w_gu: jax.Array, w_down: jax.Array) -> jax.Array:
    """``W_d (silu(W_g x) * W_u x)`` with gate and up side by side in
    ``w_gu [H, 2I]``."""
    f32 = jnp.float32
    gu = jnp.dot(x, w_gu, preferred_element_type=f32)
    gate, up = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(hid, w_down, preferred_element_type=f32).astype(x.dtype)


def route(x: jax.Array, router: jax.Array, bias, k: int,
          scale: float, groups: int = 1, groups_kept: int = 1,
          scoring: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """(chosen experts ``[T, k]`` int32, their weights ``[T, k]`` float32).
    A score is the sigmoid of the expert's logit, or with ``scoring``
    ``"softmax"`` its share of the softmax over all ``E`` logits; ``bias``
    (None for none) joins the scores in the choice only.  With
    ``groups > 1`` the experts stand in that many equal groups in order, a
    group ranks by the sum of its two largest ``score + bias``, and the
    choice is made inside the ``groups_kept`` best.  Scores, group sums and
    the choice are float32."""
    f32 = jnp.float32
    logits = jnp.dot(x, router, preferred_element_type=f32)
    s = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    u = s if bias is None else s + bias.astype(f32)
    if groups > 1:
        t, e = u.shape
        by_group = u.reshape(t, groups, e // groups)
        rank = jax.lax.top_k(by_group, 2)[0].sum(-1)          # [T, groups]
        _, kept = jax.lax.top_k(rank, groups_kept)
        is_kept = (kept[:, :, None] == jnp.arange(groups)).any(1)
        u = jnp.where(is_kept[:, :, None], by_group, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(u, k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), scale * picked / picked.sum(
        -1, keepdims=True)


def _swiglu_experts(xs, e_gu, e_down, sizes, product=jax.lax.ragged_dot):
    """The sorted rows ``xs`` through their experts' SwiGLU, each grouped
    product by ``product``.  Products accumulate in float32 inside; what
    leaves is an activation, in the activations' type."""
    f32 = jnp.float32
    gu = product(xs, e_gu, sizes, preferred_element_type=xs.dtype)
    gate, up = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(gate.astype(f32)) * up.astype(f32)).astype(xs.dtype)
    return product(hid, e_down, sizes, preferred_element_type=xs.dtype)


# a holder of every expert takes its products by the grouped-matmul kernel
# in blocks of about one expert's mean run of rows, so a run falls in one or
# two products.  By cost on a v5e, one layer alone at the Mellum cell's shape
# (a mean run of 4 096 rows; PERF.md, PR 47, §6): 74.5 ms at 512 rows a turn
# by ``ragged_dot``, which slows as its blocks grow (72.4 at 2 048, 81.5 at
# 8 192, 126.4 at 32 768); by the kernel 57.3 at 2 048, 54.6 at 4 096, 62.6
# at 8 192, 81.1 at 16 384 (XLA lays the larger blocks' rows out again).  So
# a block's temporaries stay within _BLOCK_BYTES; a tile of a product is
# _GMM_ROWS rows and passes no more than _GMM_VMEM of VMEM
_BLOCK_BYTES, _GMM_ROWS, _GMM_VMEM = 64 * 2 ** 20, 256, 14 * 2 ** 20


def _every_expert_block(rows: int, experts: int, h: int, i: int,
                        itemsize: int) -> int:
    """The sorted rows a turn of the loop carries where a layer holds every
    one of ``experts`` and so serves all ``rows`` (``T * k``): the least
    power of two from ``DISPATCH_BLOCK`` up that holds a mean run (``rows /
    experts``), no larger than the rows whose gathered input, gate and up,
    hidden activations and output (``2h + 3i`` values a row) fit
    ``_BLOCK_BYTES``, nor than ``rows``.  A power of two divides a ``rows``
    that is a multiple of it: then the blocks hold every row and no
    padding."""
    cap = _BLOCK_BYTES // ((2 * h + 3 * i) * itemsize)
    block = DISPATCH_BLOCK
    while block * experts < rows and 2 * block <= cap:
        block *= 2
    return min(block, rows)


def _gmm_tiling(k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """A grouped product's tile ``(rows, depth, columns)``: ``_GMM_ROWS``
    rows, the whole depth ``k`` — each output a single pass of float32
    sums, as ``ragged_dot`` takes it, so both give the same bits — and the
    ``n`` columns halved while the tiles, double-buffered, and the float32
    accumulator pass ``_GMM_VMEM``."""
    m, tn = _GMM_ROWS, n

    def vmem(tn):
        return 2 * (m * k + k * tn + m * tn) * itemsize + m * tn * 4

    while tn % 256 == 0 and vmem(tn) > _GMM_VMEM:
        tn //= 2
    return m, k, tn


def _gmm(lhs, rhs, sizes, preferred_element_type):
    """``ragged_dot`` as the grouped-matmul Pallas kernel (``megablox``)."""
    return _megablox_gmm(lhs, rhs, sizes,
                         preferred_element_type=preferred_element_type,
                         tiling=_gmm_tiling(*rhs.shape[1:],
                                            lhs.dtype.itemsize))


def _swiglu_grouped(xs, e_gu, e_down, sizes):
    """``_swiglu_experts`` with both products the grouped-matmul kernel on
    a TPU where a block is whole tiles, ``ragged_dot`` everywhere else
    (decided at lowering).  Rows behind the last expert's run are left as
    they were."""
    if xs.shape[0] % _GMM_ROWS:
        return _swiglu_experts(xs, e_gu, e_down, sizes)
    return lax.platform_dependent(
        xs, e_gu, e_down, sizes, default=_swiglu_experts,
        tpu=functools.partial(_swiglu_experts, product=_gmm))


def _sorted_assignments(chosen, weights, live, held):
    """The assignments that reached ``held``: the mask ``[T, k]``, the
    order that sorts all ``T * k`` by held expert (the others last), the
    held experts' loads and the weights with the others' zeroed."""
    lo, hi = held
    g = hi - lo
    mine = (chosen >= lo) & (chosen < hi) & live[:, None]
    key = jnp.where(mine, chosen - lo, g).reshape(-1)         # g sorts last
    order = jnp.argsort(key)
    loads = jnp.zeros(g + 1, jnp.int32).at[key].add(1)[:g]
    return mine, order, loads, jnp.where(mine, weights, 0.0)


# the combine kernel: the copies a run of rows keeps in flight ahead of its
# adds (the semaphores go round, so fewer than there are), and what a
# kernel step's tiles may take of VMEM (a step's rows, their sums, the
# rows again while the next step's arrive)
_AHEAD, _RING = 16, 32
_VMEM_BUDGET = 40 * 2 ** 20


def _combine_kernel_body(token_ref, held_ref, _, y_ref, out_ref, buf, arrived,
                         left, *, rows):
    """A kernel step adds its ``rows`` of ``y`` into their tokens' sums in
    HBM: a sum is copied to VMEM, added to, and copied back.  Two copies
    of one sum in flight would lose an addend, and a token stands twice in
    a block only in two experts' runs; a run's tokens rise.  So the rows go
    run of rising tokens by run, each to its end before the next starts:
    a token's addends arrive in its experts' order."""
    base = pl.program_id(0) * rows
    n = jnp.clip(held_ref[0] - base, 0, rows)

    def token(r):
        return token_ref[base + r]

    def fetch(r):
        return pltpu.make_async_copy(out_ref.at[token(r)], buf.at[r],
                                     arrived.at[r % _RING])

    def put(r):
        return pltpu.make_async_copy(buf.at[r], out_ref.at[token(r)], left)

    def one_run(start):
        end = lax.while_loop(
            lambda e: (e < n) & (token(jnp.minimum(e, rows - 1))
                                 > token(e - 1)),
            lambda e: e + 1, start + 1)

        def step(r, _):
            @pl.when(r < end)
            def _():
                fetch(r).start()

            @pl.when(r - _AHEAD >= start)
            def _():
                q = r - _AHEAD
                fetch(q).wait()
                buf[q] = buf[q] + y_ref[q]
                put(q).start()

        lax.fori_loop(start, end + _AHEAD, step, None)
        lax.fori_loop(start, end, lambda r, _: put(r).wait(), None)
        return end

    lax.while_loop(lambda start: start < n, one_run, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _combine_kernel(out, y, token, held, interpret=False):
    """``_combine``'s ``out`` through the ``moe_combine`` kernel, in place.
    Jitted so that a program's layers share one trace of the kernel's
    text."""
    block, c, lanes = y.shape
    rows = block
    while rows % 2 == 0 and 3 * rows * (c + -c % 8) * lanes * 4 > _VMEM_BUDGET:
        rows //= 2
    return pl.pallas_call(
        functools.partial(_combine_kernel_body, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(block // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((rows, c, lanes), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((rows, c, lanes), jnp.float32),
                            pltpu.SemaphoreType.DMA((_RING,)),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={2: 0},            # the sums, updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET + 8 * 2 ** 20),
        interpret=interpret, name="moe_combine",
    )(token, held.reshape(1), out, y)


def _combine(out, y, token, held):
    """(``out`` with ``y[r]`` added to ``out[token[r]]`` for ``r < held``,
    whether the kernel did it): ``out [T, C, lanes]`` float32 holds a
    token's sum as one piece of memory, so that the kernel copies it whole;
    ``y [block, C, lanes]`` float32.  On a TPU one Pallas kernel where
    ``lanes`` is a register's 128, everywhere else a scatter (decided at
    lowering)."""
    t = out.shape[0]

    def scatter(out, y, token, held):
        # the rows behind the last held assignment go nowhere
        real = jnp.arange(token.shape[0]) < held
        return out.at[jnp.where(real, token, t)].add(y, mode="drop"), \
            jnp.int32(0)

    if out.shape[-1] != 128:
        return scatter(out, y, token, held)
    return lax.platform_dependent(
        out, y, token, held, default=scatter,
        tpu=lambda *a: (_combine_kernel(*a), jnp.int32(1)))


def _row_words(ys, lanes):
    """Rows ``[n, H]`` as the by-token combine reads them, ``[n * W,
    lanes]`` uint32: a row's columns in chunks of ``lanes``, one chunk a
    word row of a 32-bit type, two of a 16-bit one (the even chunk in the
    low halves, a zero chunk behind an odd count), a row's ``W`` word rows
    one behind the other.  A row is then one run of 32-bit sublanes, which
    Mosaic copies whole from any row; a bfloat16 ``[n, H]`` pads nothing."""
    n, h = ys.shape
    c = h // lanes
    if ys.dtype.itemsize == 4:
        return lax.bitcast_convert_type(ys, jnp.uint32).reshape(n * c, lanes)
    half = lax.bitcast_convert_type(ys, jnp.uint16).astype(
        jnp.uint32).reshape(n, c, lanes)
    half = jnp.pad(half, ((0, 0), (0, c % 2), (0, 0)))
    return (half[:, 0::2] | half[:, 1::2] << 16).reshape(-1, lanes)


def _word_values(words, dtype):
    """The float32 values ``words`` hold (``_row_words``), chunk by chunk:
    one array of a 32-bit ``dtype``, two of a 16-bit one, low half first."""
    f32 = jnp.float32
    if jnp.dtype(dtype).itemsize == 4:
        return [lax.bitcast_convert_type(words, dtype).astype(f32)]
    if dtype == jnp.bfloat16:                  # a float32's upper half
        return [lax.bitcast_convert_type(words << 16, f32),
                lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), f32)]
    return [lax.bitcast_convert_type((words >> s).astype(jnp.uint16),
                                     dtype).astype(f32) for s in (0, 16)]


# the combine by token: tokens a kernel step sums (every copy of their
# rows in flight at once), tokens a group of its adds, copies a turn of
# the loop that starts them (by cost on a v5e, a layer's 262 144 rows at
# the Mellum cell's shape: 14.3 ms with a wait of its own a copy, 10.5 with
# one wait for all, 7.1 with eight starts a turn; PERF.md, PR 46)
_STEP_TOKENS, _GROUP, _STARTS = 256, 32, 8


def _token_sum_kernel_body(pos_ref, pos_v, w_ref, words_ref, out_ref, rows,
                           prods, copied, *, c):
    """A kernel step sums its tokens' rows.  It copies row ``(t, j)`` from
    the words in HBM to ``rows``' ``t * k + j``-th ``W`` word rows — an
    assignment no held expert took copies row 0, masked below — and awaits
    the bytes of all of them at once; then, a group of tokens at a time,
    weighs every row into ``prods`` (a strided load takes one word row of
    the group's ``j``-th rows) and adds a token's weighted rows in
    ascending ``j`` — its experts' order, as the blocked scatter adds them
    — and writes the sums once, in the activations' type.  Products and
    sums are two passes, so no backend contracts them into one rounding."""
    tokens, k = pos_v.shape
    n_words = rows.shape[0] // (tokens * k)
    group = prods.shape[2]
    per = prods.shape[1] // n_words

    def starts(b, _):
        for r in range(_STARTS):
            i = b * _STARTS + r
            at = jnp.maximum(pos_ref[i], 0) * n_words
            pltpu.make_async_copy(words_ref.at[pl.ds(at, n_words)],
                                  rows.at[pl.ds(i * n_words, n_words)],
                                  copied).start()

    lax.fori_loop(0, tokens * k // _STARTS, starts, None)
    pltpu.make_async_copy(rows, rows, copied).wait()     # every copy's bytes

    def one_group(g, _):
        first = pl.multiple_of(g * group, group)
        span = pl.ds(first, group)
        w, served = w_ref[span, :], pos_v[span, :] >= 0

        def weigh(word, _):
            for j in range(k):
                at = (first * k + j) * n_words + word
                values = _word_values(
                    rows[pl.ds(at, group, stride=k * n_words), :],
                    out_ref.dtype)
                for q, v in enumerate(values):
                    prods[j, word * per + q] = jnp.where(
                        served[:, j:j + 1], v * w[:, j:j + 1], 0.0)

        lax.fori_loop(0, n_words, weigh, None)
        for ch in range(c):
            acc = jnp.zeros(prods.shape[2:], jnp.float32)
            for j in range(k):
                acc = acc + prods[j, ch]
            out_ref[span, pl.ds(ch * acc.shape[1], acc.shape[1])] = \
                acc.astype(out_ref.dtype)

    lax.fori_loop(0, tokens // group, one_group, None)


@functools.partial(jax.jit, static_argnames=("dtype", "c", "interpret"))
def _token_sum_kernel(words, pos, w, dtype, c, interpret=False):
    """``_token_sum`` through the ``moe_combine`` kernel.  Jitted so that a
    program's layers share one trace of the kernel's text."""
    lanes = words.shape[1]
    t, k = pos.shape
    per = 4 // jnp.dtype(dtype).itemsize
    n_words = -(-c // per)
    tokens = min(_STEP_TOKENS, -(-t // _GROUP) * _GROUP)
    while tokens > _GROUP and k * n_words * tokens * lanes * 4 \
            > _VMEM_BUDGET // 2:
        tokens //= 2
    steps = -(-t // tokens)
    # the last step's tokens past ``t`` take no expert and write nowhere
    pos = jnp.pad(pos, ((0, steps * tokens - t), (0, 0)), constant_values=-1)
    w = jnp.pad(w, ((0, steps * tokens - t), (0, 0)))
    return pl.pallas_call(
        functools.partial(_token_sum_kernel_body, c=c),
        grid=(steps,),
        in_specs=[pl.BlockSpec((tokens * k,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tokens, k), lambda i: (i, 0)),
                  pl.BlockSpec((tokens, k), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, c * lanes), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tokens * k * n_words, lanes), jnp.uint32),
            pltpu.VMEM((k, n_words * per, _GROUP, lanes), jnp.float32),
            pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((t, c * lanes), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET + 8 * 2 ** 20),
        interpret=interpret, name="moe_combine",
    )(pos.reshape(-1), pos, w, words)


def _token_sum(words, pos, w, dtype, c):
    """(``sum_j w[t, j] * row(pos[t, j])`` ``[T, c * lanes]`` in ``dtype``,
    whether the kernel did it): ``words [N * W, lanes]`` the rows
    (``_row_words``), ``pos [T, k]`` a token's rows ascending, -1 for none.
    A token's weighted rows are added to a float32 zero in ascending
    ``j``.  On a TPU the ``moe_combine`` kernel where ``lanes`` is a
    register's 128 and the type bfloat16 or float32, everywhere else a
    gather (decided at lowering)."""
    t, k = pos.shape
    lanes = words.shape[-1]

    def gather(words, pos, w):
        rows = words.reshape(-1, -(-c // (4 // jnp.dtype(dtype).itemsize)),
                             lanes)[jnp.maximum(pos, 0)]
        v = jnp.stack(_word_values(rows, dtype), 3).reshape(
            t, k, -1, lanes)[:, :, :c]
        y = jnp.where((pos >= 0)[:, :, None, None],
                      v * w[:, :, None, None], 0.0)
        # the weighted rows whole before the adds, as the kernel's passes
        out = lax.fori_loop(0, k, lambda j, acc: acc + y[:, j],
                            jnp.zeros((t, c, lanes), jnp.float32))
        return out.reshape(t, c * lanes).astype(dtype), jnp.int32(0)

    if lanes != 128 or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return gather(words, pos, w)
    return lax.platform_dependent(
        words, pos, w, default=gather,
        tpu=lambda *a: (_token_sum_kernel(*a, dtype=dtype, c=c),
                        jnp.int32(1)))


def _blocked(x, order, loads, w, e_gu, e_down, block, init, take,
             experts=_swiglu_experts):
    """(what ``take`` leaves, rows gathered, turns): ``block`` sorted rows
    at a time, for as many blocks as the held assignments fill, each
    block's rows through ``experts`` and the outputs handed to
    ``take(carry, ys, rows, token, first, held_rows, flat_w)`` with the
    loop's carry, ``init()`` at the start."""
    t, k = w.shape
    block = min(block, t * k)
    with jax.named_scope("moe/dispatch"):
        # room for a whole block behind the last assignment
        order = jnp.pad(order.astype(jnp.int32), (0, block))
        ends = jnp.cumsum(loads)
        starts, held_rows = ends - loads, ends[-1]
        flat_w = w.reshape(-1)

    def one_block(b, carry):
        with jax.named_scope("moe/dispatch"):
            first = b * block
            rows = jax.lax.dynamic_slice(order, (first,), (block,))
            token = rows // k
            # what of each expert's run of sorted rows lies in this block
            sizes = jnp.clip(ends, first, first + block) \
                - jnp.clip(starts, first, first + block)
            xs = x[token]                                     # [block, H]
        with jax.named_scope("moe/experts"):
            ys = experts(xs, e_gu, e_down, sizes)
        with jax.named_scope("moe/combine"):
            return take(carry, ys, rows, token, first, held_rows, flat_w)

    blocks = (held_rows + block - 1) // block
    return jax.lax.fori_loop(0, blocks, one_block, init()), blocks * block, \
        blocks


def _blocked_sum(x, order, loads, w, e_gu, e_down, block):
    """(the sum ``[T, H]``, rows gathered, whether the combine was the
    kernel): each block's weighted rows added to their tokens' float32
    sums as the block leaves its experts."""
    t = w.shape[0]
    h = x.shape[1]
    f32 = jnp.float32
    lanes = 128 if h % 128 == 0 else h

    def add(carry, ys, rows, token, first, held_rows, flat_w):
        out, fused = carry
        # the weighted rows, each laid out as its token's sum is
        y = ys.astype(f32) * flat_w[rows][:, None]
        out, took = _combine(out, y.reshape(y.shape[0], -1, lanes), token,
                             held_rows - first)
        return out, jnp.maximum(fused, took)

    (out, fused), rows, _ = _blocked(
        x, order, loads, w, e_gu, e_down, block, lambda: (
            jnp.zeros((t, h // lanes, lanes), f32), jnp.int32(0)), add)
    return out.reshape(t, h).astype(x.dtype), rows, fused


def _blocked_rows(x, order, loads, w, e_gu, e_down, block):
    """(the expert outputs as words ``[N * W, lanes]`` in sorted order,
    rows gathered, turns): each block's unweighted outputs stored at the
    block's offset, ``N`` the rows of every block the assignments could
    fill."""
    t, k = w.shape
    h = x.shape[1]
    lanes = 128 if h % 128 == 0 else h
    block = min(block, t * k)
    words = -(-(h // lanes) // (4 // x.dtype.itemsize))

    def store(buf, ys, rows, token, first, held_rows, flat_w):
        return lax.dynamic_update_slice(buf, _row_words(ys, lanes),
                                        (first * words, 0))

    return _blocked(x, order, loads, w, e_gu, e_down, block, lambda: jnp.zeros(
        (-(-t * k // block) * block * words, lanes), jnp.uint32), store,
        _swiglu_grouped)


def _token_positions(order, mine, w):
    """(each token's rows' places in sorted order ``[T, k]``, ascending —
    its experts' order — and -1 for an assignment no held expert took;
    their weights beside them)."""
    t, k = mine.shape
    n = t * k
    at = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return lax.sort((jnp.where(mine, at.reshape(t, k), -1), w),
                    dimension=1, num_keys=1)


def held_experts_sum(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                     live: jax.Array, e_gu: jax.Array, e_down: jax.Array,
                     held: Tuple[int, int], experts: int
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_e w_e SwiGLU_e(x)`` over a token's chosen experts inside
    ``held``; ``live [T]`` is false for padding tokens, which reach no
    expert.  ``e_gu [G, H, 2I]``, ``e_down [G, I, H]`` hold experts
    ``lo .. hi-1`` of the router's ``experts``.  Also the layer's counters;
    a layer that holds every expert sums its rows by token and says so
    (``gather_combine``)."""
    with jax.named_scope("moe/dispatch"):
        mine, order, loads, w = _sorted_assignments(chosen, weights, live,
                                                    held)
    counters = {}
    if tuple(held) == (0, experts):
        # every assignment of a live token is served: the rows are kept in
        # sorted order, carried in large blocks, and each token's read back
        # once
        t, k = chosen.shape
        buf, rows, turns = _blocked_rows(
            x, order, loads, w, e_gu, e_down,
            _every_expert_block(t * k, experts, x.shape[1], e_down.shape[1],
                                x.dtype.itemsize))
        with jax.named_scope("moe/combine"):
            pos, pos_w = _token_positions(order, mine, w)
            out, fused = _token_sum(buf, pos, pos_w, x.dtype,
                                    x.shape[1] // buf.shape[-1])
        counters["gather_combine"] = jnp.int32(1)
        counters["dispatch_turns"] = turns
    else:
        out, rows, fused = _blocked_sum(x, order, loads, w, e_gu, e_down,
                                        DISPATCH_BLOCK)
    return out, {
        "assignments": loads.sum(),
        "load_max": loads.max(),
        "load_mean": loads.mean(),
        "unserved_tokens": jnp.sum(live & ~mine.any(-1)),
        "dispatch_rows": rows,
        "fused_combine": fused,
        **counters,
    }
