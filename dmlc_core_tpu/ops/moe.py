"""A sigmoid-routed mixture of SwiGLU experts, as one holder of a slice of
the experts computes it.

The router scores all ``E`` experts; a token takes the ``k`` experts with the
largest ``score + bias`` — where the experts stand in groups, only among the
groups whose two best ``score + bias`` sum highest — and weighs them by
their scores, renormalised over the chosen and scaled.  The layer is told
which experts it holds (``held = (lo, hi)``): it sums the chosen experts
that lie in that range and leaves out what the others would add — the part
of the result this holder contributes.  On one chip there is no exchange
and nothing stands in for it.

Experts run as grouped matrix products (``lax.ragged_dot``) over the
assignments sorted by expert, so each expert multiplies only the tokens it
received; the held assignments sort first and are carried
``DISPATCH_BLOCK`` sorted rows at a time — gathered, multiplied, weighed
and added to their tokens' float32 sums — for as many blocks as the batch's
held assignments fill.  Nothing is dropped at any load up to all ``T * k``,
no buffer of activations is larger than a block or ``[T, H]``, and the only
parameter is the number of held rows the data brings.

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
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["route", "held_experts_sum", "swiglu", "DISPATCH_BLOCK"]

# by cost on a v5e, one layer alone at the three scorer cells' shapes
# (PERF.md, PR 40, §6): a turn of the loop costs ~20 us beside its rows (the
# grouped products' metadata, the gather's and the kernel's starts), the
# padding behind the last held row next to nothing since the kernel skips
# it; 16, 32 and 128 held experts read 14.87, 18.23, 51.68 ms a layer at
# 256, 14.96, 18.58, 44.32 at 512, 15.06, 18.41, 43.96 at 1 024
DISPATCH_BLOCK = 512


def swiglu(x: jax.Array, w_gu: jax.Array, w_down: jax.Array) -> jax.Array:
    """``W_d (silu(W_g x) * W_u x)`` with gate and up side by side in
    ``w_gu [H, 2I]``."""
    f32 = jnp.float32
    gu = jnp.dot(x, w_gu, preferred_element_type=f32)
    gate, up = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(hid, w_down, preferred_element_type=f32).astype(x.dtype)


def route(x: jax.Array, router: jax.Array, bias: jax.Array, k: int,
          scale: float, groups: int = 1, groups_kept: int = 1
          ) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts ``[T, k]`` int32, their weights ``[T, k]`` float32).
    With ``groups > 1`` the experts stand in that many equal groups in
    order, a group ranks by the sum of its two largest ``score + bias``,
    and the choice is made inside the ``groups_kept`` best.  Scores, group
    sums and the choice are float32."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(x, router, preferred_element_type=f32))
    u = s + bias.astype(f32)
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


def _swiglu_experts(xs, e_gu, e_down, sizes):
    """The sorted rows ``xs`` through their experts' SwiGLU.  Products
    accumulate in float32 inside; what leaves is an activation, in the
    activations' type."""
    f32 = jnp.float32
    gu = jax.lax.ragged_dot(xs, e_gu, sizes, preferred_element_type=xs.dtype)
    gate, up = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(gate.astype(f32)) * up.astype(f32)).astype(xs.dtype)
    return jax.lax.ragged_dot(hid, e_down, sizes,
                              preferred_element_type=xs.dtype)


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


def _blocked_sum(x, order, loads, w, e_gu, e_down, block):
    """(the sum ``[T, H]``, rows gathered, whether the combine was the
    kernel): ``block`` sorted rows at a time, for as many blocks as the
    held assignments fill."""
    t, k = w.shape
    h = x.shape[1]
    f32 = jnp.float32
    block = min(block, t * k)
    lanes = 128 if h % 128 == 0 else h
    with jax.named_scope("moe/dispatch"):
        # room for a whole block behind the last assignment
        order = jnp.pad(order.astype(jnp.int32), (0, block))
        ends = jnp.cumsum(loads)
        starts, held_rows = ends - loads, ends[-1]
        flat_w = w.reshape(-1)

    def one_block(b, carry):
        out, fused = carry
        with jax.named_scope("moe/dispatch"):
            first = b * block
            rows = jax.lax.dynamic_slice(order, (first,), (block,))
            token = rows // k
            # what of each expert's run of sorted rows lies in this block
            sizes = jnp.clip(ends, first, first + block) \
                - jnp.clip(starts, first, first + block)
            xs = x[token]                                     # [block, H]
        with jax.named_scope("moe/experts"):
            ys = _swiglu_experts(xs, e_gu, e_down, sizes)
        with jax.named_scope("moe/combine"):
            # the weighted rows, each laid out as its token's sum is
            y = ys.astype(f32) * flat_w[rows][:, None]
            out, took = _combine(out, y.reshape(block, -1, lanes), token,
                                 held_rows - first)
        return out, jnp.maximum(fused, took)

    blocks = (held_rows + block - 1) // block
    out, fused = jax.lax.fori_loop(
        0, blocks, one_block,
        (jnp.zeros((t, h // lanes, lanes), f32), jnp.int32(0)))
    return out.reshape(t, h).astype(x.dtype), blocks * block, fused


def held_experts_sum(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                     live: jax.Array, e_gu: jax.Array, e_down: jax.Array,
                     held: Tuple[int, int]
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_e w_e SwiGLU_e(x)`` over a token's chosen experts inside
    ``held``; ``live [T]`` is false for padding tokens, which reach no
    expert.  ``e_gu [G, H, 2I]``, ``e_down [G, I, H]`` hold experts
    ``lo .. hi-1``.  Also the layer's counters."""
    with jax.named_scope("moe/dispatch"):
        mine, order, loads, w = _sorted_assignments(chosen, weights, live,
                                                    held)
    out, rows, fused = _blocked_sum(x, order, loads, w, e_gu, e_down,
                                    DISPATCH_BLOCK)
    return out, {
        "assignments": loads.sum(),
        "load_max": loads.max(),
        "load_mean": loads.mean(),
        "unserved_tokens": jnp.sum(live & ~mine.any(-1)),
        "dispatch_rows": rows,
        "fused_combine": fused,
    }
