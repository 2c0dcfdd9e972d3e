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
received; the held assignments sort first.  Nothing is dropped at any load
up to all ``T * k``, in either of two forms of carrying the rows:

* **blocked**: ``DISPATCH_BLOCK`` sorted rows at a time — gathered,
  multiplied, weighed and added to their tokens' sums by a scatter — for as
  many blocks as the batch's held assignments fill; no buffer of
  activations is larger than a block or ``[T, H]``;
* **whole**: one sorted buffer with room for every assignment (``T * k``
  rows), gathered back by the inverse order; the rows behind the held
  assignments are in no group.

On a v5e the loop's scatter costs 0.26 us a row of 2304 it adds and the
whole buffer's gathers 0.05 us a row of all ``T * k`` (PERF.md, PR 37), so
the layer takes the blocked form where it expects to hold under a fifth of
the assignments (``_takes_blocks``: ``held`` against the number of experts;
no caller chooses) and the whole buffer otherwise.  A combine that adds a
block's rows at a gather's price would leave the blocked form alone
(ROADMAP.md, S5).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route", "held_experts_sum", "swiglu", "DISPATCH_BLOCK"]

# by cost on a v5e (PERF.md, PR 37): a holder's last block is padding, half
# a block a layer on average, and a dispatched row costs 1.46 us; blocks of
# 256 read 0.7-1.2 ms a batch under 512 on each of 8 seeds, 512 under 1 024
# and 2 048 (rows dispatched over rows held 1.03, 1.06, 1.11, 1.24)
DISPATCH_BLOCK = 256


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


def _takes_blocks(held_count: int, experts: int) -> bool:
    """Whether a holder of ``held_count`` of ``experts`` carries its rows
    block by block: where it expects under a fifth of the assignments."""
    return 5 * held_count < experts


def _whole_sum(x, mine, order, loads, w, e_gu, e_down):
    """(the sum ``[T, H]``, rows gathered): one sorted buffer of all
    ``T * k`` assignments, gathered back by the inverse order."""
    t, k = mine.shape
    with jax.named_scope("moe/dispatch"):
        xs = x[order // k]                                    # [T*k, H]
    with jax.named_scope("moe/experts"):
        ys = _swiglu_experts(xs, e_gu, e_down, loads)
    with jax.named_scope("moe/combine"):
        # back to assignment order, then the weighted sum of a token's k
        back = jnp.zeros(t * k, jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        y = jnp.where(mine[..., None], ys[back].reshape(t, k, -1), 0.0)
        out = jnp.einsum("tk,tkh->th", w, y).astype(x.dtype)
    return out, jnp.int32(t * k)


def _blocked_sum(x, order, loads, w, e_gu, e_down, block):
    """(the sum ``[T, H]``, rows gathered): ``block`` sorted rows at a
    time, for as many blocks as the held assignments fill."""
    t, k = w.shape
    f32 = jnp.float32
    block = min(block, t * k)
    with jax.named_scope("moe/dispatch"):
        # room for a whole block behind the last assignment
        order = jnp.pad(order.astype(jnp.int32), (0, block))
        ends = jnp.cumsum(loads)
        starts, held_rows = ends - loads, ends[-1]
        flat_w = w.reshape(-1)

    def one_block(b, out):
        with jax.named_scope("moe/dispatch"):
            first = b * block
            rows = jax.lax.dynamic_slice(order, (first,), (block,))
            real = first + jnp.arange(block) < held_rows
            token = rows // k
            # what of each expert's run of sorted rows lies in this block
            sizes = jnp.clip(ends, first, first + block) \
                - jnp.clip(starts, first, first + block)
            xs = x[token]                                     # [block, H]
        with jax.named_scope("moe/experts"):
            ys = _swiglu_experts(xs, e_gu, e_down, sizes)
        with jax.named_scope("moe/combine"):
            # the weighted rows added to their tokens; the rows behind the
            # last held assignment go nowhere
            y = jnp.where(real[:, None], ys.astype(f32), 0.0) \
                * flat_w[rows][:, None]
            return out.at[jnp.where(real, token, t)].add(y, mode="drop")

    blocks = (held_rows + block - 1) // block
    out = jax.lax.fori_loop(0, blocks, one_block,
                            jnp.zeros((t, x.shape[1]), f32))
    return out.astype(x.dtype), blocks * block


def held_experts_sum(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                     live: jax.Array, e_gu: jax.Array, e_down: jax.Array,
                     held: Tuple[int, int], experts: int
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_e w_e SwiGLU_e(x)`` over a token's chosen experts inside
    ``held``, of ``experts`` in all; ``live [T]`` is false for padding
    tokens, which reach no expert.  ``e_gu [G, H, 2I]``,
    ``e_down [G, I, H]`` hold experts ``lo .. hi-1``.  Also the layer's
    counters."""
    with jax.named_scope("moe/dispatch"):
        mine, order, loads, w = _sorted_assignments(chosen, weights, live,
                                                    held)
    counters = {
        "assignments": loads.sum(),
        "load_max": loads.max(),
        "load_mean": loads.mean(),
        "unserved_tokens": jnp.sum(live & ~mine.any(-1)),
    }
    if _takes_blocks(held[1] - held[0], experts):
        out, rows = _blocked_sum(x, order, loads, w, e_gu, e_down,
                                 DISPATCH_BLOCK)
    else:
        out, rows = _whole_sum(x, mine, order, loads, w, e_gu, e_down)
    return out, dict(counters, dispatch_rows=rows)
