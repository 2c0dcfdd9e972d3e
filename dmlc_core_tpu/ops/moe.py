"""A sigmoid-routed mixture of SwiGLU experts, as one holder of a slice of
the experts computes it.

The router scores all ``E`` experts; a token takes the ``k`` experts with the
largest ``score + bias`` and weighs them by their scores, renormalised over
the chosen and scaled.  The layer is told which experts it holds
(``held = (lo, hi)``): it sums the chosen experts that lie in that range
and leaves out what the others would add — the part of the result this
holder contributes.  On one chip there is no exchange and nothing stands in
for it.

Experts run as grouped matrix products (``lax.ragged_dot``) over the
assignments sorted by expert, so each expert multiplies only the tokens it
received.  Nothing is dropped: the sorted buffer has room for every
assignment (``T * k`` rows), and the rows behind the held assignments are
in no group.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route", "held_experts_sum", "swiglu"]


def swiglu(x: jax.Array, w_gu: jax.Array, w_down: jax.Array) -> jax.Array:
    """``W_d (silu(W_g x) * W_u x)`` with gate and up side by side in
    ``w_gu [H, 2I]``."""
    f32 = jnp.float32
    gu = jnp.dot(x, w_gu, preferred_element_type=f32)
    gate, up = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(hid, w_down, preferred_element_type=f32).astype(x.dtype)


def route(x: jax.Array, router: jax.Array, bias: jax.Array, k: int,
          scale: float) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts ``[T, k]`` int32, their weights ``[T, k]`` float32).
    Scores and the choice are float32."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(x, router, preferred_element_type=f32))
    _, chosen = jax.lax.top_k(s + bias.astype(f32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), scale * picked / picked.sum(
        -1, keepdims=True)


def held_experts_sum(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                     live: jax.Array, e_gu: jax.Array, e_down: jax.Array,
                     held: Tuple[int, int]
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_e w_e SwiGLU_e(x)`` over a token's chosen experts inside
    ``held``; ``live [T]`` is false for padding tokens, which reach no
    expert.  ``e_gu [G, H, 2I]``, ``e_down [G, I, H]`` hold experts
    ``lo .. hi-1``.  Also the layer's counters."""
    t, k = chosen.shape
    lo, hi = held
    g = hi - lo
    f32 = jnp.float32
    with jax.named_scope("moe/dispatch"):
        mine = (chosen >= lo) & (chosen < hi) & live[:, None]
        key = jnp.where(mine, chosen - lo, g).reshape(-1)     # g sorts last
        order = jnp.argsort(key)
        loads = jnp.zeros(g + 1, jnp.int32).at[key].add(1)[:g]
        token = order // k
        xs = x[token]                                         # [T*k, H]
    with jax.named_scope("moe/experts"):
        # products accumulate in float32 inside; what leaves is an
        # activation, in the activations' type
        gu = jax.lax.ragged_dot(xs, e_gu, loads,
                                preferred_element_type=x.dtype)
        gate, up = jnp.split(gu, 2, axis=-1)
        hid = (jax.nn.silu(gate.astype(f32)) * up.astype(f32)).astype(x.dtype)
        ys = jax.lax.ragged_dot(hid, e_down, loads,
                                preferred_element_type=x.dtype)
    with jax.named_scope("moe/combine"):
        # back to assignment order, then the weighted sum of a token's k
        back = jnp.zeros(t * k, jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        w = jnp.where(mine, weights, 0.0)
        y = jnp.where(mine[..., None], ys[back].reshape(t, k, -1), 0.0)
        out = jnp.einsum("tk,tkh->th", w, y).astype(x.dtype)
    counters = {
        "assignments": loads.sum(),
        "load_max": loads.max(),
        "load_mean": loads.mean(),
        "unserved_tokens": jnp.sum(live & ~mine.any(-1)),
    }
    return out, counters
