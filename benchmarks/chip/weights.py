"""The benchmark's own weights: made on the device, from ``--seed``, in one
jitted call, in the type they are served in (float32).

The program's ``model.init`` gives only the *structure* (``jax.eval_shape``:
nothing is computed).  Every leaf is filled by a rule on its shape, so the
plain reference and the program get the same numbers and neither takes
anything the other has made:

* a leaf whose leading dimension is the feature count (embedding table,
  linear weights)                  -> ``embed_scale * N(0, 1)``
* any other matrix ``[..., a, b]`` -> ``N(0, 1) / sqrt(a)``
* any other vector ``[a]``         -> ``N(0, 1) / sqrt(a)``
* a scalar                         -> ``embed_scale * N(0, 1)``

Unlike the program's init no leaf is zero, so the linear term and the
biases are exercised by every comparison.  XLA fuses the random bits into
the leaf they fill: compiled for a v5e, the 8 GiB table needs 0.06 GiB of
scratch beside it (AOT ``memory_analysis``, PR 26).
"""

from __future__ import annotations

import math

def _leaf(key, shape, features: int, embed_scale: float):
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 0 or shape[0] == features:
        return embed_scale * x
    fan = shape[-2] if len(shape) >= 2 else shape[0]
    return x * (1.0 / math.sqrt(fan))


def make(structure, seed: int, features: int, embed_scale: float = 0.01):
    """A pytree like ``structure`` (of ``ShapeDtypeStruct``), filled from
    ``seed`` on the default device."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(structure)

    @jax.jit
    def fill(key):
        keys = jax.random.split(key, len(leaves))
        return [_leaf(k, tuple(leaf.shape), features, embed_scale)
                for k, leaf in zip(keys, leaves)]

    return jax.tree_util.tree_unflatten(treedef, fill(_key(seed)))


def delta_norms(params, seed: int, features: int,
                embed_scale: float = 0.01) -> dict:
    """``{leaf path: ||params - make(seed)||}`` in one jitted call, the
    start weights drawn again inside it and fused into the difference, so no
    second copy of the model is ever held."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    @jax.jit
    def norms(key, now):
        keys = jax.random.split(key, len(now))
        return [jnp.sqrt(jnp.sum(jnp.square(
            p.astype(jnp.float32)
            - _leaf(k, p.shape, features, embed_scale))))
            for k, p in zip(keys, now)]

    got = norms(_key(seed), [x for _, x in flat])
    return {jax.tree_util.keystr(k): float(v)
            for (k, _), v in zip(flat, got)}


def _key(seed: int):
    import jax
    # seeds run past 2**31: fold the two halves in separately
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)
