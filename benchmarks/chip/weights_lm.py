"""The benchmark's own weights for the document scorer: made on the device
from ``--seed``, one layer's leaves a jitted call, in the type they are
served in (bfloat16).  The program's ``model.shapes()`` gives only the
structure; every leaf is filled by a rule on its name (the configuration's
``weights`` group holds the numbers, its ``assumed`` list the reasons):

* a matrix ``[..., a, b]``       -> ``N(0, 1) / sqrt(a)``; the sublayers'
  output projections (``wo``, ``w_down``, ``e_down``, ``s_down``) times
  ``residual_out``
* ``embed``                      -> ``embed * N(0, 1)``
* conv taps ``[4, n]``           -> ``conv * N(0, 1)``
* norm weights                   -> ``1 + 0.1 N(0, 1)``
* ``router_bias``                -> ``router_bias * N(0, 1)``
* ``decay_rate`` (log of the per-head rate) -> log of uniform in
  ``decay_rate``; ``decay_bias`` -> ``softplus^-1`` of a step log-uniform in
  ``decay_step``; ``decay_up`` scaled so that the gate's output has std
  ``decay_gate``
"""

from __future__ import annotations

import math

OUT = ("wo", "w_down", "e_down", "s_down")


def _leaf(key, name: str, shape, rules: dict):
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(key, shape, jnp.float32)
    if "norm" in name:
        return 1.0 + 0.1 * x
    if name == "embed":
        return rules["embed"] * x
    if name.startswith("conv"):
        return rules["conv"] * x
    if name == "router_bias":
        return rules["router_bias"] * x
    if name == "decay_rate":
        lo, hi = rules["decay_rate"]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    if name == "decay_bias":
        lo, hi = (math.log(v) for v in rules["decay_step"])
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return jnp.log(jnp.expm1(step))                # softplus^-1
    x = x * (1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[0]))
    if name == "decay_up":
        return rules["decay_gate"] * x
    if name in OUT:
        return rules["residual_out"] * x
    return x


def make(shapes: dict, seed: int, rules: dict, dtype="bfloat16"):
    """A pytree like ``shapes`` (name -> shape tuple, one level of layer
    groups), filled from ``seed`` on the default device."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    root = _key(seed)

    fills: dict = {}            # layers of one shape share one program

    def group(index: int, names_shapes):
        names = sorted(names_shapes)
        same = tuple((n, tuple(names_shapes[n])) for n in names)
        if same not in fills:
            def fill(key):
                keys = jax.random.split(key, len(names))
                return {n: _leaf(k, n, shape, rules).astype(dtype)
                        for k, (n, shape) in zip(keys, same)}
            fills[same] = jax.jit(fill)
        return fills[same](jax.random.fold_in(root, index))

    top = {k: v for k, v in shapes.items() if not isinstance(v, dict)}
    out = group(0, top)
    for i, name in enumerate(sorted(k for k in shapes if k not in top)):
        out[name] = group(i + 1, shapes[name])
    return out


def _key(seed: int):
    import jax
    # the device's own bit generator ("rbg"): 4.3 G values in seconds where
    # the default counter-based one takes most of a minute; the same seed
    # gives the same weights on the same kind of device.  Seeds run past
    # 2**31: fold the two halves in separately
    return jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
