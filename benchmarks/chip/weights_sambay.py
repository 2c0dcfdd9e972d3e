"""The benchmark's own weights for a SambaY-type document scorer:
``weights_lm.py``'s rules and its one-layer-a-call fill, with the leaves
that file does not know filled by these rules first (the configuration's
``weights`` group holds the numbers, its ``assumed`` list the reasons):

* ``A_log [d_inner, N]``        -> ``log(n + 1)`` in column ``n`` (Mamba's
  own initialisation); ``D`` -> 1
* ``dt_bias``                   -> ``softplus^-1`` of a step log-uniform in
  ``dt_step``
* ``lambda_*``                  -> ``lambda * N(0, 1)``
* a LayerNorm's bias (``*norm*_bias``) -> 0; ``subln`` -> ``1 + 0.1
  N(0, 1)``, a norm weight
* ``conv_bias``, ``b_qkv``, ``b_q``, ``bo`` -> ``bias * N(0, 1)``

``weights_lm.py`` is not edited: this module runs a private copy of it
whose leaf rule is this one."""

from __future__ import annotations

import importlib.util
import math
import os

import weights_lm

BIASES = ("conv_bias", "b_qkv", "b_q", "bo")


def _leaf(key, name: str, shape, rules: dict):
    import jax
    import jax.numpy as jnp
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in rules["dt_step"])
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return jnp.log(jnp.expm1(step))                # softplus^-1
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("lambda"):
        return rules["lambda"] * x
    if "norm" in name and name.endswith("_bias"):
        return jnp.zeros(shape, jnp.float32)
    if name == "subln":
        return 1.0 + 0.1 * x
    if name in BIASES:
        return rules["bias"] * x
    return weights_lm._leaf(key, name, shape, rules)


def _private_weights_lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "weights_lm.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_weights_lm_sambay", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._leaf = _leaf
    return mod


make = _private_weights_lm().make
