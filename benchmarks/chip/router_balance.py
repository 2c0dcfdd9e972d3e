"""The router's bias as ``noaux_tc`` trains it, at set-up: a weights rule.

A published ``deepseek_v3`` checkpoint's ``e_score_correction_bias`` is not
drawn, it is trained: after every step each expert's bias moves by a fixed
amount against its load, down where the expert served more than the mean and
up where it served less (DeepSeek-V3, arXiv 2412.19437, section 2.1.2:
auxiliary-loss-free balancing), so that on the text the model meets the
experts serve alike.  ``weights_lm.make`` draws a bias that has met no text:
with it a seed's weights send a heavy token id's whole stream to the same
few experts, and one holder's load follows the luck of a handful of ids for
a whole run.  ``balance`` gives the drawn bias that training: one batch of
the cell's own corpus a step, the experts' loads read from the program's
``probe`` (the choices it makes, every expert of every mixture layer, live
tokens only), ``b <- b - step * sign(load - mean)`` with the step falling
geometrically from ``step_first`` to ``step_last`` over ``batches`` steps.
The bias is kept in float32 between steps and handed to the program in the
parameters' type.  Nothing else of the weights moves, the program and the
reference read the same result, and the same seed gives the same bias.
"""

from __future__ import annotations


def loads_fn(model):
    """Jitted ``(params, batch) -> [mixture layers, experts]`` int32: how
    many live tokens chose each expert, layers in the order of their
    names."""
    import jax
    import jax.numpy as jnp

    def loads(params, batch):
        _, chosen = model.probe(params, batch, jnp.zeros((1,), jnp.int32))
        live = batch["segments"] < batch["row_ptr"].shape[0] - 1
        out = []
        for name in sorted(chosen):
            e = params[name]["router_bias"].shape[0]
            key = jnp.where(live[:, None], chosen[name], e)
            out.append(jnp.zeros(e + 1, jnp.int32).at[key.reshape(-1)]
                       .add(1)[:e])
        return jnp.stack(out)

    return jax.jit(loads)


def balance(model, params, next_batch, rule: dict):
    """(``params`` with every mixture layer's ``router_bias`` balanced,
    the largest ``load / mean`` of any expert at each step).
    ``next_batch()`` gives one device batch a call; ``rule`` holds
    ``batches``, ``step_first`` and ``step_last``."""
    import jax.numpy as jnp
    import numpy as np
    n = int(rule["batches"])
    first, last = float(rule["step_first"]), float(rule["step_last"])
    loads = loads_fn(model)
    names = sorted(k for k, v in params.items()
                   if isinstance(v, dict) and "router_bias" in v)
    bias = np.stack([np.asarray(params[k]["router_bias"].astype(jnp.float32))
                     for k in names])
    dtype = params[names[0]]["router_bias"].dtype
    params = dict(params)
    worst = []
    for i in range(n):
        got = np.asarray(loads(params, next_batch())).astype(np.float64)
        mean = got.mean(-1, keepdims=True)
        worst.append(float((got / np.maximum(mean, 1.0)).max()))
        step = first * (last / first) ** (i / max(n - 1, 1))
        bias = bias - step * np.sign(got - mean).astype(np.float32)
        for k, b in zip(names, bias):
            params[k] = dict(params[k], router_bias=jnp.asarray(b, dtype))
    return params, worst
