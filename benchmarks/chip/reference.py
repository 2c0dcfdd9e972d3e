"""Plain references: the models' equations in straightforward ``jax.numpy``,
float32, matmuls at ``highest`` precision.  No kernels, no CSR, no segment
sums, no optax, nothing imported from the program.

Rows come row-padded, ``ids[R, K]`` / ``vals[R, K]`` with absent fields at
value 0 (``corpus.Corpus.rows_padded``), straight from the generator's own
truth.  Weights are the benchmark's (``weights.make``).

``dtype`` puts the reference in the program's place at a lower precision —
the *control* of every comparison: parameters, inputs and all arithmetic in
that type.

* FM (Rendle 2010):  ``w0 + sum_k w[i_k] x_k
  + 1/2 sum_d [(sum_k v[i_k,d] x_k)^2 - sum_k (v[i_k,d] x_k)^2]``
* DCNv2 as ``dmlc_core_tpu/models/dcn.py`` states it (sum-pooled ``x0``,
  full ``[D, D]`` cross; *not* MLPerf's concatenated block):
  ``x0 = sum_k x_k v[i_k]``, ``x_{l+1} = x0 * (x_l W_l + b_l) + x_l``,
  ``w0 + sum_k w[i_k] x_k + x_L . h + c``
* loss: mean over rows of ``-(y log s(z) + (1 - y) log s(-z))``
* Adam (Kingma & Ba 2015), b1 0.9, b2 0.999, eps 1e-8, bias-corrected.
"""

from __future__ import annotations

import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def _cast(tree, dtype):
    import jax
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def fm_logits(params, ids, vals):
    import jax.numpy as jnp
    x = vals.astype(params["v"].dtype)
    vx = params["v"][ids] * x[..., None]                      # [R, K, D]
    pair = 0.5 * jnp.sum(jnp.sum(vx, 1) ** 2 - jnp.sum(vx * vx, 1), -1)
    return params["w0"] + jnp.sum(params["w"][ids] * x, 1) + pair


def dcn_logits(params, ids, vals):
    import jax.numpy as jnp
    x = vals.astype(params["v"].dtype)
    x0 = jnp.sum(params["v"][ids] * x[..., None], 1)          # [R, D]
    xl = x0
    for layer in range(params["cross"]["w"].shape[0]):
        xl = x0 * (xl @ params["cross"]["w"][layer]
                   + params["cross"]["b"][layer]) + xl
    return (params["w0"] + jnp.sum(params["w"][ids] * x, 1)
            + xl @ params["head"]["w"] + params["head"]["b"])


LOGITS = {"fm": fm_logits, "dcn": dcn_logits}


def bce(logits, labels):
    import jax
    import jax.numpy as jnp
    y = labels.astype(logits.dtype)
    return jnp.mean(-(y * jax.nn.log_sigmoid(logits)
                      + (1 - y) * jax.nn.log_sigmoid(-logits)))


def scores(model: str, params, ids, vals, dtype=None, block: int = 4096
           ) -> np.ndarray:
    """``sigmoid(logits)`` of every row, computed ``block`` rows at a time
    so that the ``[R, K, D]`` gather fits beside the model."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32

    @jax.jit
    def one(p, i, x):
        return jax.nn.sigmoid(LOGITS[model](p, i, x)).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        p = _cast(params, dtype) if dtype != jnp.float32 else params
        out = [np.asarray(one(p, jnp.asarray(ids[lo:lo + block]),
                              jnp.asarray(vals[lo:lo + block])))
               for lo in range(0, len(ids), block)]
    return np.concatenate(out)


def tree_norms(tree) -> dict:
    """``{leaf path: l2 norm}`` (float64 on the host)."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    @jax.jit
    def norms(leaves):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in leaves]

    got = norms([x for _, x in flat])
    return {jax.tree_util.keystr(k): float(v)
            for (k, _), v in zip(flat, got)}


def train(model: str, params, batches, lr: float, dtype=None):
    """``len(batches)`` Adam steps from ``params`` (consumed) on
    ``(ids, vals, labels)`` batches.  Returns the loss of every step, the
    per-leaf norm of the first gradient, and the final parameters."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    logits = LOGITS[model]

    def loss_fn(p, ids, vals, labels):
        return bce(logits(p, ids, vals), labels)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids, vals, labels):
        loss, g = jax.value_and_grad(loss_fn)(p, ids, vals, labels)
        gnorm = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                 for x in jax.tree_util.tree_leaves(g)]
        m = jax.tree_util.tree_map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: B2 * a + (1 - B2) * b * b,
                                   v, g)
        c1 = (1 - B1 ** t).astype(dtype)
        c2 = (1 - B2 ** t).astype(dtype)
        p = jax.tree_util.tree_map(
            lambda a, mm, vv: a - lr * (mm / c1) / (jnp.sqrt(vv / c2) + EPS),
            p, m, v)
        return p, m, v, loss.astype(jnp.float32), gnorm

    with jax.default_matmul_precision("highest"):
        p = _cast(params, dtype) if dtype != jnp.float32 else params
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, first = [], None
        for t, (ids, vals, labels) in enumerate(batches, 1):
            p, m, v, loss, gnorm = step(
                p, m, v, jnp.float32(t), jnp.asarray(ids),
                jnp.asarray(vals), jnp.asarray(labels))
            losses.append(float(loss))
            if first is None:
                paths = [jax.tree_util.keystr(k) for k, _ in
                         jax.tree_util.tree_flatten_with_path(p)[0]]
                first = dict(zip(paths, map(float, gnorm)))
        del m, v
    return losses, first, p
