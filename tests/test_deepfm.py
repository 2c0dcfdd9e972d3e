"""DeepFM: pipelined-tower parity over a 'pp' mesh, and learning a
nonlinearity the plain FM cannot express (the forward against a dense
reference: ``test_models.py``)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.models import DeepFM, FactorizationMachine, \
    make_train_step  # noqa: E402


def _flat_batch(rng, B, F, cap):
    ids, vals, segs = [], [], []
    for r in range(B):
        k = int(rng.integers(1, 5))
        for i in rng.choice(F, size=k, replace=False):
            ids.append(int(i)), vals.append(float(rng.random()) + 0.1)
            segs.append(r)
    pad = cap - len(ids)
    return {"ids": jnp.asarray(ids + [0] * pad, jnp.int32),
            "vals": jnp.asarray(vals + [0.0] * pad, jnp.float32),
            "segments": jnp.asarray(segs + [B] * pad, jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 2, B), jnp.float32),
            "weights": jnp.ones((B,), jnp.float32)}


def test_deepfm_pipelined_tower_matches_sequential():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs 4 devices")
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devices[:4]), ("pp",))
    rng = np.random.default_rng(1)
    B, F = 16, 40
    flat = _flat_batch(rng, B, F, cap=128)
    model = DeepFM(num_features=F, dim=8, layers=4)
    params = model.init(jax.random.PRNGKey(0))
    pp = model.with_pipelined_tower(mesh, "pp", microbatches=4)
    np.testing.assert_allclose(pp.forward(params, flat),
                               model.forward(params, flat),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError):
        DeepFM(num_features=F, dim=8, layers=3).with_pipelined_tower(
            mesh, "pp")


def test_deepfm_beats_fm_on_nonlinear_target():
    """Labels depend on a threshold of the embedding sum — representable by
    the tanh tower, not by FM's quadratic form.  DeepFM must reach a lower
    train loss than FM with the same budget."""
    optax = pytest.importorskip("optax")
    rng = np.random.default_rng(2)
    B, F = 256, 30
    flat = _flat_batch(rng, B, F, cap=1280)
    # nonlinear target: parity of the number of active features in a group
    segs = np.asarray(flat["segments"])
    ids = np.asarray(flat["ids"])
    labels = np.zeros(B, np.float32)
    for r in range(B):
        m = (segs == r)
        labels[r] = float((ids[m] < 15).sum() % 2)
    flat["labels"] = jnp.asarray(labels)

    def fit(model, steps=150, lr=0.05):
        params = model.init(jax.random.PRNGKey(3))
        opt = optax.adam(lr)
        state = opt.init(params)
        step = make_train_step(model, opt)
        loss = None
        for _ in range(steps):
            params, state, loss = step(params, state, flat)
        return float(loss)

    fm_loss = fit(FactorizationMachine(num_features=F, dim=8))
    deep_loss = fit(DeepFM(num_features=F, dim=8, layers=2))
    assert deep_loss < fm_loss * 0.9, (fm_loss, deep_loss)
