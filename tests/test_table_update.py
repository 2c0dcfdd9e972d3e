"""The fused table update (``ops/table_update.py``) against the dense step.

The kernel runs interpreted here (the step takes it only on a TPU, so each
test hands the step its kernel in interpret mode); ``tests/test_tpu_compile.py``
compiles it for a described v5e.  Small tables: 2^14 rows of 32, two blocks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from dmlc_core_tpu.models import FactorizationMachine, make_train_step, train
from dmlc_core_tpu.ops import table_update
from dmlc_core_tpu.utils.metrics import metrics

F, D, R, NNZ = 1 << 14, 32, 64, 2048


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(table_update, "_table_update", functools.partial(
        table_update._table_update, interpret=True))


def _ids(kind, rng):
    """A batch's ids: ``dups`` draws with repeats and 200 padding entries;
    ``blocks`` puts 700 unique rows in the first block (six chunks of 128)
    and none in the second."""
    if kind == "dups":
        ids = rng.integers(0, F, NNZ)
        ids[:400] = rng.integers(0, 300, 400)
        return ids
    return np.concatenate([rng.permutation(700),
                           rng.integers(0, 700, NNZ - 700)])


def _batch(kind, seed=0):
    rng = np.random.default_rng(seed)
    ids = _ids(kind, rng).astype(np.int32)
    vals = rng.random(NNZ).astype(np.float32)
    seg = np.sort(rng.integers(0, R, NNZ)).astype(np.int32)
    ids[-200:], vals[-200:], seg[-200:] = 0, 0.0, R       # padding entries
    return {"ids": jnp.asarray(ids), "vals": jnp.asarray(vals),
            "segments": jnp.asarray(seg),
            "labels": jnp.asarray((rng.random(R) > 0.5).astype(np.float32)),
            "weights": jnp.ones((R,), jnp.float32)}


def _params(model):
    p = model.init(jax.random.PRNGKey(0))
    return dict(p, w=p["w"] + 0.01 * jax.random.normal(
        jax.random.PRNGKey(1), p["w"].shape))


@pytest.mark.parametrize("kind", ["dups", "blocks"])
def test_fused_step_is_dense_adam(kind, interpreted):
    model = FactorizationMachine(F, D)
    opt = optax.adam(1e-2)
    dense = jax.jit(train._sgd_step(model, opt))
    fused = jax.jit(train._sgd_step(model, opt, fused=True))
    pd = pf = _params(model)
    sd = sf = opt.init(pd)
    for i in range(5):
        batch = _batch(kind, seed=i)
        pd, sd, ld = dense(pd, sd, batch)
        pf, sf, lf = fused(pf, sf, batch)
        assert float(lf) == pytest.approx(float(ld), rel=1e-6)
    assert metrics.gauge("train.fused_tables").value == 2
    assert jax.tree.structure(sf) == jax.tree.structure(sd)
    assert int(sf[0].count) == int(sd[0].count) == 5
    for got, want in ((pf, pd), (sf[0].mu, sd[0].mu), (sf[0].nu, sd[0].nu)):
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=1e-5, atol=1e-9, err_msg=k)


@pytest.mark.parametrize("kind", ["dups", "blocks"])
def test_block_gradient_is_the_scatter(kind, interpreted):
    """With plain steps of -1 from a zero table the kernel writes the block's
    gradient itself: the scatter's, bit for bit, at every magnitude."""
    opt = optax.sgd(-1.0)
    params = {"w0": jnp.float32(0.0), "w": jnp.zeros((F,), jnp.float32),
              "v": jnp.zeros((F, D), jnp.float32)}
    state = opt.init(params)

    def update(g, s, p):
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    ids = _batch(kind)["ids"]
    uid, inv, count = table_update.batch_rows(ids)
    plan, why = table_update.plan(update, params, state, ("v", "w"), NNZ)
    assert plan is not None, why
    rng = np.random.default_rng(3)
    cap = uid.shape[0]

    def draw(shape):                    # zero past the batch's rows
        x = rng.standard_normal(shape) * 10.0 ** rng.integers(-20, 20, shape)
        live = np.arange(cap).reshape((cap,) + (1,) * (len(shape) - 1))
        return jnp.asarray(np.where(live < int(count), x, 0.0), jnp.float32)

    rows = {"v": draw((cap, D)), "w": draw((cap,))}
    flat = jax.tree.leaves((params, state, params))
    new_params, _ = jax.jit(plan.apply)(flat, rows, uid)
    for k in ("v", "w"):
        scatter = jnp.zeros_like(params[k]).at[uid].add(rows[k], mode="drop")
        np.testing.assert_array_equal(
            np.asarray(new_params[k]).view(np.int32),
            np.asarray(scatter).view(np.int32), err_msg=k)


def test_batch_rows_and_gather():
    ids = _batch("dups")["ids"]
    uid, inv, count = table_update.batch_rows(ids)
    want = np.unique(np.asarray(ids))
    assert int(count) == len(want)
    assert uid.shape[0] % 1024 == 0
    np.testing.assert_array_equal(np.asarray(uid[:int(count)]), want)
    assert bool(jnp.all(uid[int(count):] >= F))
    np.testing.assert_array_equal(np.asarray(uid[inv]), np.asarray(ids))
    table = jax.random.normal(jax.random.PRNGKey(2), (F, 3))
    got = table_update.gather_rows(table, uid, count)
    np.testing.assert_array_equal(np.asarray(got[:int(count)]),
                                  np.asarray(table[want]))
    assert not np.asarray(got[int(count):]).any()


def _mesh_step(model, opt):
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    params = train.shard_params(model.init(jax.random.PRNGKey(0)),
                                train.param_shardings(
                                    model, model.init(jax.random.PRNGKey(0)),
                                    mesh))
    return make_train_step(model, opt, mesh, donate=False), params


@pytest.mark.parametrize("case", ["l2", "clip", "mesh", "cpu"])
def test_dense_fallbacks(case, interpreted):
    """The tables keep the dense update, and the gauge says so, where the
    loss reads them whole (``l2``), the optimizer's update of them is not
    elementwise (a global-norm clip), the step has a mesh, or the arguments
    are not on a TPU."""
    model = FactorizationMachine(F, D, l2=1e-3 if case == "l2" else 0.0)
    opt = (optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-2))
           if case == "clip" else optax.adam(1e-2))
    batch = _batch("dups")
    if case == "mesh":
        step, params = _mesh_step(model, opt)
    elif case == "cpu":
        step, params = make_train_step(model, opt, donate=False), \
            _params(model)
    else:
        step = jax.jit(train._sgd_step(model, opt, fused=True))
        params = _params(model)
        dense = jax.jit(train._sgd_step(model, opt))
    metrics.gauge("train.fused_tables").set(-1)
    p, s, loss = step(params, opt.init(params), batch)
    assert metrics.gauge("train.fused_tables").value == 0
    if case in ("l2", "clip"):
        pd, _, ld = dense(params, opt.init(params), batch)
        assert float(loss) == float(ld)
        for k in pd:
            np.testing.assert_array_equal(np.asarray(p[k]), np.asarray(pd[k]))


@pytest.mark.parametrize("case, why", [
    ("no_tables", "declares no table"),
    ("odd_rows", "do not divide into blocks"),
    ("large_batch", "do not fit"),
])
def test_plan_refuses(case, why):
    opt = optax.adam(1e-2)
    rows = F + 1 if case == "odd_rows" else F
    params = {"w0": jnp.float32(0.0), "w": jnp.zeros((rows,), jnp.float32),
              "v": jnp.zeros((rows, D), jnp.float32)}

    def update(g, s, p):
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    plan, got = table_update.plan(
        update, params, opt.init(params),
        () if case == "no_tables" else ("v", "w"),
        1 << 20 if case == "large_batch" else NNZ)
    assert plan is None and why in got
