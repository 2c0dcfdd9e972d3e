"""Model tests: logreg/FM learn synthetic data end-to-end through the full
ingest pipeline; mesh-sharded training matches single-device results."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from dmlc_core_tpu.data import create_parser  # noqa: E402
from dmlc_core_tpu.models import (DCNv2, DeepFM, FactorizationMachine,  # noqa: E402
                                  SparseLogReg, batch_sharding, fit_stream,
                                  make_eval_step, make_train_step,
                                  param_shardings, shard_params)
from dmlc_core_tpu.pipeline import DeviceLoader  # noqa: E402


def write_linear_dataset(path, rng, n=3000, f=60):
    w_true = rng.normal(size=f)
    with open(path, "w") as fh:
        for _ in range(n):
            idx = np.sort(rng.choice(f, size=10, replace=False))
            x = rng.random(10)
            y = 1 if (w_true[idx] * x).sum() > 0 else 0
            fh.write(f"{y} " + " ".join(
                f"{j}:{v:.4f}" for j, v in zip(idx, x)) + "\n")


def test_logreg_learns(tmp_path):
    rng = np.random.default_rng(0)
    path = str(tmp_path / "lin.libsvm")
    write_linear_dataset(path, rng)
    loader = DeviceLoader(create_parser(path), batch_rows=256, nnz_cap=4096)
    model = SparseLogReg(num_features=60)
    params, _ = fit_stream(model, loader, epochs=3,
                           optimizer=optax.adam(0.05), log_every=0)
    ev = make_eval_step(model)
    loader.before_first()
    corr = tot = 0.0
    for b in loader:
        c, t = ev(params, b)
        corr += float(c)
        tot += float(t)
    loader.close()
    assert corr / tot > 0.88


def _per_step_baseline(model, path, batch_rows, nnz_cap, n_epochs=1):
    """The classic one-dispatch-per-step loop the fused trainer replaces."""
    opt = optax.adam(0.05)
    params = model.init(jax.random.PRNGKey(7))
    opt_state = opt.init(params)
    step = make_train_step(model, opt)
    loader = DeviceLoader(create_parser(path), batch_rows=batch_rows,
                          nnz_cap=nnz_cap)
    try:
        for _ in range(n_epochs):
            for b in loader:
                params, opt_state, loss = step(params, opt_state, b)
            loader.before_first()
    finally:
        loader.close()
    return params, float(loss)


@pytest.mark.parametrize("k", [1, 4, 7])
def test_fused_kstep_matches_per_step(tmp_path, k):
    """lax.scan k-step dispatch follows the SAME SGD trajectory as the
    per-step loop (stream order preserved across meta-change flushes and
    the partial tail group)."""
    from dmlc_core_tpu.models import FusedTrainer

    rng = np.random.default_rng(3)
    path = str(tmp_path / "lin.libsvm")
    write_linear_dataset(path, rng, n=1100, f=60)  # 1100/128 -> tail batch
    model = FactorizationMachine(num_features=60, dim=4)
    ref_params, ref_loss = _per_step_baseline(model, path, 128, 2048)

    opt = optax.adam(0.05)
    loader = DeviceLoader(create_parser(path), batch_rows=128, nnz_cap=2048,
                          emit="host")
    try:
        tr = FusedTrainer(model, opt, loader, k=k, seed=7)
        loss = tr.run_epoch()
    finally:
        loader.close()
    assert tr.steps == 9  # ceil(1100/128): every batch trained exactly once
    for key in ref_params:
        np.testing.assert_allclose(np.asarray(tr.params[key]),
                                   np.asarray(ref_params[key]),
                                   rtol=1e-5, atol=1e-6)
    assert abs(loss - ref_loss) < 1e-4


def test_fused_kstep_meta_change_flush(tmp_path):
    """Rows with wildly different nnz force multiple packer buckets; the
    trainer must flush on meta change and still train every batch once."""
    from dmlc_core_tpu.models import FusedTrainer

    rng = np.random.default_rng(4)
    path = str(tmp_path / "var.libsvm")
    with open(path, "w") as fh:
        for i in range(600):
            # alternate sparse / dense blocks to swing the nnz bucket
            nnz = 2 if (i // 64) % 2 == 0 else 30
            idx = np.sort(rng.choice(60, size=nnz, replace=False))
            y = i % 2
            fh.write(f"{y} " + " ".join(
                f"{j}:{v:.3f}" for j, v in zip(idx, rng.random(nnz))) + "\n")
    model = FactorizationMachine(num_features=60, dim=4)
    ref_params, _ = _per_step_baseline(model, path, 64, 64 * 32)
    loader = DeviceLoader(create_parser(path), batch_rows=64,
                          nnz_cap=64 * 32, emit="host")
    try:
        tr = FusedTrainer(model, optax.adam(0.05), loader, k=4, seed=7)
        tr.run_epoch()
    finally:
        loader.close()
    assert tr.steps == 10  # ceil(600/64)
    for key in ref_params:
        np.testing.assert_allclose(np.asarray(tr.params[key]),
                                   np.asarray(ref_params[key]),
                                   rtol=1e-5, atol=1e-6)


def test_fm_learns_interactions(tmp_path):
    # labels depend ONLY on a feature pair interaction — linear can't fit it
    rng = np.random.default_rng(1)
    path = str(tmp_path / "xor.libsvm")
    with open(path, "w") as fh:
        for _ in range(4000):
            a, b = rng.integers(0, 2), rng.integers(0, 2)
            y = a ^ b
            feats = [f"{0 if a else 1}:1", f"{2 if b else 3}:1"]
            fh.write(f"{y} " + " ".join(feats) + "\n")
    loader = DeviceLoader(create_parser(path), batch_rows=256, nnz_cap=1024)
    model = FactorizationMachine(num_features=4, dim=4)
    params, _ = fit_stream(model, loader, epochs=6,
                           optimizer=optax.adam(0.1), log_every=0)
    ev = make_eval_step(model)
    loader.before_first()
    corr = tot = 0.0
    for b in loader:
        c, t = ev(params, b)
        corr += float(c)
        tot += float(t)
    loader.close()
    assert corr / tot > 0.95


def _run_sharded(model, path, mesh_arg, table_shard="dim"):
    """One training pass under the family sharding recipe — the shared
    harness of every sharded-vs-single equivalence test (loader args,
    recipe application, step loop live HERE once)."""
    opt = optax.sgd(0.1)
    loader = DeviceLoader(create_parser(path), batch_rows=64, nnz_cap=1024,
                          sharding=batch_sharding(mesh_arg))
    params = model.init(jax.random.PRNGKey(0))
    params = shard_params(params, param_shardings(
        model, params, mesh_arg, table_shard=table_shard))
    opt_state = opt.init(params)
    step = make_train_step(model, opt, mesh_arg, donate=False)
    losses = []
    for batch in loader:
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    loader.close()
    return losses, params


def _mesh_4x2_or_skip():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual cpu devices")
    return Mesh(np.array(devices).reshape(4, 2), ("dp", "mp"))


def _dcn_factory():
    from dmlc_core_tpu.models.dcn import DCNv2

    return DCNv2(num_features=64, dim=8, layers=2)


@pytest.mark.parametrize("model_factory", [
    lambda: FactorizationMachine(num_features=64, dim=8),
    _dcn_factory,
], ids=["fm", "dcn"])
def test_sharded_step_matches_single_device(model_factory, tmp_path):
    """dp batch + dim-sharded factor table: per-step losses must match the
    single-device run for every family member (nested DCN cross params
    included), and v really is sharded over mp."""
    mesh = _mesh_4x2_or_skip()
    rng = np.random.default_rng(2)
    path = str(tmp_path / "s.libsvm")
    write_linear_dataset(path, rng, n=512)
    model = model_factory()
    losses_single, _ = _run_sharded(model, path, None)
    losses_mesh, params_mesh = _run_sharded(model, path, mesh)
    np.testing.assert_allclose(losses_single, losses_mesh, rtol=2e-4, atol=2e-5)
    # the factor table really is sharded over mp
    assert params_mesh["v"].sharding.spec == P(None, "mp")


def test_row_sharded_table_matches_single_device(tmp_path):
    """table_shard='rows' (ps/ep-style feature sharding, SURVEY §5.8):
    losses match the single-device run bit-for-tolerance and each chip
    holds a feature slice of BOTH v and w."""
    mesh = _mesh_4x2_or_skip()
    rng = np.random.default_rng(4)
    path = str(tmp_path / "r.libsvm")
    write_linear_dataset(path, rng, n=512)

    model = FactorizationMachine(num_features=64, dim=8)

    losses_single, _ = _run_sharded(model, path, None)
    losses_rows, params_rows = _run_sharded(model, path, mesh,
                                            table_shard="rows")
    np.testing.assert_allclose(losses_single, losses_rows,
                               rtol=2e-4, atol=2e-5)
    assert params_rows["v"].sharding.spec == P("mp", None)
    assert params_rows["w"].sharding.spec == P("mp")
    with pytest.raises(ValueError):
        param_shardings(model, model.init(jax.random.PRNGKey(0)), mesh,
                        table_shard="bogus")


# -- the CSR forwards against the same models written out densely ----------
#
# The flat batch is densified to X[B, F] in numpy and each model is written
# out with dense products: an implementation that shares no op with
# ``ops.csr`` (no gather, no segment sum), for the forward and for
# ``jax.grad`` of the loss.

def _dense_forward(name, xp, p, X):
    """Scores [B] of model ``name`` on dense rows ``X[B, F]``; ``xp`` is
    numpy (float64 reference) or jax.numpy (for ``jax.grad``)."""
    if name == "logreg":
        return X @ p["w"] + p["b"]
    linear = p["w0"] + X @ p["w"]
    s1 = X @ p["v"]
    if name == "dcn":
        x = s1
        for w, b in zip(p["cross"]["w"], p["cross"]["b"]):
            x = s1 * (x @ w + b) + x
        return linear + x @ p["head"]["w"] + p["head"]["b"]
    pair = 0.5 * ((s1 * s1) - (X * X) @ (p["v"] * p["v"])).sum(-1)
    if name == "fm":
        return linear + pair
    h = s1
    for w, b in zip(p["tower"]["w"], p["tower"]["b"]):
        h = xp.tanh(h @ w + b)
    return linear + pair + h @ p["head"]["w"] + p["head"]["b"]


def _dense_loss(name, l2, p, X, labels, weights):
    """Weighted BCE + l2 as ``task_loss`` defines it, written with
    softplus; padding rows carry weight 0."""
    z = _dense_forward(name, jnp, p, X)
    y = (labels > 0).astype(z.dtype)
    per = y * jax.nn.softplus(-z) + (1.0 - y) * jax.nn.softplus(z)
    base = (per * weights).sum() / jnp.maximum(weights.sum(), 1e-9)
    regs = [p["w"], p["v"]]
    if name != "fm":
        regs += [p["cross" if name == "dcn" else "tower"]["w"],
                 p["head"]["w"]]
    return base + l2 * sum(jnp.sum(r ** 2) for r in regs)


DENSE_F = 96


def _dense_model(name, l2=0.0):
    model = {"logreg": lambda: SparseLogReg(DENSE_F, l2=l2),
             "fm": lambda: FactorizationMachine(DENSE_F, dim=8, l2=l2),
             "dcn": lambda: DCNv2(DENSE_F, dim=8, layers=2, l2=l2),
             "deepfm": lambda: DeepFM(DENSE_F, dim=8, layers=2, l2=l2)}[name]()
    params = model.init(jax.random.PRNGKey(0))
    # randomize the zero-initialized leaves: an all-zero w would make the
    # linear-term comparison vacuously 0 == 0
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    return model, jax.tree_util.tree_unflatten(tree, [
        v + 0.1 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)])


def _loader_batch(tmp_path, padded):
    """One flat batch off the loader and its rows as dense X[64, F].
    ``padded``: 50 rows of 1-6 values in a 64-row, 512-value batch (padded
    rows and padded values); else 64 rows of 5 values = exactly 320."""
    rng = np.random.default_rng(11)
    path = tmp_path / "d.libsvm"
    with open(path, "w") as f:
        for i in range(50 if padded else 64):
            n = int(rng.integers(1, 7)) if padded else 5
            idx = sorted(rng.choice(DENSE_F, n, replace=False).tolist())
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.random() + 0.1:.4f}" for j in idx) + "\n")
    with DeviceLoader(create_parser(str(path)), batch_rows=64,
                      nnz_cap=512 if padded else 320) as ld:
        (batch,) = list(ld)
    ids, vals, segs = (np.asarray(batch[k]) for k in
                       ("ids", "vals", "segments"))
    live = segs < 64                 # padding points at the scratch row
    assert live.all() != padded      # the case is what its name says
    assert bool(np.asarray(batch["weights"]).all()) != padded
    X = np.zeros((64, DENSE_F), np.float64)
    np.add.at(X, (segs[live], ids[live]), vals[live])
    assert (X != 0).sum() == live.sum()
    return batch, X


@pytest.mark.parametrize("padded", [False, True], ids=["full", "padded"])
@pytest.mark.parametrize("name", ["logreg", "fm", "dcn", "deepfm"])
def test_forward_matches_dense_reference(name, padded, tmp_path):
    model, params = _dense_model(name)
    batch, X = _loader_batch(tmp_path, padded)
    ref = _dense_forward(
        name, np, jax.tree.map(lambda a: np.asarray(a, np.float64), params),
        X)
    np.testing.assert_allclose(np.asarray(model.forward(params, batch)), ref,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", ["fm", "dcn", "deepfm"])
def test_grad_matches_dense_reference(name, tmp_path):
    """``jax.grad`` of ``loss`` through the CSR gather and segment sums
    equals the dense form's on every leaf, the table's untouched rows
    (l2 alone) included."""
    l2 = 1e-3
    model, params = _dense_model(name, l2=l2)
    batch, X = _loader_batch(tmp_path, padded=True)
    loss, got = jax.value_and_grad(model.loss)(params, batch)
    ref_loss, ref = jax.value_and_grad(
        lambda p: _dense_loss(name, l2, p, jnp.asarray(X, jnp.float32),
                              batch["labels"], batch["weights"]))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(ref)):
        assert float(jnp.abs(r).max()) > 0, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=1e-6, err_msg=str(path))


def test_streaming_auc_matches_sklearn_style_reference():
    """Binned streaming AUC equals the exact pairwise AUC within bin
    resolution, accumulates across batches, and handles weights."""
    from dmlc_core_tpu.models import streaming_auc, auc_from_histograms

    rng = np.random.default_rng(0)
    n = 4000
    labels = rng.integers(0, 2, n).astype(np.float32)
    # informative but noisy scores
    scores = (labels * 1.5 - 0.75 + rng.standard_normal(n)).astype(np.float32)
    weights = rng.random(n).astype(np.float32)

    def exact_auc(s, y, w):
        pos, neg = s[y > 0], s[y == 0]
        wp, wn = w[y > 0], w[y == 0]
        wins = ties = 0.0
        for a, wa in zip(pos, wp):
            wins += wa * (wn * (a > neg)).sum()
            ties += wa * (wn * (a == neg)).sum()
        return (wins + 0.5 * ties) / (wp.sum() * wn.sum())

    want = exact_auc(scores, labels, weights)
    # accumulate over 4 streaming batches
    pos = neg = 0.0
    for i in range(0, n, 1000):
        p, q = streaming_auc(jnp.asarray(scores[i:i + 1000]),
                             jnp.asarray(labels[i:i + 1000]),
                             jnp.asarray(weights[i:i + 1000]),
                             num_bins=4096)
        pos, neg = pos + p, neg + q
    got = float(auc_from_histograms(pos, neg))
    assert abs(got - want) < 5e-3, (got, want)

    # degenerate single-class input stays finite
    p, q = streaming_auc(jnp.asarray(scores[:10]), jnp.ones((10,)),
                         jnp.ones((10,)))
    assert np.isfinite(float(auc_from_histograms(p, q)))


def test_evaluate_stream_helper(tmp_path):
    from dmlc_core_tpu.models import evaluate_stream
    rng = np.random.default_rng(6)
    path = str(tmp_path / "e.libsvm")
    write_linear_dataset(path, rng, n=600)
    loader = DeviceLoader(create_parser(path), batch_rows=128, nnz_cap=2048)
    model = SparseLogReg(num_features=60)
    params, _ = fit_stream(model, loader, epochs=3,
                           optimizer=optax.adam(0.05), log_every=0)
    loader.before_first()
    r = evaluate_stream(model, params, loader)
    loader.close()
    assert r["accuracy"] > 0.85 and 0.85 < r["auc"] <= 1.0, r
    assert r["weight"] == 600


def test_dcn_learns_interactions(tmp_path):
    """The cross network must capture a pure pairwise interaction (XOR on
    two one-hot groups) that the linear term cannot — same bar as the FM
    interaction test, met by learned cross weights instead of a fixed
    inner-product form."""
    from dmlc_core_tpu.models.dcn import DCNv2

    rng = np.random.default_rng(4)
    path = str(tmp_path / "xor.libsvm")
    with open(path, "w") as fh:
        for _ in range(4000):
            a, b = rng.integers(0, 2), rng.integers(0, 2)
            y = a ^ b
            feats = [f"{0 if a else 1}:1", f"{2 if b else 3}:1"]
            fh.write(f"{y} " + " ".join(feats) + "\n")
    loader = DeviceLoader(create_parser(path), batch_rows=256, nnz_cap=1024)
    model = DCNv2(num_features=4, dim=8, layers=2)
    params, _ = fit_stream(model, loader, epochs=6,
                           optimizer=optax.adam(0.1), log_every=0)
    ev = make_eval_step(model)
    loader.before_first()
    corr = tot = 0.0
    for b in loader:
        c, t = ev(params, b)
        corr += float(c)
        tot += float(t)
    loader.close()
    assert corr / tot > 0.95


def test_dcn_cross_layer_closed_form():
    """One cross layer is x0*(x0@W + b) + x0 exactly (DCNv2 definition) —
    pin the scan against a hand-computed numpy reference so a future
    stacking/scan refactor cannot silently reorder the recurrence."""
    from dmlc_core_tpu.models.dcn import DCNv2

    rng = np.random.default_rng(5)
    B, D = 4, 6
    x0 = rng.standard_normal((B, D)).astype(np.float32)
    w1 = rng.standard_normal((D, D)).astype(np.float32)
    b1 = rng.standard_normal(D).astype(np.float32)
    w2 = rng.standard_normal((D, D)).astype(np.float32)
    b2 = rng.standard_normal(D).astype(np.float32)
    cross = {"w": jnp.stack([w1, w2]), "b": jnp.stack([b1, b2])}
    x1 = x0 * (x0 @ w1 + b1) + x0
    x2 = x0 * (x1 @ w2 + b2) + x1            # note: x0, not x1, multiplies
    got = DCNv2._cross(cross, jnp.asarray(x0))
    np.testing.assert_allclose(np.asarray(got), x2, rtol=1e-5, atol=1e-5)


def test_dcn_registered_in_cli():
    """Registered AND reachable: the CLI enum derives from the registry,
    so a registered model must validate as a TrainParams.model value (a
    hardcoded enum once orphaned dcn — r4 review catch)."""
    from dmlc_core_tpu.models.cli import MODEL_REGISTRY, TrainParams

    assert MODEL_REGISTRY.find("dcn") is not None
    p = TrainParams()
    p.init({"data": "x.libsvm", "model": "dcn"})
    assert p.model == "dcn"


def test_plugin_model_registered_after_import_validates():
    """The model enum is LAZY (ADVICE r4): a model registered after
    models.cli imported — a user plugin — must pass TrainParams
    validation, not just MODEL_REGISTRY.find."""
    from dmlc_core_tpu.models.cli import MODEL_REGISTRY, TrainParams

    name = "plugin_model_under_test"
    MODEL_REGISTRY.register(name, "late-registered plugin")(lambda p: None)
    try:
        p = TrainParams()
        p.init({"data": "x.libsvm", "model": name})
        assert p.model == name
    finally:
        MODEL_REGISTRY.remove(name)
    with pytest.raises(Exception):
        TrainParams().init({"data": "x.libsvm", "model": name})



def test_fit_stream_host_loader_routes_through_fused(tmp_path):
    """fit_stream on an emit='host' loader trains via the k-step fused
    dispatch and learns the same task the per-step path does."""
    rng = np.random.default_rng(5)
    path = str(tmp_path / "fs.libsvm")
    write_linear_dataset(path, rng, n=2500, f=60)
    model = SparseLogReg(num_features=60)
    loader = DeviceLoader(create_parser(path), batch_rows=256, nnz_cap=4096,
                          emit="host")
    try:
        params, history = fit_stream(model, loader, epochs=4,
                                     optimizer=optax.adam(0.05),
                                     log_every=1, kstep=4)
    finally:
        loader.close()
    assert len(history) == 4 and history[-1] < history[0]
    # a device-emitting loader must REJECT kstep, not silently ignore it
    dev_loader = DeviceLoader(create_parser(path), batch_rows=256,
                              nnz_cap=4096)
    try:
        with pytest.raises(ValueError, match="emit='host'"):
            fit_stream(model, dev_loader, epochs=1, kstep=4)
    finally:
        dev_loader.close()
    ev_loader = DeviceLoader(create_parser(path), batch_rows=256,
                             nnz_cap=4096)
    ev = make_eval_step(model)
    corr = tot = 0.0
    for b in ev_loader:
        c, t = ev(params, b)
        corr += float(c)
        tot += float(t)
    ev_loader.close()
    assert corr / tot > 0.85


def test_fused_kstep_fuzz_random_shapes(tmp_path):
    """Property fuzz: random row-count/nnz-distribution corpora × random k
    — the fused trainer's step count always equals the per-step loop's,
    and final params match bitwise-closely regardless of how bucket
    boundaries and tail groups land."""
    from dmlc_core_tpu.models import FusedTrainer

    rng = np.random.default_rng(12)
    for trial in range(4):
        n = int(rng.integers(150, 900))
        k = int(rng.integers(2, 9))
        batch_rows = int(rng.choice([32, 64, 128]))
        path = str(tmp_path / f"fz{trial}.libsvm")
        with open(path, "w") as fh:
            for i in range(n):
                nnz = int(rng.integers(1, 24))
                idx = np.sort(rng.choice(60, size=nnz, replace=False))
                fh.write(f"{i % 2} " + " ".join(
                    f"{j}:{v:.3f}"
                    for j, v in zip(idx, rng.random(nnz))) + "\n")
        model = FactorizationMachine(num_features=60, dim=4)
        ref_params, _ = _per_step_baseline(model, path, batch_rows,
                                           batch_rows * 24)
        loader = DeviceLoader(create_parser(path), batch_rows=batch_rows,
                              nnz_cap=batch_rows * 24, emit="host")
        try:
            tr = FusedTrainer(model, optax.adam(0.05), loader, k=k, seed=7)
            tr.run_epoch()
        finally:
            loader.close()
        expect_steps = -(-n // batch_rows)
        assert tr.steps == expect_steps, (trial, n, batch_rows, k)
        for key in ref_params:
            np.testing.assert_allclose(
                np.asarray(tr.params[key]), np.asarray(ref_params[key]),
                rtol=1e-5, atol=1e-6,
                err_msg=f"trial {trial} n={n} k={k} rows={batch_rows}")
