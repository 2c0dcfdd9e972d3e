"""FieldAwareFM: field-bucket formulation vs brute-force pair loop,
end-to-end from libfm text through DeviceLoader(fields=True).
Reference parity: the libfm field coordinate (`src/data/libfm_parser.h:36-93`,
`include/dmlc/data.h:168`) finally has an in-framework consumer."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.models import FieldAwareFM, make_train_step  # noqa: E402


def brute_ffm(w0, w, v, rows):
    """rows: list of [(idx, field, val), ...] per example."""
    out = []
    for row in rows:
        y = w0 + sum(w[i] * x for i, _, x in row)
        for a in range(len(row)):
            for b in range(a + 1, len(row)):
                ia, fa, xa = row[a]
                ib, fb, xb = row[b]
                y += float(np.dot(v[ia, fb], v[ib, fa])) * xa * xb
        out.append(y)
    return np.array(out, np.float32)


def make_case(rng, B, kmax, F, nf):
    rows = []
    for _ in range(B):
        k = int(rng.integers(1, kmax + 1))
        idx = rng.choice(F, size=k, replace=False)
        rows.append([(int(i), int(rng.integers(0, nf)),
                      float(rng.random()) + 0.1) for i in idx])
    return rows


def to_flat(rows, B, cap):
    ids, vals, fields, segs = [], [], [], []
    for r, row in enumerate(rows):
        for i, f, x in row:
            ids.append(i), fields.append(f), vals.append(x), segs.append(r)
    pad = cap - len(ids)
    ids += [0] * pad
    vals += [0.0] * pad
    fields += [0] * pad
    segs += [B] * pad          # scratch row
    return {"ids": jnp.asarray(ids, jnp.int32),
            "vals": jnp.asarray(vals, jnp.float32),
            "fields": jnp.asarray(fields, jnp.int32),
            "segments": jnp.asarray(segs, jnp.int32),
            "labels": jnp.zeros((B,), jnp.float32),
            "weights": jnp.ones((B,), jnp.float32)}


def test_ffm_matches_bruteforce():
    rng = np.random.default_rng(7)
    B, K, F, nf, d = 6, 5, 37, 4, 3
    rows = make_case(rng, B, K, F, nf)
    model = FieldAwareFM(num_features=F, num_fields=nf, dim=d)
    params = model.init(jax.random.PRNGKey(0))
    params["w"] = jnp.asarray(rng.standard_normal(F), jnp.float32)
    params["w0"] = jnp.asarray(0.3, jnp.float32)

    expect = brute_ffm(float(params["w0"]), np.asarray(params["w"]),
                       np.asarray(params["v"]), rows)
    got = model.forward(params, to_flat(rows, B, cap=64))
    np.testing.assert_allclose(got, expect, rtol=2e-4, atol=2e-4)


def test_ffm_field_clip_and_missing_fields():
    model = FieldAwareFM(num_features=10, num_fields=2, dim=2)
    params = model.init(jax.random.PRNGKey(0))
    batch = to_flat([[(1, 5, 1.0), (2, 0, 1.0)]], 1, cap=4)  # field 5 ≥ nf
    out = model.forward(params, batch)          # clipped, not out-of-bounds
    assert np.isfinite(float(out[0]))
    with pytest.raises(KeyError):
        bad = {k: v for k, v in batch.items() if k != "fields"}
        model.forward(params, bad)


def test_ffm_trains_on_separable_fields():
    """Loss decreases and grads flow through v on a field-XOR-ish task a
    plain FM cannot represent with dim this small."""
    optax = pytest.importorskip("optax")
    rng = np.random.default_rng(0)
    B, K, F, nf, d = 64, 2, 20, 3, 4
    rows, labels = [], []
    for _ in range(B):
        i, j = rng.choice(F, size=2, replace=False)
        fi, fj = int(rng.integers(0, nf)), int(rng.integers(0, nf))
        rows.append([(int(i), fi, 1.0), (int(j), fj, 1.0)])
        labels.append(1.0 if (fi + fj) % 2 == 0 else 0.0)
    batch = to_flat(rows, B, cap=B * K)
    batch["labels"] = jnp.asarray(labels, jnp.float32)

    model = FieldAwareFM(num_features=F, num_fields=nf, dim=d,
                         init_scale=0.1)
    params = model.init(jax.random.PRNGKey(1))
    opt = optax.adam(0.05)
    state = opt.init(params)
    step = make_train_step(model, opt)
    first = None
    for _ in range(60):
        params, state, loss = step(params, state, batch)
        first = float(loss) if first is None else first
    assert float(loss) < first * 0.7, (first, float(loss))


def test_ffm_sharded_step_matches_single_device(tmp_path):
    """dp×mp mesh: FFM train losses equal the single-device run and the
    3-D factor table really shards its trailing dim over 'mp'."""
    optax = pytest.importorskip("optax")
    from jax.sharding import Mesh, PartitionSpec as P
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.models import (batch_sharding, param_shardings,
                                      shard_params)
    from dmlc_core_tpu.pipeline import DeviceLoader

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual cpu devices")
    mesh = Mesh(np.array(devices).reshape(4, 2), ("dp", "mp"))

    rng = np.random.default_rng(5)
    path = str(tmp_path / "s.libfm")
    with open(path, "w") as fh:
        for r in range(256):
            k = int(rng.integers(1, 5))
            idx = rng.choice(64, size=k, replace=False)
            ent = " ".join(f"{int(rng.integers(0, 3))}:{i}:"
                           f"{rng.random():.4f}" for i in idx)
            fh.write(f"{r % 2} {ent}\n")

    model = FieldAwareFM(num_features=64, num_fields=3, dim=4)
    opt = optax.sgd(0.1)

    def run(mesh_arg):
        loader = DeviceLoader(create_parser(path, 0, 1, "libfm"),
                              batch_rows=64, nnz_cap=512, fields=True,
                              sharding=batch_sharding(mesh_arg))
        params = model.init(jax.random.PRNGKey(0))
        params = shard_params(params,
                              param_shardings(model, params, mesh_arg))
        state = opt.init(params)
        from dmlc_core_tpu.models import make_train_step
        step = make_train_step(model, opt, mesh_arg, donate=False)
        losses = []
        for batch in loader:
            params, state, loss = step(params, state, batch)
            losses.append(float(loss))
        loader.close()
        return losses, params

    losses_single, _ = run(None)
    losses_mesh, params_mesh = run(mesh)
    np.testing.assert_allclose(losses_single, losses_mesh,
                               rtol=2e-4, atol=2e-5)
    assert params_mesh["v"].sharding.spec == P(None, None, "mp")


def test_ffm_end_to_end_from_libfm_text(tmp_path):
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader

    rng = np.random.default_rng(3)
    path = tmp_path / "t.libfm"
    lines, truth = [], []
    for r in range(23):
        k = int(rng.integers(1, 6))
        idx = rng.choice(100, size=k, replace=False)
        ent = [(int(f), int(i), round(float(x), 4))
               for f, i, x in zip(rng.integers(0, 5, k), idx, rng.random(k))]
        lines.append(f"{r % 2} " + " ".join(
            f"{f}:{i}:{x}" for f, i, x in ent))
        truth.append(sorted((i, f, np.float32(x)) for f, i, x in ent))
    path.write_text("\n".join(lines) + "\n")

    loader = DeviceLoader(
        create_parser(f"file://{path}", 0, 1, "libfm"),
        batch_rows=8, nnz_cap=64, fields=True)
    got = []
    for batch in loader:
        assert "fields" in batch
        ids = np.asarray(batch["ids"])
        vals = np.asarray(batch["vals"])
        fields = np.asarray(batch["fields"])
        segs = np.asarray(batch["segments"])
        for r in range(int(np.asarray(batch["labels"]).shape[0])):
            m = segs == r
            if m.any():
                got.append(sorted(
                    zip(ids[m].tolist(), fields[m].tolist(),
                        vals[m].tolist())))
    loader.close()
    assert len(got) == len(truth)
    for g, t in zip(got, truth):
        assert [(i, f) for i, f, _ in g] == [(i, f) for i, f, _ in t]
        np.testing.assert_allclose([x for _, _, x in g],
                                   [x for _, _, x in t], rtol=1e-5)
