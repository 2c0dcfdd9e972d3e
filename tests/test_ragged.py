"""Ragged CSR path (ISSUE 6): ops equivalence vs the padded path,
nnz-budget packing that never truncates, the capacity-ladder serving
engine, and the best_fit golden sweep — all on the CPU/XLA fallback
(bit-identical by construction) plus interpret-mode Pallas (allclose)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from dmlc_core_tpu.data.row_block import RowBlock  # noqa: E402
from dmlc_core_tpu.ops import csr, ragged_csr  # noqa: E402
from dmlc_core_tpu.pipeline import packing  # noqa: E402
from dmlc_core_tpu.pipeline.device_loader import DeviceLoader  # noqa: E402
from dmlc_core_tpu.serving.engine import (  # noqa: E402
    BucketLadder, InferenceEngine, RequestTooLarge)
from dmlc_core_tpu.utils.metrics import metrics  # noqa: E402

F = 700          # feature space
D = 16           # embedding width


def _block(rng, rows, max_k, *, empty_every=0, giant=None):
    """Random CSR RowBlock; ``empty_every``: every Nth row empty;
    ``giant``: (row, count) forcing one huge row."""
    counts = rng.integers(1, max_k + 1, rows).astype(np.int64)
    if empty_every:
        counts[::empty_every] = 0
    if giant is not None:
        counts[giant[0]] = giant[1]
    nnz = int(counts.sum())
    return RowBlock(
        offsets=np.concatenate([[0], np.cumsum(counts)]).astype(np.uint64),
        indices=rng.integers(0, F, nnz).astype(np.uint64),
        values=rng.normal(size=nnz).astype(np.float32),
        labels=rng.integers(0, 2, rows).astype(np.float32))


def _poison_tails(batch):
    """Overwrite everything past nnz_used with hostile garbage — any
    consumer that reads past the prefix words will fail loudly."""
    k = int(batch["nnz_used"])
    batch = dict(batch)
    for key, bad in (("ids", 2**31 - 1), ("vals", np.nan),
                     ("segments", -1)):
        arr = batch[key].copy()
        arr[k:] = bad
        batch[key] = arr
    return batch


# ---------------------------------------------------------------------------
# satellite: ragged-vs-padded numerical equivalence sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fill_pct", [1, 10, 50, 100])
@pytest.mark.parametrize("shape", ["plain", "empty_rows", "giant_row"])
def test_equivalence_sweep(fill_pct, shape):
    """pack_flat + padded ops == pack_ragged + ragged ops, bit-identical
    on the XLA fallback, across fill levels 1%–100%, rows with zero
    values, and a single row holding (almost) the whole budget."""
    rng = np.random.default_rng(fill_pct * 7 + len(shape))
    rows, cap = 24, 512
    target = max(rows, cap * fill_pct // 100)
    max_k = max(1, target // rows)
    kw = {}
    if shape == "empty_rows":
        kw["empty_every"] = 3
    elif shape == "giant_row":
        # one row takes the whole budget minus one slot per other row
        max_k = 1
        kw["giant"] = (5, max(1, target - (rows - 1)))
    blk = _block(rng, rows, max_k, **kw)

    padded = packing.pack_flat(blk, rows, cap)
    rag = _poison_tails(packing.pack_ragged(blk, rows, cap))
    nnz_used = jnp.int32(int(rag["nnz_used"]))
    w = jnp.asarray(rng.normal(size=F).astype(np.float32))
    table = jnp.asarray(rng.normal(size=(F, D)).astype(np.float32))

    ref_mv = csr.csr_dense_matvec(
        jnp.asarray(padded["ids"]), jnp.asarray(padded["vals"]),
        jnp.asarray(padded["segments"]), w, rows)
    got_mv = ragged_csr.ragged_dense_matvec(
        jnp.asarray(rag["ids"]), jnp.asarray(rag["vals"]),
        jnp.asarray(rag["segments"]), nnz_used, w, rows)
    assert np.array_equal(np.asarray(got_mv), np.asarray(ref_mv))

    ref_es = csr.csr_embed_sum(
        jnp.asarray(padded["ids"]), jnp.asarray(padded["vals"]),
        jnp.asarray(padded["segments"]), table, rows)
    got_es = ragged_csr.ragged_embed_sum(
        jnp.asarray(rag["ids"]), jnp.asarray(rag["vals"]),
        jnp.asarray(rag["segments"]), nnz_used, table, rows,
        engine="xla")
    assert np.array_equal(np.asarray(got_es), np.asarray(ref_es))

    ref_fm = csr.fm_pairwise(
        jnp.asarray(padded["ids"]), jnp.asarray(padded["vals"]),
        jnp.asarray(padded["segments"]), table, rows)
    got_fm = ragged_csr.ragged_fm_pairwise(
        jnp.asarray(rag["ids"]), jnp.asarray(rag["vals"]),
        jnp.asarray(rag["segments"]), nnz_used, table, rows,
        engine="xla")
    assert np.array_equal(np.asarray(got_fm), np.asarray(ref_fm))


def test_ragged_segment_sum_tolerates_garbage_tails():
    rng = np.random.default_rng(0)
    cap, rows, used = 64, 5, 23
    data = rng.normal(size=(cap, 3)).astype(np.float32)
    segs = np.full(cap, -9, np.int32)        # hostile tail
    segs[:used] = rng.integers(0, rows, used)
    data[used:] = np.nan
    ref = np.zeros((rows, 3), np.float32)
    for i in range(used):
        ref[segs[i]] += data[i]
    got = ragged_csr.ragged_segment_sum(jnp.asarray(data),
                                        jnp.asarray(segs),
                                        jnp.int32(used), rows)
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)


def test_pallas_interpret_matches_xla():
    """The predicated DMA-ring kernels (interpret mode off-TPU) agree
    with the masked XLA reference; allclose, not bit-identical — the
    kernel accumulates in gather order per chunk."""
    rng = np.random.default_rng(2)
    rows, cap, width = 6, 48, 128
    counts = rng.integers(0, 9, rows)
    nnz = int(counts.sum())
    ids = np.full(cap, 3, np.int32)
    vals = rng.normal(size=cap).astype(np.float32)
    segs = np.full(cap, 2, np.int32)
    ids[:nnz] = rng.integers(0, F, nnz)
    segs[:nnz] = np.repeat(np.arange(rows), counts)
    table = rng.normal(size=(F, width)).astype(np.float32)

    ref = ragged_csr._embed_sum_xla(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(segs),
        jnp.int32(nnz), jnp.asarray(table), rows)
    out = ragged_csr._gather_pallas(
        jnp.asarray(ids), jnp.asarray(segs), jnp.asarray(vals),
        jnp.int32(nnz), jnp.asarray(table), rows, fm=False,
        interpret=True)[:rows]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)

    ref_fm = ragged_csr._fm_pairwise_xla(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(segs),
        jnp.int32(nnz), jnp.asarray(table), rows)
    s1, s2 = ragged_csr._gather_pallas(
        jnp.asarray(ids), jnp.asarray(segs), jnp.asarray(vals),
        jnp.int32(nnz), jnp.asarray(table), rows, fm=True,
        interpret=True)
    got_fm = 0.5 * jnp.sum(s1[:rows] * s1[:rows] - s2[:rows], axis=-1)
    np.testing.assert_allclose(np.asarray(got_fm), np.asarray(ref_fm),
                               atol=1e-4)

    # zero fill: output must be exactly zero, no DMA ran
    out0 = ragged_csr._gather_pallas(
        jnp.asarray(ids), jnp.asarray(segs), jnp.asarray(vals),
        jnp.int32(0), jnp.asarray(table), rows, fm=False,
        interpret=True)[:rows]
    assert (np.asarray(out0) == 0).all()


def test_mask_batch_matches_padded_model_forward():
    """mask_batch turns a garbage-tailed ragged batch into the padded
    convention: a zoo model's forward is bit-identical on both."""
    from dmlc_core_tpu.models import SparseLogReg
    rng = np.random.default_rng(3)
    rows, cap = 16, 256
    blk = _block(rng, rows, 8)
    padded = packing.pack_flat(blk, rows, cap)
    rag = _poison_tails(packing.pack_ragged(blk, rows, cap))
    model = SparseLogReg(num_features=F)
    params = {"w": jnp.arange(F, dtype=jnp.float32) / F,
              "b": jnp.float32(0.5)}
    ref = model.forward(params, {k: jnp.asarray(v)
                                 for k, v in padded.items()})
    masked = ragged_csr.mask_batch({k: jnp.asarray(v)
                                    for k, v in rag.items()})
    got = model.forward(params, masked)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# pack_ragged / ragged_slices: budget cuts, never truncate
# ---------------------------------------------------------------------------

def test_ragged_slices_cover_exactly_once_within_budget():
    rng = np.random.default_rng(4)
    blk = _block(rng, 100, 12, empty_every=7)
    rows = nnz = 0
    prev_end = 0
    for s in packing.ragged_slices(blk, batch_rows=16, nnz_cap=64):
        o = s.offsets.astype(np.int64)
        snnz = int(o[-1] - o[0])
        assert s.size <= 16 and snnz <= 64
        rows += s.size
        nnz += snnz
        prev_end += s.size
    assert rows == blk.size
    assert nnz == int(blk.offsets[-1] - blk.offsets[0])


def test_ragged_never_truncates_giant_row_raises():
    rng = np.random.default_rng(5)
    blk = _block(rng, 3, 4, giant=(1, 200))
    with pytest.raises(ValueError, match="never truncates"):
        list(packing.ragged_slices(blk, batch_rows=8, nnz_cap=64))
    with pytest.raises(ValueError, match="never truncates"):
        packing.pack_ragged(blk, 8, 64)


def test_pack_ragged_prefix_equals_pack_flat():
    rng = np.random.default_rng(6)
    blk = _block(rng, 10, 6)
    flat = packing.pack_flat(blk, 16, 128)
    rag = packing.pack_ragged(blk, 16, 128)
    k = int(rag["nnz_used"])
    assert int(rag["rows_used"]) == blk.size
    for key in ("ids", "vals", "segments"):
        assert np.array_equal(rag[key][:k], flat[key][:k])
    assert np.array_equal(rag["row_ptr"], flat["row_ptr"])
    assert np.array_equal(rag["labels"], flat["labels"])
    assert np.array_equal(rag["weights"], flat["weights"])


def test_pack_flat_truncation_is_surfaced():
    """Satellite: silent pack_flat truncation now bumps the
    pipeline.pack.* counters (and logs, rate-limited)."""
    rng = np.random.default_rng(7)
    blk = _block(rng, 20, 10)
    total = int(blk.offsets[-1])
    v0 = metrics.counter("pipeline.pack.truncated_values").value
    r0 = metrics.counter("pipeline.pack.truncated_rows").value
    stats = packing.PackStats()
    packing.pack_flat(blk, 20, total // 2, stats=stats)
    dv = metrics.counter("pipeline.pack.truncated_values").value - v0
    dr = metrics.counter("pipeline.pack.truncated_rows").value - r0
    assert dv == stats.truncated_values > 0
    assert dr == stats.truncated_rows > 0
    assert stats.padding_ratio > 0


def test_device_loader_ragged_end_to_end():
    """Ragged loader: every row exactly once, in order, within budget,
    prefix words on every batch, padding_ratio 1.0."""
    rng = np.random.default_rng(8)
    blocks = [_block(rng, 30, 9, empty_every=5) for _ in range(4)]

    class Src:
        def __iter__(self):
            return iter(blocks)

        def before_first(self):
            pass

    dl = DeviceLoader(Src(), batch_rows=16, nnz_cap=64, ragged=True)
    rows = nnz = 0
    labels = []
    for b in dl:
        ru, nu = int(b["rows_used"]), int(b["nnz_used"])
        assert ru <= 16 and nu <= 64
        assert b["ids"].shape == (64,) and b["labels"].shape == (16,)
        rows += ru
        nnz += nu
        labels.append(np.asarray(b["labels"])[:ru])
    dl.close()
    assert rows == sum(b.size for b in blocks)
    assert nnz == sum(int(b.offsets[-1]) for b in blocks)
    assert np.array_equal(np.concatenate(labels),
                          np.concatenate([b.labels for b in blocks]))
    assert dl.stats.padding_ratio == 1.0


def test_device_loader_ragged_fingerprint_field():
    """The pack-config fingerprint carries the ragged flag, so pages
    written by a padded loader can never serve a ragged one (PR-4 cache
    invalidation contract)."""
    rng = np.random.default_rng(9)

    class Src:
        def __iter__(self):
            return iter([_block(rng, 8, 4)])

        def before_first(self):
            pass

    dl = DeviceLoader(Src(), batch_rows=8, nnz_cap=64, ragged=True)
    try:
        import inspect

        from dmlc_core_tpu.pipeline import fingerprint as fp

        # the shared builder carries the flag...
        assert '"ragged"' in inspect.getsource(fp.pack_fingerprint)
        # ...and the loader threads its own setting into it
        src = inspect.getsource(type(dl)._cache_fingerprint)
        assert "ragged=self.ragged" in src
        assert dl.ragged is True
    finally:
        dl.close()


# ---------------------------------------------------------------------------
# serving: best_fit golden sweep + ragged capacity engine
# ---------------------------------------------------------------------------

def test_best_fit_bisect_matches_linear_sweep():
    """Golden selection sweep (satellite): the bisect early-exit picks
    the same bucket as the full linear scan for every (rows, nnz)."""
    for lad in (BucketLadder.default(),
                BucketLadder.ragged_default(),
                BucketLadder([(8, 64), (8, 512), (32, 512),
                              (128, 4096), (7, 333)])):
        for rows in range(1, lad.max_rows + 2, 3):
            for nnz in range(1, lad.max_nnz + 2,
                             max(1, lad.max_nnz // 97)):
                ref = next((b for b in lad.buckets
                            if b.rows >= rows and b.nnz >= nnz), None)
                try:
                    got = lad.best_fit(rows, nnz)
                except RequestTooLarge:
                    got = None
                assert got == ref, (rows, nnz, got, ref)


def test_ragged_default_ladder_is_small():
    assert len(BucketLadder.ragged_default()) <= 3
    assert len(BucketLadder.ragged_default()) < len(BucketLadder.default())


def _fm_engines(ladder):
    from dmlc_core_tpu.models import FactorizationMachine
    model = FactorizationMachine(num_features=F, dim=8)
    params = model.init(jax.random.PRNGKey(0))
    pad = InferenceEngine(model, params, postprocess="sigmoid",
                          buckets=BucketLadder(list(ladder)))
    rag = InferenceEngine(model, params, postprocess="sigmoid",
                          ragged=True, buckets=BucketLadder(list(ladder)))
    return pad, rag


def test_ragged_engine_scores_bit_identical():
    pad, rag = _fm_engines([(8, 128), (32, 512)])
    rng = np.random.default_rng(10)
    for rows, k in [(1, 4), (8, 15), (30, 16), (32, 16), (3, 1)]:
        counts = rng.integers(1, k + 1, rows)
        ids = rng.integers(0, F, int(counts.sum())).astype(np.int32)
        vals = rng.random(len(ids), dtype=np.float32)
        rp = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        assert np.array_equal(pad.predict(ids, vals, rp),
                              rag.predict(ids, vals, rp))


def test_ragged_engine_compile_count_flat_under_mixed_traffic():
    """The no-retrace proof for the capacity ladder: warmup compiles
    every tier, then maximally mixed (rows, nnz) traffic adds ZERO
    compiles and no watchdog alert — one executable per capacity serves
    every fill level."""
    from dmlc_core_tpu.telemetry import xla_introspect
    _, rag = _fm_engines([(8, 128), (32, 512)])
    xla_introspect.watchdog.reset_alert()
    rag.warmup_all()
    assert rag.compile_count == len(rag.ladder) == 2
    rng = np.random.default_rng(11)
    for _ in range(40):
        rows = int(rng.integers(1, 33))
        counts = rng.integers(1, 17, rows)
        nnz = int(counts.sum())
        if nnz > 512:
            continue
        ids = rng.integers(0, F, nnz).astype(np.int32)
        vals = rng.random(nnz, dtype=np.float32)
        rp = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        rag.predict(ids, vals, rp)
    assert rag.compile_count == 2          # steady state: zero retraces
    assert not xla_introspect.watchdog.alerted


def test_ragged_engine_env_pin_roundtrip(monkeypatch):
    """DMLC_RAGGED_ENGINE pins the ops dispatch; bogus values raise."""
    monkeypatch.setenv("DMLC_RAGGED_ENGINE", "xla")
    out = ragged_csr.ragged_embed_sum(
        jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.float32),
        jnp.zeros(8, jnp.int32), jnp.int32(4),
        jnp.ones((4, 8), jnp.float32), 2)
    assert out.shape == (2, 8)
    monkeypatch.setenv("DMLC_RAGGED_ENGINE", "bogus")
    with pytest.raises(ValueError, match="unknown ragged engine"):
        ragged_csr.ragged_embed_sum(
            jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.float32),
            jnp.zeros(8, jnp.int32), jnp.int32(4),
            jnp.ones((4, 8), jnp.float32), 2)
