"""Packing + DeviceLoader tests: fixed shapes, padding/truncation accounting,
epoch resets, row conservation."""

import os

import numpy as np
import pytest

from dmlc_core_tpu.data import RowBlockContainer, create_parser
from dmlc_core_tpu.pipeline import (DeviceLoader, PackStats, batch_slices,
                                    pack_flat)


def block_of(rows):
    c = RowBlockContainer()
    for label, idx, vals in rows:
        c.push_row(label, idx, vals)
    return c.get_block()


def test_pack_flat_shapes_and_padding():
    blk = block_of([(1.0, [3, 7], [0.5, 1.5]), (0.0, [2], [2.0])])
    out = pack_flat(blk, batch_rows=4, nnz_cap=8)
    assert out["ids"].shape == (8,) and out["labels"].shape == (4,)
    np.testing.assert_array_equal(out["ids"][:3], [3, 7, 2])
    np.testing.assert_array_equal(out["segments"][:3], [0, 0, 1])
    np.testing.assert_array_equal(out["segments"][3:], [4, 4, 4, 4, 4])
    np.testing.assert_array_equal(out["weights"], [1, 1, 0, 0])
    assert out["vals"][3:].sum() == 0


def test_pack_flat_truncation():
    blk = block_of([(1.0, list(range(10)), [1.0] * 10),
                    (0.0, list(range(10, 16)), [1.0] * 6)])
    stats = PackStats()
    out = pack_flat(blk, batch_rows=2, nnz_cap=8, stats=stats)
    assert stats.truncated_values == 8
    # both rows keep some values
    assert (out["segments"] == 0).sum() > 0
    assert (out["segments"] == 1).sum() > 0


def test_waterfill_minimal_truncation():
    from dmlc_core_tpu.pipeline.packing import _waterfill
    # skewed rows: short rows keep everything, only the minimum is dropped
    keep = _waterfill(np.array([1, 12]), 10)
    assert keep.sum() == 10 and keep.tolist() == [1, 9]
    keep = _waterfill(np.array([2, 3, 10]), 9)
    assert keep.sum() == 9 and keep.tolist() == [2, 3, 4]
    keep = _waterfill(np.array([5, 5, 5]), 9)
    assert keep.sum() == 9
    assert _waterfill(np.array([2, 2]), 10).tolist() == [2, 2]  # no-op
    assert _waterfill(np.array([4, 4]), 1).sum() == 1


def test_batch_slices():
    blk = block_of([(float(i), [i], [1.0]) for i in range(10)])
    pieces = list(batch_slices(blk, 4))
    assert [p.size for p in pieces] == [4, 4, 2]
    assert pieces[2].labels.tolist() == [8.0, 9.0]


@pytest.fixture()
def libsvm_file(tmp_path):
    rng = np.random.default_rng(0)
    path = tmp_path / "d.libsvm"
    with open(path, "w") as f:
        for i in range(1037):  # deliberately not a multiple of batch size
            n = int(rng.integers(1, 6))
            idx = sorted(rng.choice(100, n, replace=False).tolist())
            f.write(f"{i % 2} " + " ".join(f"{j}:1" for j in idx) + "\n")
    return str(path)


def test_device_loader_row_conservation(libsvm_file):
    with DeviceLoader(create_parser(libsvm_file), batch_rows=128,
                      nnz_cap=1024) as loader:
        batches = list(loader)
        rows = sum(int(np.asarray(b["weights"]).sum()) for b in batches)
        assert rows == 1037
        assert all(b["labels"].shape == (128,) for b in batches)
        # epochs
        loader.before_first()
        rows2 = sum(int(np.asarray(b["weights"]).sum()) for b in loader)
        assert rows2 == 1037
    assert loader.stats.rows >= 1037


def test_device_loader_transfer_pool_ordered(libsvm_file):
    """put_threads>1 (the multi-stream transfer pool for high-latency h2d
    links) must yield the exact same batch sequence as the single-thread
    path: same order, same contents, same epoch-reset behavior."""
    def collect(pt):
        with DeviceLoader(create_parser(libsvm_file), batch_rows=128,
                          nnz_cap=1024, put_threads=pt) as loader:
            first = [np.asarray(b["labels"]) for b in loader]
            loader.before_first()
            second = [np.asarray(b["labels"]) for b in loader]
        return first, second

    ref1, ref2 = collect(1)
    pool1, pool2 = collect(3)
    assert len(pool1) == len(ref1)
    for a, b in zip(ref1, pool1):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref2, pool2):
        np.testing.assert_array_equal(a, b)


def test_transfer_pool_error_propagates(libsvm_file, monkeypatch):
    from dmlc_core_tpu.utils.logging import DMLCError

    def failing(self, item, sync=True):
        raise RuntimeError("injected transfer failure")

    monkeypatch.setattr(DeviceLoader, "_transfer_item", failing)
    loader = DeviceLoader(create_parser(libsvm_file), batch_rows=128,
                          nnz_cap=1024, put_threads=2)
    with pytest.raises(DMLCError, match="injected transfer failure"):
        for _ in loader:
            pass
    loader.close()


def _loader_batches(path, wire_compact, batch_rows=128, nnz_cap=1024):
    with DeviceLoader(create_parser(path), batch_rows=batch_rows,
                      nnz_cap=nnz_cap, wire_compact=wire_compact) as loader:
        return [{k: np.asarray(v) for k, v in b.items()} for b in loader]


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert sorted(x) == sorted(y)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k], err_msg=k)


def test_wire_compact_matches_plain(libsvm_file):
    """The v3 compact wire layout (bit-packed ids + dict-coded values) must
    reconstruct bit-identical batches to the plain v2 layout.  This file has
    small ids (8-bit width) and a 2-entry value dictionary (all 1.0)."""
    from dmlc_core_tpu import native
    if not native.has_compact():
        pytest.skip("native compact packer unavailable")
    _assert_batches_equal(_loader_batches(libsvm_file, False),
                          _loader_batches(libsvm_file, True))


def test_wire_compact_variants(tmp_path):
    """Compact-wire regimes beyond the easy case: (a) high-cardinality
    values forcing the raw-f32 dictionary fallback, (b) 20-bit ids, and
    (c) a near-int32-max id forcing the 32-bit width bucket — all must
    round-trip bit-exactly, including the flushed partial batch."""
    from dmlc_core_tpu import native
    if not native.has_compact():
        pytest.skip("native compact packer unavailable")
    rng = np.random.default_rng(7)
    path = tmp_path / "v.libsvm"
    with open(path, "w") as f:
        for i in range(600):
            n = int(rng.integers(3, 9))
            idx = sorted(rng.choice(1 << 20, n, replace=False).tolist())
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.random():.6f}" for j in idx) + "\n")
        # one giant id → this batch's ids bucket to the full 32-bit width
        f.write("1 2147483646:0.5\n")
    _assert_batches_equal(_loader_batches(str(path), False),
                          _loader_batches(str(path), True))


def test_device_loader_drop_remainder(libsvm_file):
    with DeviceLoader(create_parser(libsvm_file), batch_rows=128,
                      nnz_cap=1024, drop_remainder=True) as loader:
        batches = list(loader)
    assert len(batches) == 1037 // 128
    for b in batches:
        assert int(np.asarray(b["weights"]).sum()) == 128


def test_fused_h2d_matches_per_array(tmp_path):
    """The single-transfer fused path (v2 layout: row_ptr shipped, segments
    rebuilt on device from it; host-side on the CPU backend) must produce
    bitwise-identical batch contents to the packed host arrays."""
    import numpy as np
    from dmlc_core_tpu.pipeline.device_loader import _fused_put
    rows, nnz = 64, 256
    rng = np.random.default_rng(0)
    rows_spec = []
    for i in range(50):                      # partial batch: 50 < 64 rows
        n = int(rng.integers(0, 6))          # includes empty rows
        idx = sorted(rng.choice(1000, n, replace=False).tolist())
        rows_spec.append((float(i % 2), idx, rng.random(n).astype(np.float32)))
    host = pack_flat(block_of(rows_spec), batch_rows=rows, nnz_cap=nnz)
    fused = _fused_put(host, rows, nnz)
    for k, v in host.items():
        np.testing.assert_array_equal(np.asarray(fused[k]), v, err_msg=k)
        assert fused[k].dtype == v.dtype, k


_DEC_ROWS, _DEC_NNZ = 64, 256


def _decoder_rows(case):
    """Row specs (label, ids, vals) whose row_ptr has the named shape."""
    rng = np.random.default_rng(5)

    def row(i, n):
        idx = sorted(rng.choice(1000, n, replace=False).tolist())
        return (float(i % 2), idx, rng.random(n).astype(np.float32))

    counts = {
        "empty_rows": [0 if i % 3 == 0 else 5 for i in range(_DEC_ROWS)],
        "partial_batch": [int(rng.integers(0, 6)) for _ in range(50)],
        "all_empty": [0] * _DEC_ROWS,
        "ends_at_nnz": [_DEC_NNZ // _DEC_ROWS] * _DEC_ROWS,
        "one_row_holds_all": [_DEC_NNZ],
    }[case]
    return [row(i, n) for i, n in enumerate(counts)]


def _pack_bits(values, width):
    """numpy inverse of the decoder's ``unpack_bits``: ``values`` back to
    back, ``width`` bits each, LSB first, in ceil(n * width / 32) words."""
    n = len(values)
    nw = (n * width + 31) // 32
    v = np.asarray(values).astype(np.uint64)
    bitpos = np.arange(n, dtype=np.uint64) * np.uint64(width)
    word = (bitpos >> np.uint64(5)).astype(np.int64)
    off = bitpos & np.uint64(31)
    packed = np.zeros(nw + 1, np.uint64)      # +1: the last value's spill
    np.bitwise_or.at(packed, word, (v << off) & np.uint64(0xFFFFFFFF))
    np.bitwise_or.at(packed, word + 1, (v << off) >> np.uint64(32))
    return packed[:nw].astype(np.uint32).view(np.int32)


def _wire_buffer(host, rows, nnz, id_bits):
    """(buf, meta) of a packed host dict: the v2 wire (``id_bits`` 0) or a
    compact v3 one with ``id_bits``-wide ids and raw f32 values."""
    from dmlc_core_tpu.pipeline.device_loader import _host_fused
    v2 = _host_fused(host, rows, nnz)
    if not id_bits:
        return v2, nnz
    buf = np.concatenate([_pack_bits(host["ids"], id_bits), v2[nnz:]])
    return buf, nnz | (id_bits << 32)


@pytest.mark.parametrize("nnz", [208, 203], ids=["whole_groups", "ragged_tail"])
@pytest.mark.parametrize("dbits", [0, 2, 4, 6, 8, 10, 12, 14, 16])
@pytest.mark.parametrize("w", [8, 12, 16, 20, 24, 28, 32])
def test_decoder_unpacks_every_emitted_width(w, dbits, nnz):
    """Every (id width, dictionary bits) the native packer can emit, at an
    ``nnz`` of whole 16-value groups and at one whose last group is cut
    (the decoder pads the region with zero words): ids and values come
    back bit for bit.  The last id and the last code have their top bit
    set, so a straddle into the next word that was dropped would show."""
    import jax
    from dmlc_core_tpu.pipeline.device_loader import (_fused_words_meta,
                                                      make_decoder)
    rows = 8
    rng = np.random.default_rng(w * 64 + dbits)
    ids = rng.integers(0, 1 << w, nnz, dtype=np.uint64)
    ids[-1] |= 1 << (w - 1)
    if dbits:
        codes = rng.integers(0, 1 << dbits, nnz, dtype=np.uint64)
        codes[-1] |= 1 << (dbits - 1)
        table = rng.standard_normal(1 << dbits).astype(np.float32)
        vals = table[codes]
        val_words = [_pack_bits(codes, dbits), table.view(np.int32)]
    else:
        vals = rng.standard_normal(nnz).astype(np.float32)
        val_words = [vals.view(np.int32)]
    row_ptr = np.minimum(np.arange(rows + 1) * (nnz // rows + 1), nnz)
    buf = np.concatenate(
        [_pack_bits(ids, w)] + val_words
        + [row_ptr.astype(np.int32), np.zeros(2 * rows, np.int32)])
    meta = nnz | (w << 32) | (dbits << 40)
    assert len(buf) == _fused_words_meta(rows, meta)
    out = jax.jit(make_decoder(rows, meta))(buf)
    assert out["ids"].dtype == np.int32 and out["vals"].dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(out["ids"]).view(np.uint32), ids.astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(out["vals"]).view(np.uint32), vals.view(np.uint32))


@pytest.mark.parametrize("id_bits", [0, 10], ids=["v2", "v3"])
@pytest.mark.parametrize("case", ["empty_rows", "partial_batch", "all_empty",
                                  "ends_at_nnz", "one_row_holds_all"])
def test_decoder_rebuilds_segments_on_device(case, id_bits):
    """``make_decoder`` called WITHOUT host segments — what the chip runs
    and ``_put_fused_buf`` never does on the CPU backend — rebuilds
    ``segments`` bit for bit as ``_host_segments`` does (padding → the
    scratch row ``rows``), whatever the shape of ``row_ptr``."""
    import jax
    from dmlc_core_tpu.pipeline.device_loader import (_fused_words_meta,
                                                      _host_segments,
                                                      make_decoder)
    rows, nnz = _DEC_ROWS, _DEC_NNZ
    host = pack_flat(block_of(_decoder_rows(case)), rows, nnz)
    if case in ("ends_at_nnz", "one_row_holds_all"):
        assert host["row_ptr"][rows] == nnz     # the out-of-range row end
    buf, meta = _wire_buffer(host, rows, nnz, id_bits)
    assert len(buf) == _fused_words_meta(rows, meta)
    out = jax.jit(make_decoder(rows, meta))(buf)
    assert out["segments"].dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(out["segments"]),
        _host_segments(buf, rows, nnz, len(buf)))
    for k, v in host.items():               # segments too: pack_flat's own
        np.testing.assert_array_equal(np.asarray(out[k]), v, err_msg=k)
        assert out[k].dtype == v.dtype, k


@pytest.mark.parametrize("id_bits", [0, 10], ids=["v2", "v3"])
def test_decoder_rebuilds_segments_inside_scan(id_bits):
    """The decoder as ``make_train_step_fused`` calls it: in the body of a
    ``lax.scan`` over stacked wire buffers, no host segments."""
    import jax
    from dmlc_core_tpu.pipeline.device_loader import make_decoder
    rows, nnz = _DEC_ROWS, _DEC_NNZ
    hosts = [pack_flat(block_of(_decoder_rows(c)), rows, nnz)
             for c in ("partial_batch", "ends_at_nnz")]
    bufs, metas = zip(*(_wire_buffer(h, rows, nnz, id_bits) for h in hosts))
    decode = make_decoder(rows, metas[0])
    _, out = jax.jit(lambda stacked: jax.lax.scan(
        lambda c, b: (c, decode(b)), 0, stacked))(np.stack(bufs))
    for i, host in enumerate(hosts):
        for k, v in host.items():
            np.testing.assert_array_equal(np.asarray(out[k][i]), v,
                                          err_msg=f"{k}[{i}]")


def test_ids_overflow_raises_and_id_mod_hashes():
    """VERDICT r1 #5: ids past int32 must raise, not wrap; id_mod gives the
    documented feature-hashing remap (reference keeps uint64 ids first-class,
    src/data.cc:131-147)."""
    from dmlc_core_tpu.utils import IdOverflowError
    big = np.uint64(2**33 + 5)
    blk = block_of([(1.0, np.array([1, big], np.uint64), [0.5, 1.5])])
    with pytest.raises(IdOverflowError):
        pack_flat(blk, batch_rows=2, nnz_cap=8)
    out = pack_flat(blk, batch_rows=2, nnz_cap=8, id_mod=1000)
    np.testing.assert_array_equal(out["ids"][:2], [1, int(big) % 1000])


def test_native_packer_overflow_and_id_mod():
    from dmlc_core_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    from dmlc_core_tpu.utils import IdOverflowError
    big = np.uint64(2**33 + 5)
    blk = block_of([(1.0, np.array([1, big], np.uint64), [0.5, 1.5])])
    p = native.Packer(2, 8)
    with pytest.raises(IdOverflowError):
        list(p.feed(blk))
    p.close()
    p = native.Packer(2, 8, id_mod=1000)
    assert list(p.feed(blk)) == []          # one row: stays in carry
    buf, nnz_b = p.flush()
    assert nnz_b >= 2
    np.testing.assert_array_equal(buf[:2], [1, int(big) % 1000])
    p.close()


def test_native_packer_matches_python_pack(libsvm_file):
    """The native fused packer and the python pack path must produce
    identical device batches when no early-close pressure exists."""
    from dmlc_core_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    parser = create_parser(libsvm_file, threaded=False)
    blocks = [c.get_block() for c in parser]
    parser.close()
    rows_cap, nnz_cap = 256, 8192
    p = native.Packer(rows_cap, nnz_cap)
    fused = []
    for blk in blocks:
        fused.extend(p.feed(blk))
    tail = p.flush()
    if tail is not None:
        fused.append(tail)
    # python reference: accumulate blocks then pack slice by slice
    acc = RowBlockContainer()
    for blk in blocks:
        acc.push_block(blk)
    whole = acc.get_block()
    expect = []
    for s in batch_slices(whole, rows_cap):
        expect.append(pack_flat(s, rows_cap, nnz_cap))
    assert len(fused) == len(expect)
    for (buf, B), host in zip(fused, expect):
        # v2 layout: ids[B] | vals[B] | row_ptr[rows+1] | labels | weights;
        # B <= nnz_cap is the bucketed actual nnz, python pads to nnz_cap
        assert B <= nnz_cap
        np.testing.assert_array_equal(buf[:B], host["ids"][:B])
        assert not host["ids"][B:].any()
        np.testing.assert_array_equal(
            buf[B:2 * B].view(np.float32), host["vals"][:B])
        assert not host["vals"][B:].any()
        rp = buf[2 * B:2 * B + rows_cap + 1]
        np.testing.assert_array_equal(rp, host["row_ptr"])
        np.testing.assert_array_equal(
            buf[2 * B + rows_cap + 1:2 * B + 2 * rows_cap + 1]
            .view(np.float32), host["labels"])
        np.testing.assert_array_equal(
            buf[2 * B + 2 * rows_cap + 1:2 * B + 3 * rows_cap + 1]
            .view(np.float32), host["weights"])


def test_packer_early_close_on_nnz_pressure():
    """A batch closes early (padded) when the next row would overflow
    nnz_cap — no values are lost, unlike per-slice truncation."""
    from dmlc_core_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    rows = [(float(i), np.arange(5, dtype=np.uint64), None) for i in range(4)]
    blk = block_of(rows)
    p = native.Packer(4, 12)            # 2 rows of 5 fit per batch (10 <= 12)
    bufs = list(p.feed(blk))
    tail = p.flush()
    assert len(bufs) == 1 and tail is not None
    assert bufs[0][1] >= 10             # bucket covers the 10 staged values
    st = p.stats()
    assert st["rows"] == 4 and st["truncated_values"] == 0
    p.close()


def test_pack_roundtrip_fuzz():
    """Property fuzz (the reference's recordio-fuzz idea applied to the
    pack layer): random ragged CSR blocks — including empty rows, dense
    rows, valueless features and fields — must reconstruct exactly from
    the packed layout when nothing is truncated."""
    import numpy as np
    from dmlc_core_tpu.data.row_block import RowBlockContainer
    from dmlc_core_tpu.pipeline.packing import pack_flat

    rng = np.random.default_rng(0)
    for trial in range(25):
        n = int(rng.integers(1, 40))
        c = RowBlockContainer()
        truth = []
        with_fields = bool(trial % 2)
        for r in range(n):
            k = int(rng.integers(0, 12))       # empty rows included
            idx = np.sort(rng.choice(10_000, size=k, replace=False))
            vals = rng.random(k).astype(np.float32)
            fields = (rng.integers(0, 7, k).astype(np.uint32)
                      if with_fields else None)
            c.push_row(float(r % 3), idx.astype(np.uint64), vals,
                       weight=1.0 + r,
                       fields=fields)
            truth.append((idx, vals, fields))
        blk = c.get_block()
        cap = int(blk.offsets[-1]) + 5
        rows_cap = n + int(rng.integers(0, 4))

        flat = pack_flat(blk, rows_cap, cap, want_fields=with_fields)
        for r, (idx, vals, fields) in enumerate(truth):
            m = flat["segments"] == r
            assert m.sum() == len(idx), (trial, r)
            np.testing.assert_array_equal(flat["ids"][m], idx)
            np.testing.assert_allclose(flat["vals"][m], vals, rtol=1e-6)
            if with_fields:
                np.testing.assert_array_equal(flat["fields"][m], fields)
            assert flat["labels"][r] == float(r % 3)
            assert flat["weights"][r] == 1.0 + r
        # padding rows weigh zero — silent-loss guard for the loss masks
        assert (flat["weights"][n:] == 0).all()


def test_wire_compact_property_fuzz(tmp_path):
    """Hypothesis-style generative sweep of the compact codec's regime
    space: id widths 1..31 bits, value cardinalities from binary to
    unbounded, row counts hitting every flush path — plain and compact
    wire must agree bit-exactly in all of them."""
    import itertools
    from dmlc_core_tpu import native
    if not native.has_compact():
        pytest.skip("native compact packer unavailable")
    rng = np.random.default_rng(11)
    id_spaces = [2, 1 << 7, 1 << 13, 1 << 20, (1 << 31) - 2]
    val_modes = ["binary", "quantized", "continuous"]
    rowcounts = [1, 127, 128, 300]
    for trial, (ids_hi, vmode, nrows) in enumerate(
            itertools.product(id_spaces, val_modes, rowcounts)):
        path = tmp_path / f"f{trial}.libsvm"
        with open(path, "w") as f:
            for r in range(nrows):
                n = int(rng.integers(1, 7))
                hi = min(ids_hi, 1 << 20)  # choice() cost; top id forced:
                idx = sorted(set(rng.integers(0, hi, n).tolist()))
                if r == 0 and ids_hi > hi:
                    idx = sorted(set(idx + [ids_hi - 1]))
                if vmode == "binary":
                    toks = [f"{j}:1" for j in idx]
                elif vmode == "quantized":
                    toks = [f"{j}:{rng.integers(0, 16) * 0.25}"
                            for j in idx]
                else:
                    toks = [f"{j}:{rng.random():.7f}" for j in idx]
                f.write(f"{r % 2} " + " ".join(toks) + "\n")
        _assert_batches_equal(_loader_batches(str(path), False),
                              _loader_batches(str(path), True))


def test_wire_compact_with_transfer_pool(libsvm_file):
    """The bench probes compact × put_threads on the chip; the combination
    (pool recycling + compact buffers) must agree with the plain single-
    thread path batch-for-batch."""
    from dmlc_core_tpu import native
    if not native.has_compact():
        pytest.skip("native compact packer unavailable")
    plain = _loader_batches(libsvm_file, False)
    with DeviceLoader(create_parser(libsvm_file), batch_rows=128,
                      nnz_cap=1024, wire_compact=True,
                      put_threads=4) as loader:
        pooled = [{k: np.asarray(v) for k, v in b.items()} for b in loader]
    _assert_batches_equal(plain, pooled)


def test_python_pack_preserves_row_order_across_blocks(monkeypatch):
    """Cross-block carry must not permute rows (code-review r4): once a
    partial tail is pending, later full slices may NOT jump ahead of it —
    predict's one-score-per-input-row contract depends on batch order ==
    input order.  Forced onto the python pack path (the native packer
    streams in order by construction)."""
    from dmlc_core_tpu import native
    monkeypatch.setattr(native, "has_packer", lambda: False)

    # blocks sized so tails interleave with full slices: 36-row tail, then
    # a block large enough to yield full slices while the carry is pending
    sizes = [100, 200, 37, 64, 99]
    blocks, label = [], 0
    for sz in sizes:
        c = RowBlockContainer()
        for _ in range(sz):
            c.push_row(float(label), [label % 50], [1.0])
            label += 1
        blocks.append(c.get_block())

    loader = DeviceLoader(iter(blocks), batch_rows=64, nnz_cap=256)
    seen = []
    try:
        for batch in loader:
            w = np.asarray(batch["weights"])
            seen.extend(np.asarray(batch["labels"])[w > 0].tolist())
    finally:
        loader.close()
    assert seen == [float(i) for i in range(sum(sizes))]


def test_streampack_matches_two_stage(tmp_path, monkeypatch):
    """The fused native parse→pack fast path (SpPacker: text → wire in one
    C++ pass) must produce the SAME device batch stream as the two-stage
    parse→Packer path, on messy input (label:weight heads, implicit-1.0
    tokens, blank/bad lines) across multiple chunks and both wire
    layouts."""
    from dmlc_core_tpu import native
    if not native.has_sppack():
        pytest.skip("native sppack not built")

    rng = np.random.default_rng(11)
    path = tmp_path / "m.libsvm"
    with open(path, "w") as f:
        for i in range(4000):
            n = int(rng.integers(1, 10))
            idx = np.sort(rng.choice(50_000, size=n, replace=False))
            toks = [f"{j}" if rng.random() < 0.3 else
                    f"{j}:{rng.random():.4f}" for j in idx]
            head = f"{i % 2}" if i % 5 else f"{i % 2}:{rng.random():.2f}"
            f.write(head + " " + " ".join(toks) + "\n")
            if i == 777:
                f.write("\n")            # blank line
            if i == 1234:
                f.write("1 5:xx 9:1\n")  # bad token mid-row

    from dmlc_core_tpu.data import create_parser

    def collect(streampack: bool, compact: bool):
        monkeypatch.setenv("DMLC_STREAMPACK", "1" if streampack else "0")
        loader = DeviceLoader(
            create_parser(f"file://{path}", 0, 1, "libsvm", nthreads=1,
                          threaded=False),
            batch_rows=512, nnz_cap=8192, wire_compact=compact)
        if streampack:
            assert loader._use_streampack()
        else:
            assert not loader._use_streampack()
        out = []
        try:
            for b in loader:
                out.append({k: np.asarray(v) for k, v in b.items()})
        finally:
            loader.close()
        return out, loader.stats.rows

    for compact in (False, True):
        a, rows_a = collect(True, compact)
        b, rows_b = collect(False, compact)
        assert rows_a == rows_b
        assert len(a) == len(b), (compact, len(a), len(b))
        for i, (x, y) in enumerate(zip(a, b)):
            assert x.keys() == y.keys()
            for k in x:
                np.testing.assert_array_equal(x[k], y[k], err_msg=f"{i}/{k}")


@pytest.mark.parametrize("fmt", ["libfm", "csv"])
def test_streampack_matches_two_stage_other_formats(tmp_path, monkeypatch,
                                                    fmt):
    """libfm (field dropped — fused wire carries none) and csv (column
    position = feature id, bad rows dropped whole) through the fused path
    must match the two-stage path batch-for-batch."""
    from dmlc_core_tpu import native
    if not native.has_sppack():
        pytest.skip("native sppack not built")

    rng = np.random.default_rng(13)
    if fmt == "libfm":
        path = tmp_path / "m.libfm"
        with open(path, "w") as f:
            for i in range(2000):
                n = int(rng.integers(1, 7))
                ent = " ".join(
                    f"{int(rng.integers(0, 9))}:{int(rng.integers(0, 9999))}"
                    f":{rng.random():.3f}" for _ in range(n))
                f.write(f"{i % 2} {ent}\n")
            f.write("1 3:5\n")            # malformed libfm token (2-part)
        uri = f"file://{path}"
    else:
        path = tmp_path / "m.csv"
        with open(path, "w") as f:
            for i in range(2000):
                row = rng.random(7)
                f.write(f"{i % 2}," +
                        ",".join(f"{v:.4f}" for v in row) + "\n")
            f.write("1,0.5,oops,0.25,1,2,3,4\n")   # bad cell → row dropped
            f.write("0,,0.5,,1,2,3,4\n")           # empty cells → 0.0
        uri = f"file://{path}?label_column=0"

    from dmlc_core_tpu.data import create_parser

    def collect(streampack: bool):
        monkeypatch.setenv("DMLC_STREAMPACK", "1" if streampack else "0")
        loader = DeviceLoader(
            create_parser(uri, 0, 1, fmt, nthreads=1, threaded=False),
            batch_rows=256, nnz_cap=4096)
        assert loader._use_streampack() == streampack
        out = []
        try:
            for b in loader:
                out.append({k: np.asarray(v) for k, v in b.items()})
        finally:
            loader.close()
        return out

    a, b = collect(True), collect(False)
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k], err_msg=f"{i}/{k}")


def test_streampack_with_cache_sugar(tmp_path, monkeypatch):
    """#cachefile URI sugar replays CHUNKS from the cache file on epoch 2;
    the fused streampack path consumes chunks directly from the split, so
    replay must deliver identical batches even after the source file is
    deleted (the CachedInputSplit contract)."""
    from dmlc_core_tpu import native
    if not native.has_sppack():
        pytest.skip("native sppack not built")
    rng = np.random.default_rng(17)
    src = tmp_path / "c.libsvm"
    with open(src, "w") as f:
        for i in range(800):
            idx = np.sort(rng.choice(999, size=4, replace=False))
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{rng.random():.3f}" for j in idx) + "\n")
    cache = tmp_path / "cc"
    from dmlc_core_tpu.data import create_parser
    loader = DeviceLoader(
        create_parser(f"file://{src}#{cache}", 0, 1, "libsvm", nthreads=1,
                      threaded=False),
        batch_rows=256, nnz_cap=4096)
    assert loader._use_streampack()
    try:
        ep1 = [np.asarray(b["labels"]) for b in loader]
        os.remove(src)                       # epoch 2 must come from cache
        loader.before_first()
        ep2 = [np.asarray(b["labels"]) for b in loader]
    finally:
        loader.close()
    assert len(ep1) == len(ep2) == 4
    for a, b in zip(ep1, ep2):
        np.testing.assert_array_equal(a, b)


def test_tuned_config_roundtrip_and_resolve(tmp_path, monkeypatch):
    """VERDICT r4 #2: the probe's winner persists per-platform and the
    loader's "auto" knobs resolve through it — explicit values always win,
    cpu never inherits link tuning (no link to tune)."""
    from dmlc_core_tpu.pipeline import tuned

    monkeypatch.setenv("DMLC_TUNED_CONFIG", str(tmp_path / "tuned.json"))
    assert tuned.load_tuned("tpu") is None
    # untuned defaults
    assert tuned.resolve("tpu", "auto", "auto") == (1, True)
    assert tuned.resolve("cpu", "auto", "auto") == (1, False)
    tuned.save_tuned({"platform": "tpu", "put_threads": 4,
                      "wire_compact": False, "batch_rows": 49152,
                      "nnz_cap": 1572864, "mbps": 72.3})
    tuned.save_tuned({"platform": "cpu", "put_threads": 2,
                      "wire_compact": True})
    # per-platform entries don't clobber each other
    assert tuned.load_tuned("tpu")["batch_rows"] == 49152
    assert tuned.load_tuned("cpu")["put_threads"] == 2
    # auto inherits the persisted winner (tpu); cpu stays untuned-by-design
    # (no link: extra put threads only time-slice the core, compact wire
    # costs host cycles with nothing to save — even a cpu file entry is
    # deliberately ignored)
    assert tuned.resolve("tpu", "auto", "auto") == (4, False)
    assert tuned.resolve("cpu", "auto", "auto") == (1, False)
    # explicit values pass through
    assert tuned.resolve("tpu", 2, True) == (2, True)
    # corrupt file degrades to defaults
    (tmp_path / "tuned.json").write_text("{not json")
    assert tuned.load_tuned("tpu") is None
    assert tuned.resolve("tpu", "auto", "auto") == (1, True)
