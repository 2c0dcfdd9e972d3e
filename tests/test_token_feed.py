"""The feed's contract for token documents: a libsvm row of value-less ids —
repeated, unsorted, a few hundred to several thousand of them — is a
document, and comes out of the parser, ``pack_flat``, the native packer and
both wire layouts in order and whole."""

import numpy as np
import pytest

from dmlc_core_tpu.data import create_parser
from dmlc_core_tpu.pipeline import DeviceLoader
from dmlc_core_tpu.pipeline.packing import pack_flat

VOCAB = 81920
ROWS, CAP = 4, 4096
LENGTHS = [1500, 2, 2000, 594, 1, 3000, 1000, 95, 700, 1200]


@pytest.fixture(scope="module")
def documents(tmp_path_factory):
    """(path of the text, the documents as written)."""
    rng = np.random.default_rng(30)
    docs = []
    for n in LENGTHS:
        d = rng.integers(0, VOCAB, n)
        d[n // 2:n // 2 + 3] = d[0]            # repeats, next to each other
        docs.append(d)
    docs[2][:5] = [VOCAB - 1, 0, VOCAB - 1, 0, 7]   # unsorted, extremes
    path = tmp_path_factory.mktemp("docs") / "docs.libsvm"
    with open(path, "w") as f:
        for d in docs:
            f.write("0 " + " ".join(map(str, d)) + "\n")
    return str(path), docs


def check_batches(batches, docs):
    """Every document, whole and in order, rows and tokens as written."""
    got = []
    for b in batches:
        ids, rp = np.asarray(b["ids"]), np.asarray(b["row_ptr"])
        seg = np.asarray(b["segments"]) if "segments" in b else None
        rows = len(rp) - 1
        for r in range(rows):
            if rp[r + 1] > rp[r]:
                got.append(ids[rp[r]:rp[r + 1]])
                if seg is not None:
                    assert (seg[rp[r]:rp[r + 1]] == r).all()
        if seg is not None:
            assert (seg[rp[rows]:] == rows).all()        # padding tokens
        assert (np.asarray(b["vals"])[:rp[rows]] == 1.0).all()
    assert len(got) == len(docs)
    for g, d in zip(got, docs):
        np.testing.assert_array_equal(g, d)


@pytest.mark.parametrize("nthreads", [1, 2])
def test_parser_keeps_order_and_repeats(documents, nthreads):
    path, docs = documents
    p = create_parser(f"file://{path}", 0, 1, "libsvm", nthreads=nthreads,
                      threaded=False)
    got = []
    try:
        for c in p:
            blk = c.get_block()
            off = np.asarray(blk.offsets)
            for r in range(blk.size):
                got.append(np.asarray(blk.indices[off[r]:off[r + 1]]))
            assert blk.values is None or (np.asarray(blk.values) == 1).all()
    finally:
        p.close()
    assert len(got) == len(docs)
    for g, d in zip(got, docs):
        np.testing.assert_array_equal(g.astype(np.int64), d)


@pytest.mark.parametrize("want_segments", [True, False])
def test_pack_flat_takes_value_less_ordered_rows(documents, want_segments):
    from dmlc_core_tpu.data.row_block import RowBlock
    _, docs = documents
    some = docs[:3]
    blk = RowBlock(
        offsets=np.concatenate([[0], np.cumsum([len(d) for d in some])]),
        labels=np.zeros(len(some), np.float32),
        indices=np.concatenate(some).astype(np.uint64), values=None)
    out = pack_flat(blk, ROWS, CAP, id_mod=VOCAB,
                    want_segments=want_segments)
    check_batches([out], some)


@pytest.mark.parametrize("compact", [False, True], ids=["v2", "compact"])
@pytest.mark.parametrize("path_kind", ["streampack", "native", "python"])
def test_loader_delivers_documents_whole(documents, monkeypatch, path_kind,
                                         compact):
    from dmlc_core_tpu import native
    if path_kind != "python" and not native.has_packer():
        pytest.skip("native packer not built")
    path, docs = documents
    monkeypatch.setenv("DMLC_STREAMPACK",
                       "1" if path_kind == "streampack" else "0")
    if path_kind == "python":
        monkeypatch.setattr(native, "has_packer", lambda: False)
    loader = DeviceLoader(
        create_parser(f"file://{path}", 0, 1, "libsvm", nthreads=1,
                      threaded=False),
        batch_rows=ROWS, nnz_cap=CAP, id_mod=VOCAB, wire_compact=compact)
    assert loader._use_streampack() == (path_kind == "streampack")
    try:
        batches = [{k: np.asarray(v) for k, v in b.items()} for b in loader]
    finally:
        loader.close()
    assert loader.stats.truncated_values == 0
    check_batches(batches, docs)


def test_a_batch_that_fills_rows_and_tokens_exactly_is_one_batch(tmp_path):
    """The scorer's batches fill ``batch_rows`` and ``nnz_cap`` at once:
    neither limit may close the batch one row early."""
    lens = [1000, 24, 2000, 1072]                  # sums to CAP
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, VOCAB, n) for n in lens * 3]
    path = tmp_path / "full.libsvm"
    with open(path, "w") as f:
        for d in docs:
            f.write("0 " + " ".join(map(str, d)) + "\n")
    loader = DeviceLoader(
        create_parser(f"file://{path}", 0, 1, "libsvm", nthreads=1,
                      threaded=False),
        batch_rows=ROWS, nnz_cap=CAP, id_mod=VOCAB)
    try:
        batches = [{k: np.asarray(v) for k, v in b.items()} for b in loader]
    finally:
        loader.close()
    assert len(batches) == 3
    for b in batches:
        assert b["ids"].shape == (CAP,) and b["row_ptr"][-1] == CAP
        assert (b["weights"] == 1).all()
    check_batches(batches, docs)


@pytest.mark.parametrize("used", [0, 1, 1500, 3500])
def test_dedup_ids_gives_back_an_ordered_stream_with_repeats(documents, used):
    """The sharded-embedding wire ships a batch's distinct ids once;
    ``uniq[pos]`` is the token stream again, order and repeats and all."""
    from dmlc_core_tpu.pipeline.packing import dedup_ids
    _, docs = documents
    stream = np.concatenate(docs[:3]).astype(np.int32)
    uniq, pos = dedup_ids(stream, used)
    assert (np.diff(uniq) > 0).all() and len(pos) == used
    np.testing.assert_array_equal(uniq[pos], stream[:used])
