"""Double-buffered host→device feed: the TPU-native replacement for the
reference's CPU consumer loop (SURVEY §7 "the prefetch ladder ends in a
double-buffered device pipeline").

Pipeline (two stages, each its own thread — reference composes the same
ladder from ``threadediter.h`` stages, `threaded_input_split.h:23` +
`parser.h:71`):

  parser → [pack thread]    fixed-shape fused host buffers (native packer
                            or numpy pack) into a bounded queue
         → [transfer thread] ``jax.device_put`` + on-device unpack into a
                            bounded queue of device batches

While step N computes on device, batch N+1 is in transfer and batch N+2 is
being packed.  The transfer stage keeps a small ring of in-flight batches:
once a batch is confirmed on device its host buffer returns to a pool, so
the steady state allocates nothing (the reference's recycling free list,
`threadediter.h:385`, applied to transfer staging).

The fused buffer uses the v2 layout (``ids[B]|vals[B]|row_ptr|labels|
weights``, B = actual nnz rounded up to a bucket): one int32 transfer per
batch sized to the data, with per-value ``segments`` rebuilt on device from
``row_ptr`` — 4·B bytes cheaper on the wire than shipping segments.  The
rebuild is search-free: a 1 scattered at every row's end, then one prefix
sum (0.04 ms a 4096 × 163840 batch on a v5e; a binary search over
``row_ptr`` is a ``while`` of scalar gathers there, 15 ms — PERF.md, PR 29).

Both layouts carry a row's ids in source order with repeats (the compact
wire bit-packs positions, it does not sort or deduplicate), so a batch may
be a packed stream of token documents: ``ids`` the tokens, ``row_ptr`` /
``segments`` the document boundaries (``pipeline.packing``).

With a sharding whose mesh spans multiple devices, ``device_put`` scatters
the batch across them (data-parallel input sharding ≙ the reference's
``ResetPartition(rank, nsplit)`` expressed on the device mesh instead of the
byte range).
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from ..data.parser import ParserBase
from ..telemetry import trace as teltrace
from ..utils import ThreadedIter, check
from ..utils.parameter import parse_lenient_bool
from . import fingerprint as fingerprint_mod
from . import page_cache
from .packing import (PackStats, batch_slices, pack_flat, pack_ragged,
                      ragged_slices)

__all__ = ["DeviceLoader", "make_decoder"]


def fused_words(batch_rows: int, nnz_bucket: int) -> int:
    """int32 words of a v2 fused batch: ids|vals|row_ptr|labels|weights."""
    return 2 * nnz_bucket + 3 * batch_rows + 1


def _decode_meta(meta: int):
    """(B, id_width, dict_bits) from a packer emit meta.  id_width 0 ⇒ v2
    layout; dict_bits 0 ⇒ raw f32 values (no dictionary)."""
    return meta & 0xFFFFFFFF, (meta >> 32) & 0xFF, (meta >> 40) & 0xFF


def _fused_words_meta(rows: int, meta: int) -> int:
    """int32 words of a fused batch for either layout (v2 or compact v3)."""
    nnz, w, dbits = _decode_meta(meta)
    if w == 0:
        return fused_words(rows, nnz)
    iw = (nnz * w + 31) // 32
    vw = ((nnz * dbits + 31) // 32 + (1 << dbits)) if dbits else nnz
    return iw + vw + 3 * rows + 1


_unpack_cache: Dict[tuple, object] = {}


def _host_segments(view: np.ndarray, rows: int, nnz: int,
                   words: int) -> np.ndarray:
    """Per-value row ids computed host-side from the buffer's row_ptr
    region (pad → ``rows`` scratch row, same contract as the on-device
    rebuild).  Used on the CPU backend, where the "on-device" rebuild would
    run on the host core anyway and np.repeat does it in 0.3 ms per
    393k-value batch."""
    voff = words - 3 * rows - 1
    rp = view[voff:voff + rows + 1]
    seg = np.full(nnz, rows, np.int32)
    n = int(rp[rows])
    seg[:n] = np.repeat(np.arange(rows, dtype=np.int32), np.diff(rp))
    return seg


def make_decoder(rows: int, meta: int):
    """Pure (traceable) decode of one fused wire buffer → batch dict.

    v2 (id_width 0): slices + bitcasts, aliasing-friendly.  Compact v3: ids
    and value codes are bit-unpacked with shifts the trace fixes — a w-bit
    stream repeats every 32/gcd(w, 32) values, so each place in a group has
    one word and one shift and nothing is indexed — and values decode
    through the shipped dictionary, the one gather left in the program
    (its indices are data).  ``segments`` (row id per value, padding →
    ``rows`` scratch row — same contract as ops.csr) are a prefix sum over
    the row ends scattered from ``row_ptr`` unless precomputed host-side.

    Shared by the per-batch jitted unpack (:func:`_get_unpack`) and the
    k-step fused trainer (models.train.make_train_step_fused), which calls
    it inside a ``lax.scan`` body so k steps ride one dispatch.
    """
    import jax.numpy as jnp
    nnz, w, dbits = _decode_meta(meta)

    def _unpack(b, segs=None):
        # scopes only name the HLO ops; the function stays ``_unpack``, so
        # the compiled program stays ``jit__unpack``
        with jax.named_scope("wire_decode"):
            f32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.float32)  # noqa: E731
            u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731

            def unpack_bits(region, width):
                # bit i*width recurs every P values over Q words, so place
                # j of every group has one word and one shift: P
                # shift-or-mask rows over a [Q, G] view, no computed index
                # (an iota-indexed pu[word] is a scalar gather, 1.16 ms a
                # 163840-value stream on a v5e — PERF.md, PR 31)
                if width == 32:
                    return region
                g = math.gcd(width, 32)
                P, Q = 32 // g, width // g
                G = -(-nnz // P)
                pu = u32(region)
                if G * Q > len(region):  # zero words under a ragged tail
                    pu = jnp.pad(pu, (0, G * Q - len(region)))
                words = pu.reshape(G, Q).T
                mask = jnp.uint32((1 << width) - 1)
                places = []
                for j in range(P):
                    a, off = (j * width) >> 5, (j * width) & 31
                    v = words[a] >> off
                    if off + width > 32:  # straddles into the next word
                        v = v | (words[a + 1] << (32 - off))
                    places.append(v & mask)
                out = jnp.stack(places).T.reshape(-1)[:nnz]
                return out.astype(jnp.int32)

            iw = nnz if w == 0 else (nnz * w + 31) // 32
            with jax.named_scope("ids"):
                # v2: raw int32 ids; v3: w-bit packed
                ids = b[:nnz] if w == 0 else unpack_bits(b[:iw], w)
            with jax.named_scope("vals"):
                if w and dbits:  # dict-coded values: dbits-wide codes + gather
                    cw = (nnz * dbits + 31) // 32
                    dw = 1 << dbits
                    codes = unpack_bits(b[iw:iw + cw], dbits)
                    vals = f32(b[iw + cw:iw + cw + dw])[codes]
                    voff = iw + cw + dw
                else:  # raw f32 (v2, and v3's fallback)
                    vals = f32(b[iw:iw + nnz])
                    voff = iw + nnz
            rp = b[voff:voff + rows + 1]
            with jax.named_scope("segments"):
                if segs is not None:
                    segments = segs
                else:
                    # segments[i] = #{r >= 1: row_ptr[r] <= i}: count the
                    # rows that end at each position, then a prefix sum.
                    # row_ptr is non-decreasing and its padding rows repeat
                    # the batch's nnz, so padding reads ``rows``; an end at
                    # ``nnz`` itself is out of range and dropped.
                    ends = jnp.zeros(nnz, jnp.int32).at[rp[1:]].add(
                        1, mode="drop", indices_are_sorted=True)
                    segments = jnp.cumsum(ends, dtype=jnp.int32)
            return {
                "ids": ids,
                "vals": vals,
                "segments": segments,
                "row_ptr": rp,
                "labels": f32(b[voff + rows + 1:voff + 2 * rows + 1]),
                "weights": f32(b[voff + 2 * rows + 1:voff + 3 * rows + 1]),
            }

    return _unpack


def _get_unpack(rows: int, meta: int):
    """Jitted on-device unpack of a fused buffer, cached per (rows, meta).
    The buffer is donated so XLA needn't keep a second copy in HBM."""
    key = (rows, meta)
    unpack = _unpack_cache.get(key)
    if unpack is None:
        # donation is a TPU/HBM win; CPU ignores it with a warning, so gate
        donate = (0,) if jax.default_backend() != "cpu" else ()
        unpack = jax.jit(make_decoder(rows, meta), donate_argnums=donate)
        _unpack_cache[key] = unpack
    return unpack


def _put_fused_buf(buf: np.ndarray, rows: int, meta: int) -> Dict[str, jax.Array]:
    """Transfer a fused int32 buffer in ONE device_put, then decode inside
    a cached jitted fn (layout chosen by the emit meta).  On the CPU
    backend segments are precomputed host-side (see _host_segments).

    The two calls are of different nature, so each is a span of its own
    inside the caller's ``device_loader.put``: ``put.transfer`` hands the
    runtime a host buffer to copy, ``put.decode`` dispatches a compiled
    program (the CPU's per-array path has no decode to dispatch).  Between
    them they cover the whole function, so that what ``put`` holds beside
    them is the spans' own entering and leaving."""
    with teltrace.span("device_loader.put.transfer"):
        words = _fused_words_meta(rows, meta)
        view = buf if len(buf) == words else buf[:words]
        if jax.default_backend() == "cpu":
            nnz, w, _ = _decode_meta(meta)
            segs = _host_segments(view, rows, nnz, words)
            dp = jax.device_put
            if w == 0:
                # v2 on CPU: slice copies + per-array puts, no jit dispatch
                # (measured ~2x cheaper per batch than fused-put + jitted
                # slices).  The .copy() is load-bearing: device_put of a
                # numpy VIEW on the CPU backend may alias rather than copy,
                # and an aliased output would be corrupted when the pooled
                # buffer is recycled — a fresh owned temp is safe either
                # way and costs the same single memcpy.
                f32 = np.float32
                return {
                    "ids": dp(view[:nnz].copy()),
                    "vals": dp(view[nnz:2 * nnz].copy().view(f32)),
                    "segments": dp(segs),
                    "row_ptr": dp(view[2 * nnz:2 * nnz + rows + 1].copy()),
                    "labels": dp(view[2 * nnz + rows + 1:
                                      2 * nnz + 2 * rows + 1].copy().view(f32)),
                    "weights": dp(
                        view[2 * nnz + 2 * rows + 1:words].copy().view(f32)),
                }
            # compact v3 on CPU (explicit opt-in): jitted decode, host
            # segments
            on_device = dp(view), dp(segs)
        else:
            on_device = (jax.device_put(view),)
    with teltrace.span("device_loader.put.decode"):
        return _get_unpack(rows, meta)(*on_device)


def _host_fused(host: Dict[str, np.ndarray], rows: int, nnz: int,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """Build the v2 fused int32 buffer from a packed host dict (python pack
    path; the native packer writes this layout directly)."""
    words = fused_words(rows, nnz)
    buf = out if out is not None and len(out) >= words else np.empty(words, np.int32)
    buf[:nnz] = host["ids"]
    buf[nnz:2 * nnz] = host["vals"].view(np.int32)
    buf[2 * nnz:2 * nnz + rows + 1] = host["row_ptr"]
    buf[2 * nnz + rows + 1:2 * nnz + 2 * rows + 1] = host["labels"].view(np.int32)
    buf[2 * nnz + 2 * rows + 1:words] = host["weights"].view(np.int32)
    return buf


def _fused_put(host: Dict[str, np.ndarray], rows: int,
               nnz: int) -> Dict[str, jax.Array]:
    """One host→device transfer for a packed flat batch."""
    return _put_fused_buf(_host_fused(host, rows, nnz), rows, nnz)


class _BufPool:
    """Bounded recycle pool for fused transfer buffers (all ``words_max``
    sized, so any buffer serves any bucket)."""

    def __init__(self, cap: int = 8):
        self.cap = cap
        self._lock = threading.Lock()
        self._bufs: list = []

    def get(self, words: int) -> np.ndarray:
        with self._lock:
            while self._bufs:
                b = self._bufs.pop()
                if len(b) >= words:
                    return b
        return np.empty(words, np.int32)

    def put(self, buf: np.ndarray) -> None:
        if not buf.flags.writeable:
            # an mmap'd page-cache view: recycling it would hand a
            # read-only buffer to a packer as scratch — drop it instead
            # (the map stays alive as long as any view does)
            return
        with self._lock:
            if len(self._bufs) < self.cap:
                self._bufs.append(buf)

    def clear(self) -> None:
        with self._lock:
            self._bufs.clear()


class _TransferPool:
    """K ordered transfer workers over the pack queue (stage-2 alternative).

    Over a high-latency host→device link a single transfer thread
    serializes round trips; K workers keep K transfers in flight while the
    consumer still sees batches in pack order (whether a direct-attached
    chip has such a link is ROADMAP S3's question — not measured).  Items are pulled from the pack queue under ``_pull_lock``
    so sequence assignment matches pull order; completed batches land in a
    reorder map keyed by sequence and are emitted strictly in order.  Same
    consumer contract as :class:`ThreadedIter` (next/before_first/destroy,
    producer-exception propagation in stream order).
    """

    def __init__(self, pack_iter: ThreadedIter, do_transfer, n_threads: int,
                 window: int):
        self._pack = pack_iter
        self._do = do_transfer          # host item -> device batch (blocking)
        self._window = max(int(n_threads), int(window))
        self._cv = threading.Condition()
        self._pull_lock = threading.Lock()
        self._done: Dict[int, tuple] = {}   # seq -> (batch, error)
        self._next_seq = 0                  # next seq a worker will pull
        self._emit_seq = 0                  # next seq the consumer takes
        self._end_seq: Optional[int] = None
        self._epoch = 0
        self._stop = False
        self._threads = [threading.Thread(target=self._worker, daemon=True)
                         for _ in range(int(n_threads))]
        for t in self._threads:
            t.start()

    def _worker(self) -> None:
        while True:
            with self._cv:
                # park at end-of-epoch / flow-control limit
                while not self._stop and (
                        self._end_seq is not None
                        or self._next_seq - self._emit_seq >= self._window):
                    self._cv.wait()
                if self._stop:
                    return
            with self._pull_lock:
                # epoch can't change while we hold _pull_lock (before_first
                # takes it), so seq/epoch read below is consistent
                with self._cv:
                    if self._stop:
                        return
                    if self._end_seq is not None:
                        continue
                    epoch = self._epoch
                    seq = self._next_seq
                try:
                    item = self._pack.next()
                except BaseException as e:  # pack/parse producer failed:
                    # surface it at this stream position (put_threads=1
                    # raises the same error through ThreadedIter)
                    with self._cv:
                        if self._epoch == epoch:
                            self._done[seq] = (None, e)
                            self._next_seq = seq + 1
                            self._end_seq = seq + 1
                            self._cv.notify_all()
                    continue
                with self._cv:
                    if item is None:
                        self._end_seq = seq
                        self._cv.notify_all()
                    else:
                        self._next_seq = seq + 1
            if item is None:
                continue
            try:
                result = (self._do(item), None)
            except BaseException as e:  # noqa: BLE001
                result = (None, e)
            with self._cv:
                if self._epoch == epoch:
                    self._done[seq] = result
                    self._cv.notify_all()

    def next(self):
        with self._cv:
            while True:
                if self._emit_seq in self._done:
                    out, err = self._done.pop(self._emit_seq)
                    self._emit_seq += 1
                    self._cv.notify_all()
                    if err is not None:
                        from ..utils.logging import DMLCError
                        raise DMLCError(
                            f"transfer worker failed: {err!r}") from err
                    return out
                if (self._end_seq is not None
                        and self._emit_seq >= self._end_seq):
                    return None
                if self._stop:
                    return None
                self._cv.wait()

    def before_first(self) -> None:
        # _pull_lock serializes against a worker mid-pull, so no item from
        # the reset stream can be tagged with a pre-reset sequence number
        with self._pull_lock:
            with self._cv:
                self._epoch += 1
                self._done.clear()
                self._next_seq = 0
                self._emit_seq = 0
                self._end_seq = None
                self._cv.notify_all()
            self._pack.before_first()

    def destroy(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=10.0)
        self._threads = []


class DeviceLoader:
    """Stream fixed-shape device batches from a parser or RowBlockIter.

    Parameters
    ----------
    source:        ParserBase or RowBlockIter (anything yielding RowBlocks).
    batch_rows:    rows per device batch (static shape).
    nnz_cap:       value capacity per batch.
    sharding:      optional ``jax.sharding.NamedSharding`` for the batch
                   arrays (batch axis over 'dp' typically).
    prefetch:      device batches to keep in flight (double buffer = 2).
    drop_remainder: drop the final partial batch instead of padding it.
    put_threads:   transfer streams.  1 = single async transfer
                   thread with an in-flight ring; >1 = ``_TransferPool`` of
                   ordered workers, each completing its transfer
                   synchronously — K concurrent h2d transfers, which
                   pipelines a high-latency link that one stream can't
                   saturate.  "auto" (default) inherits the root bench's
                   persisted winner for this backend (``pipeline.tuned``)
                   and falls back to 1.
    wire_compact:  use the native packer's v3 compact wire layout
                   (bit-packed ids + dictionary-coded values, lossless,
                   ~half the h2d bytes on typical sparse text).  "auto"
                   (default): the persisted tuning for this backend if one
                   exists, else on for any backend with a link to save
                   (non-CPU) — on CPU the encode/decode would cost pure
                   host cycles.  Ignored when the native packer is
                   unavailable.
    fields:        also ship the libfm per-value field ids (int32, padding
                   0) in each batch — required by ``FieldAwareFM``.  Field
                   batches take the per-array transfer path (the fused wire
                   layouts carry no field region), so this knob trades a
                   little transfer efficiency for the extra array.
    emit:          "device" (default) yields device batches; "host" stops
                   after stage 1 and yields the packed fused host items
                   (``("fused", buf, meta, rows)``) without touching any
                   device — the producer side of the disaggregated ingest
                   service (:mod:`dmlc_core_tpu.pipeline.ingest_service`).
                   Requires the fused path (no sharding, no fields).
                   Recycle consumed buffers via ``recycle(buf)``.
    ragged:        pack by **cumulative true nnz** against ``nnz_cap``
                   instead of padding every batch to it: batches keep the
                   flat-CSR capacity shapes but carry ``nnz_used`` /
                   ``rows_used`` prefix scalars and garbage tails
                   (``pack_ragged``) — consumers mask via
                   ``ops.ragged_csr`` (``mask_batch``) or the ragged
                   kernels.  Never truncates: a row that alone exceeds
                   ``nnz_cap`` raises.  Requires no sharding, forces the
                   python per-array path (the fused
                   wire formats carry no prefix words), and disables the
                   page cache (fused-path only; the ``ragged``
                   fingerprint field keeps stale padded pages from ever
                   serving a ragged loader).
    cache:         packed-page epoch cache (:mod:`.page_cache`).  "auto"
                   (default): enabled when the source URI carried a
                   ``#cachefile`` fragment (the page file lands at
                   ``<fragment>.pages`` with the fragment's per-partition
                   suffix) and the loader is on the fused path.  A path
                   string enables it at that exact location; None/False
                   disables.  Epoch 1 mirrors fused buffers to disk off
                   the hot path; epochs ≥2 mmap the pages and skip
                   chunk→parse→pack entirely.  Stale/truncated caches are
                   detected by fingerprint and rebuilt silently.
    cache_queue_pages / cache_readahead:
                   page-cache writer queue depth and ``MADV_WILLNEED``
                   window, in pages.  0 / None (default) defer to the
                   ``DMLC_PAGE_CACHE_QUEUE`` / ``DMLC_PAGE_CACHE_READAHEAD``
                   env knobs; explicit values are how the autotuner
                   (:mod:`.autotune`) applies these knobs per epoch.
    """

    def __init__(self, source, batch_rows: int, nnz_cap: int,
                 sharding: Optional[jax.sharding.Sharding] = None,
                 prefetch: int = 2, drop_remainder: bool = False,
                 id_mod: int = 0, put_threads="auto",
                 wire_compact="auto", fields: bool = False,
                 emit: str = "device", cache="auto",
                 ragged: bool = False, cache_queue_pages: int = 0,
                 cache_readahead: Optional[int] = None):
        check(emit in ("device", "host"), f"bad emit {emit!r}")
        if ragged:
            check(sharding is None,
                  "ragged=True requires no sharding "
                  "(prefix scalars don't shard over a batch axis)")
            check(emit == "device",
                  "ragged=True is incompatible with emit='host' (the "
                  "fused wire layouts carry no nnz_used prefix)")
        self.ragged = bool(ragged)
        if emit == "host":
            check(sharding is None and not fields,
                  "emit='host' requires the fused path "
                  "(no sharding, no fields)")
        from .tuned import resolve as _resolve_tuned
        put_threads, wire_compact = _resolve_tuned(
            jax.default_backend(), put_threads, wire_compact)
        self.wire_compact = bool(wire_compact)
        self.source = source
        self.batch_rows = batch_rows
        self.nnz_cap = nnz_cap
        self.sharding = sharding
        self.drop_remainder = drop_remainder
        self.id_mod = id_mod
        self.fields = bool(fields)
        self.stats = PackStats()
        self.emit = emit
        # trace context of the constructing (consumer) thread: the pack /
        # transfer stage threads re-activate it so their spans join the
        # trainer's trace rather than rooting one orphan trace per stage
        self._trace = teltrace.current()
        self._cache_path = self._resolve_cache(cache)
        # page-cache knobs: 0/None defer to the (leniently parsed) env
        # defaults; explicit values are the autotuner's application path
        self._cache_queue_pages = max(0, int(cache_queue_pages))
        self._cache_readahead = cache_readahead
        self._cache_writer: Optional[page_cache.PageCacheWriter] = None
        self._cache_reader: Optional[page_cache.PageCacheReader] = None
        put_threads = max(1, int(put_threads))
        depth = max(2, int(prefetch), put_threads)
        self._pool = _BufPool(cap=2 * depth + 2)
        self._inflight: deque = deque()
        self._inflight_depth = depth
        # before a stage thread exists: the consumer, the pack thread and
        # the transfer thread all read these handles
        self._bind_metrics()
        # stage 1: parse+pack in its own thread → bounded host-buffer queue
        self._pack_iter: ThreadedIter = ThreadedIter(
            max_capacity=depth,
            wait_spans=("device_loader.pack_queue.wait_slot",
                        "device_loader.pack_queue.wait_item"))
        self._pack_iter.init(self._pack_factory(), self._reset_source)
        # stage 2: device transfer → bounded device queue
        if emit == "host":
            self._iter = self._pack_iter      # stage 1 only
        elif put_threads > 1:
            self._iter = _TransferPool(
                self._pack_iter,
                lambda item: self._transfer_item(item, sync=True),
                n_threads=put_threads,
                window=max(int(prefetch), put_threads))
        else:
            # its consumer's wait is ``device_loader.next_batch`` already
            self._iter = ThreadedIter(
                max_capacity=max(1, int(prefetch)),
                wait_spans=("device_loader.batch_queue.wait_slot", None))
            self._iter.init(self._transfer_next, self._reset_transfer)

    # ---------------- stage 1: pack ----------------
    def _blocks(self) -> Iterator:
        src = self.source
        if isinstance(src, ParserBase):
            for container in src:
                yield container.get_block()
        else:  # RowBlockIter or any iterable of RowBlocks
            for blk in src:
                yield blk

    def _use_native_pack(self) -> bool:
        from .. import native
        return (self.sharding is None and not self.fields
                and not self.ragged and native.has_packer())

    def _use_streampack(self) -> bool:
        """Fused native parse→pack: text chunks straight into wire batches,
        never materialising the chunk's CSR block (throughput-neutral on a
        serial host but ~⅓ the peak RSS, and one fewer pipeline stage).
        Only for an UN-threaded, SINGLE-parse-thread text source in a
        SpPacker-supported format (libsvm/libfm/csv): a ThreadedParser's
        prefetch thread pulls chunks from the same InputSplit and would
        race this path, and a parser configured with nthreads>1 gets
        OpenMP chunk-parallel parsing from the two-stage path that this
        serial pass would silently forfeit.  ``DMLC_STREAMPACK=0`` opts
        out."""
        import os

        from .. import native
        from ..data.parser import TextParser
        return (parse_lenient_bool("DMLC_STREAMPACK") is not False
                and self._use_native_pack() and native.has_sppack()
                and type(self.source) is TextParser
                and getattr(self.source, "nthreads", 0) == 1
                and getattr(self.source, "text_format", None)
                in native.SpPacker.FORMATS)

    # ---------------- packed-page epoch cache ----------------
    def _resolve_cache(self, cache) -> Optional[str]:
        if cache in (None, False, ""):
            return None
        fused = (self.sharding is None and not self.fields
                 and not self.ragged)
        if cache == "auto":
            if not fused:
                return None
            cf = self._src_attr("cache_file")
            return page_cache.page_path(cf) if cf else None
        check(fused, "cache= requires the fused path "
                     "(no sharding, no fields)")
        return str(cache)

    def _src_attr(self, name: str, default=None):
        return fingerprint_mod.source_attr(self.source, name, default)

    def _cache_split(self):
        """The file-backed InputSplit under the source, or None (page
        caching needs stat-able source identity)."""
        return fingerprint_mod.find_file_split(self.source)

    def _cache_fingerprint(self) -> Optional[dict]:
        """Source identity (file list + sizes + mtimes) plus the full pack
        config, via the shared :mod:`.fingerprint` builder (also the basis
        of the autotuner's tuning key — one builder, so cache invalidation
        and tuning keys can never drift apart).  Recomputed at every epoch
        start, so a touched source file, a repartition
        (``reset_partition``), or any config change shifts the fingerprint
        and forces a silent rebuild."""
        split = self._cache_split()
        if split is None:
            return None
        pack_path = ("streampack" if self._use_streampack() else
                     "native" if self._use_native_pack() else "python")
        return fingerprint_mod.pack_fingerprint(
            split,
            page_format=page_cache.FORMAT_VERSION,
            batch_rows=self.batch_rows, nnz_cap=self.nnz_cap,
            id_mod=self.id_mod,
            wire_compact=self.wire_compact,
            drop_remainder=self.drop_remainder,
            # the ragged field (ISSUE 6) shifts every pre-ragged
            # fingerprint once, so pages written before it existed rebuild
            # instead of silently serving a ragged-incompatible pack
            ragged=self.ragged,
            pack_path=pack_path,
            text_format=self._src_attr("text_format"),
            csv=[self._src_attr("csv_label_col", -1),
                 self._src_attr("csv_delim", ",")])

    def cached_page_file(self) -> Optional[str]:
        """Path of a validated page file this loader would serve the next
        epoch from, or None.  The data-service worker's fd-passing lane
        asks this before streaming: when a valid cache exists, the file
        descriptor itself can cross the UNIX socket (``SCM_RIGHTS``) and
        the consumer maps the pages instead of receiving copies."""
        if self._cache_path is None:
            return None
        fingerprint = self._cache_fingerprint()
        if fingerprint is None:
            return None
        reader = page_cache.open_reader(
            self._cache_path, fingerprint,
            expected_words=lambda meta: _fused_words_meta(
                self.batch_rows, int(meta)),
            readahead=0)
        if reader is None:
            return None
        reader.close()
        return self._cache_path

    def _serve_cached(self, reader: page_cache.PageCacheReader) -> Iterator:
        """Epoch from the page file: mmap'd read-only fused views go
        straight to the transfer stage, no parse/pack at all.  The pool's
        writeable guard keeps the views out of the recycle pool when
        consumers hand them back."""
        self._cache_reader = reader
        try:
            with teltrace.span("page_cache.serve_epoch",
                               pages=reader.npages):
                it = reader.pages()
                while True:
                    with self._m_cache_read.time():
                        page = next(it, None)
                    if page is None:
                        return
                    meta, rows, view = page
                    self._m_cache_bytes_read.add(view.nbytes)
                    yield ("fused", view, meta, rows)
        finally:
            self._cache_reader = None
            reader.close()

    def _write_through(self, fingerprint: dict) -> Iterator:
        """First epoch against an absent/stale cache: serve the normal
        parse→pack stream while mirroring every fused buffer to the
        background page writer.  Backpressure or a write error drops the
        build (the epoch is served regardless); a clean end of epoch
        finalizes the page file atomically."""
        writer = page_cache.PageCacheWriter(
            self._cache_path, fingerprint,
            queue_pages=self._cache_queue_pages)
        self._cache_writer = writer
        ok = False
        try:
            for item in self._host_items_uncached():
                if item[0] == "fused" and writer.active:
                    _, buf, meta, rows = item
                    words = _fused_words_meta(self.batch_rows, int(meta))
                    with self._m_cache_write.time():
                        if writer.offer(buf, int(meta), rows, words):
                            self._m_cache_bytes_written.add(words * 4)
                        else:
                            self._m_cache_drops.add(1)
                yield item
            ok = True
        finally:
            self._cache_writer = None
            if not (ok and writer.finalize()):
                writer.abort()

    def _host_items(self) -> Iterator:
        """Yield host-side items: ('fused', buf, B, rows|None) for the
        one-transfer path, ('arrays', dict) for sharded/field batches.
        With a page cache configured, a valid cache replays mmap'd fused
        pages and a miss rebuilds it write-through."""
        if self._cache_path is None:
            yield from self._host_items_uncached()
            return
        self._maybe_bind()
        fingerprint = self._cache_fingerprint()
        reader = None
        if fingerprint is not None:
            reader = page_cache.open_reader(
                self._cache_path, fingerprint,
                expected_words=lambda meta: _fused_words_meta(
                    self.batch_rows, int(meta)),
                readahead=self._cache_readahead)
        if reader is not None:
            self._m_cache_hits.add(1)
            yield from self._serve_cached(reader)
            return
        if fingerprint is None:
            # source identity unknowable (no file-backed split under the
            # source) — serve uncached rather than risk a stale replay
            yield from self._host_items_uncached()
            return
        self._m_cache_misses.add(1)
        yield from self._write_through(fingerprint)

    def _host_items_uncached(self) -> Iterator:
        self._maybe_bind()
        if self.ragged:
            yield from self._host_items_ragged()
            return
        if self._use_streampack():
            yield from self._host_items_streampack()
            return
        if self._use_native_pack():
            yield from self._host_items_native()
            return
        fused = self.sharding is None and not self.fields
        carry = None
        for blk in self._blocks():
            for piece in batch_slices(blk, self.batch_rows):
                if carry is not None and carry.rows > 0:
                    # a pending partial tail: EVERY subsequent piece must
                    # route through the carry until it drains, or batches
                    # would leave in permuted row order (full slices
                    # jumping ahead of carried rows — breaks the one-
                    # score-per-row alignment predict depends on)
                    full = carry.add(piece)
                    if full is not None:
                        yield self._pack_host(full, fused)
                elif piece.size == self.batch_rows:
                    yield self._pack_host(piece, fused)
                else:
                    # merge leftovers across source blocks
                    if carry is None:
                        carry = _Accum(self.batch_rows)
                    full = carry.add(piece)
                    if full is not None:
                        yield self._pack_host(full, fused)
        if carry is not None and carry.rows > 0 and not self.drop_remainder:
            yield self._pack_host(carry.flush(), fused)

    def _host_items_ragged(self) -> Iterator:
        """Ragged packing: accumulate source blocks in row order, cut by
        cumulative true nnz (``ragged_slices``), and hold back the last —
        possibly partial — cut so rows from the next source block can top
        it up (the carry discipline of the padded path, but the "is it
        full" test is the nnz budget, not the row count)."""
        from ..data.row_block import RowBlockContainer

        def _nnz(b) -> int:
            o = b.offsets
            return int(o[-1] - o[0])

        acc = RowBlockContainer()
        acc_nnz = 0
        for blk in self._blocks():
            acc.push_block(blk)
            acc_nnz += _nnz(blk)
            if acc.size < self.batch_rows and acc_nnz < self.nnz_cap:
                continue
            big = acc.get_block()
            acc = RowBlockContainer()
            acc_nnz = 0
            pieces = list(ragged_slices(big, self.batch_rows,
                                        self.nnz_cap))
            for piece in pieces[:-1]:
                yield self._pack_host_ragged(piece)
            acc.push_block(pieces[-1])      # may still take more rows
            acc_nnz = _nnz(pieces[-1])
        if acc.size:
            big = acc.get_block()
            pieces = list(ragged_slices(big, self.batch_rows,
                                        self.nnz_cap))
            if self.drop_remainder:
                pieces = pieces[:-1]        # final partial batch dropped
            for piece in pieces:
                yield self._pack_host_ragged(piece)

    def _pack_span(self, stall=None, **attrs):
        """``device_loader.pack`` on every pack path: one span that is also
        the stage total, in the trace of the thread that built the loader.
        The python packers, whose every call packs one batch, also hand
        the duration to the stall detector; a native generator's ``next``
        is either a batch or the end of a block, two populations that one
        z-score would read as stalls."""
        return teltrace.span("device_loader.pack", stage=self._m_pack,
                             stall=stall, parent=self._trace, **attrs)

    def _pack_host_ragged(self, block):
        with self._pack_span(self._stall_pack, rows=block.size, ragged=True):
            host = pack_ragged(block, self.batch_rows, self.nnz_cap,
                               self.stats, id_mod=self.id_mod,
                               want_fields=self.fields)
            host["_rows"] = block.size
        return ("arrays", host)

    def _pack_host(self, block, fused: bool):
        with self._pack_span(self._stall_pack,
                             rows=getattr(block, "size", self.batch_rows)):
            host = pack_flat(block, self.batch_rows, self.nnz_cap,
                             self.stats, id_mod=self.id_mod,
                             want_segments=not fused,
                             want_fields=self.fields)
            host["_rows"] = getattr(block, "size", self.batch_rows)
            if fused:
                buf = _host_fused(host, self.batch_rows, self.nnz_cap,
                                  out=self._pool.get(
                                      fused_words(self.batch_rows, self.nnz_cap)))
                return ("fused", buf, self.nnz_cap, host["_rows"])
        return ("arrays", host)

    def _host_items_streampack(self) -> Iterator:
        """Fused fast path: InputSplit chunks → native SpPacker → fused
        wire buffers in one C++ pass (bitwise-identical to the two-stage
        path, tests/test_pipeline.py::test_streampack_matches_two_stage).
        Chunk fetch times under parser.chunk; the combined parse+pack cost
        times under device_loader.pack (parser.parse stays 0 here — one
        pass has no parse/pack boundary to attribute)."""
        from .. import native
        from ..utils.metrics import metrics
        split = self.source.source          # the TextParser's InputSplit
        m_chunk = metrics.stage("parser.chunk")
        m_bytes = metrics.throughput("parser.bytes")
        sp = native.SpPacker(self.batch_rows, self.nnz_cap,
                             id_mod=self.id_mod,
                             compact=(self.wire_compact
                                      and native.has_compact()),
                             fmt=self.source.text_format,
                             label_col=getattr(self.source,
                                               "csv_label_col", -1),
                             delim=getattr(self.source, "csv_delim", ","))
        rows_seen = 0
        try:
            while True:
                with teltrace.span("parser.chunk", stage=m_chunk):
                    chunk = split.next_chunk()
                if chunk is None:
                    break
                m_bytes.add(len(chunk))
                gen = sp.feed_text(chunk, get_buf=self._pool.get,
                                   put_buf=self._pool.put)
                while True:
                    with self._pack_span():
                        item = next(gen, None)
                    if item is None:
                        break
                    yield ("fused", item[0], item[1], None)
                st = sp.stats()
                self._m_rows.add(st["rows"] - rows_seen)
                rows_seen = st["rows"]
            if not self.drop_remainder:
                tail = sp.flush(get_buf=self._pool.get)
                if tail is not None:
                    yield ("fused", tail[0], tail[1], None)
            st = sp.stats()
            self.stats.rows += st["rows"]
            self.stats.padded_rows += st["padded_rows"]
            self.stats.truncated_values += st["truncated_values"]
        finally:
            sp.close()

    def _host_items_native(self) -> Iterator:
        """Fast path: the native packer streams CSR rows straight into fused
        transfer buffers (no per-batch numpy pack, no slice/accumulate
        churn); buffers come from the recycle pool, sized to the actual nnz
        bucket so the wire carries ~the data, not the cap."""
        from .. import native
        packer = native.Packer(self.batch_rows, self.nnz_cap,
                               id_mod=self.id_mod,
                               compact=(self.wire_compact
                                        and native.has_compact()))
        try:
            for blk in self._blocks():
                gen = packer.feed(blk, get_buf=self._pool.get,
                                  put_buf=self._pool.put)
                while True:
                    with self._pack_span():
                        item = next(gen, None)
                    if item is None:
                        break
                    yield ("fused", item[0], item[1], None)
                # real rows, once per block (carry rows count when packed);
                # rows_real=None above keeps the transfer stage from
                # double-counting what this line already counts
                self._m_rows.add(blk.size)
            if not self.drop_remainder:
                tail = packer.flush(get_buf=self._pool.get)
                if tail is not None:
                    yield ("fused", tail[0], tail[1], None)
            st = packer.stats()
            self.stats.rows += st["rows"]
            self.stats.padded_rows += st["padded_rows"]
            self.stats.truncated_values += st["truncated_values"]
        finally:
            packer.close()

    def _pack_factory(self):
        state = {"gen": None}

        def next_fn(_cell):
            if state["gen"] is None:
                state["gen"] = self._host_items()
            try:
                return next(state["gen"])
            except StopIteration:
                state["gen"] = None
                return None

        self._pack_state = state
        return next_fn

    def _reset_source(self):
        self._pack_state["gen"] = None
        self.source.before_first()

    # ---------------- stage 2: transfer ----------------
    def _transfer_next(self, _cell):
        item = self._pack_iter.next()
        if item is None:
            self._drain_inflight()
            return None
        return self._transfer_item(item, sync=False)

    def _transfer_item(self, item, sync: bool):
        """Move one packed host item to device.

        ``sync=False`` (single transfer thread): async put; the in-flight
        ring recycles host buffers once transfers land.  ``sync=True``
        (transfer pool): block until this batch is on device, then recycle
        immediately — concurrency comes from the pool's threads, and the
        ring (not thread-safe) stays unused."""
        self._maybe_bind()
        # pool mode times under its own name: K workers accumulate
        # overlapping seconds, which must not be read as serial h2d time.
        # Its children say what the stage was doing: ``put`` is the host
        # issuing the transfer and dispatching the decode, ``ring_wait`` /
        # ``pool_wait`` is the feed blocked behind the chip.
        with (teltrace.span("device_loader.h2d_pool",
                            stage=self._m_h2d_pool, stall=self._stall_h2d,
                            parent=self._trace, sync=True) if sync else
              teltrace.span("device_loader.h2d",
                            stage=self._m_h2d, stall=self._stall_h2d,
                            parent=self._trace, sync=False)):
            if item[0] == "fused":
                _, buf, nnz, rows_real = item
                # which wire the batch rode: (id_width, dict_bits) of its
                # emit meta — (0, 0) is v2, (w, 0) the raw-value fallback
                with teltrace.span("device_loader.put", stage=self._m_put,
                                   wire=_decode_meta(nnz)[1:]):
                    out = _put_fused_buf(buf, self.batch_rows, nnz)
                # wait on the WHOLE batch before recycling: the CPU direct
                # path issues independent per-array puts, so readiness of
                # one leaf doesn't imply the others have copied the buffer
                if sync:
                    self._pool_wait(out)
                    self._pool.put(buf)
                else:
                    self._ring_push(out, buf)
            else:
                host = item[1]
                rows_real = host.pop("_rows", self.batch_rows)
                # row_ptr is rows+1 long — not divisible by a dp mesh axis;
                # sharded consumers use segments, which ships anyway
                host.pop("row_ptr", None)
                # sharded arrays lead with the batch/nnz axis: one sharding
                # fits each; fusing would mix axes, so transfer per-array
                with teltrace.span("device_loader.put", stage=self._m_put), \
                        teltrace.span("device_loader.put.transfer"):
                    out = {k: jax.device_put(v, self.sharding)
                           for k, v in host.items()}
                if sync:
                    self._pool_wait(out)
        self._m_batches.add(1)
        if rows_real is not None:
            self._m_rows.add(rows_real)
        return out

    def _ring_push(self, leaf, buf: np.ndarray) -> None:
        """Track an in-flight transfer (``leaf`` is any pytree of device
        arrays — the whole batch dict); once the ring is deeper than the
        pipeline depth, wait for the oldest to land and recycle its host
        buffer (steady state: zero allocation, bounded device memory)."""
        self._inflight.append((leaf, buf))
        while len(self._inflight) > self._inflight_depth:
            old_leaf, old_buf = self._inflight.popleft()
            self._ring_wait(old_leaf)
            self._pool.put(old_buf)

    def _ring_wait(self, leaf) -> None:
        with teltrace.span("device_loader.ring_wait",
                           stage=self._m_ring_wait):
            jax.block_until_ready(leaf)

    def _pool_wait(self, leaf) -> None:
        with teltrace.span("device_loader.pool_wait",
                           stage=self._m_pool_wait):
            jax.block_until_ready(leaf)

    def _drain_inflight(self) -> None:
        while self._inflight:
            leaf, buf = self._inflight.popleft()
            try:
                self._ring_wait(leaf)
            except Exception:
                pass
            self._pool.put(buf)

    def _reset_transfer(self):
        self._drain_inflight()
        self._pack_iter.before_first()

    def _maybe_bind(self) -> None:
        from ..utils.metrics import metrics
        if getattr(self, "_m_gen", None) != metrics.generation:
            self._bind_metrics()

    def _bind_metrics(self) -> None:
        # cached handles (locked registry lookups are off the per-batch
        # path); re-bind when the registry generation changes
        from ..utils.metrics import metrics
        if not hasattr(self, "_stall_pack"):
            # stall detectors keep their EWMA history across registry
            # generations (they rebind their own gauges internally)
            from ..telemetry.anomaly import StallDetector
            self._stall_pack = StallDetector("device_loader.pack")
            self._stall_h2d = StallDetector("device_loader.h2d")
        generation = metrics.generation
        self._m_pack = metrics.stage("device_loader.pack")
        self._m_h2d = metrics.stage("device_loader.h2d")
        self._m_h2d_pool = metrics.stage("device_loader.h2d_pool")
        self._m_put = metrics.stage("device_loader.put")
        self._m_ring_wait = metrics.stage("device_loader.ring_wait")
        self._m_pool_wait = metrics.stage("device_loader.pool_wait")
        self._m_next_batch = metrics.stage("device_loader.next_batch")
        self._m_batches = metrics.counter("device_loader.batches")
        self._m_rows = metrics.throughput("device_loader.rows")
        self._m_cache_read = metrics.stage("device_loader.cache_read")
        self._m_cache_write = metrics.stage("device_loader.cache_write")
        self._m_cache_hits = metrics.counter("page_cache.hits")
        self._m_cache_misses = metrics.counter("page_cache.misses")
        self._m_cache_drops = metrics.counter("page_cache.drops")
        self._m_cache_bytes_read = metrics.counter("page_cache.bytes_read")
        self._m_cache_bytes_written = metrics.counter(
            "page_cache.bytes_written")
        # last: a thread that sees the generation may use every handle
        self._m_gen = generation

    # -- consumer side --
    def __iter__(self):
        while True:
            b = self.next_batch()
            if b is None:
                return
            yield b

    def next_batch(self) -> Optional[Dict[str, jax.Array]]:
        """The next batch, or None at the end of an epoch.  Its span is how
        long the caller waited for the feed; ``got`` is false for the
        None.  ``proc_cpu_us`` is the process's CPU clock at the hand-over
        — every thread's, a native call's own workers included — so two
        records say how many cores the process kept busy between them."""
        self._maybe_bind()
        with teltrace.span("device_loader.next_batch",
                           stage=self._m_next_batch) as s:
            batch = self._iter.next()
            s.attrs["got"] = batch is not None
            s.attrs["proc_cpu_us"] = time.process_time_ns() // 1000
        return batch

    def before_first(self) -> None:
        self._iter.before_first()

    def recycle(self, buf: np.ndarray) -> None:
        """Return a consumed host buffer to the pool (emit='host' mode)."""
        self._pool.put(buf)

    def close(self) -> None:
        # upstream first: a transfer thread blocked in pack_iter.next()
        # unblocks with None (destroy-aware next), then unwinds cleanly
        self._pack_iter.destroy()
        if self._iter is not self._pack_iter:
            self._iter.destroy()
        self._drain_inflight()
        self._pool.clear()
        # a mid-epoch close leaves the pack generator suspended inside the
        # cache stream — drop its build / map deterministically, not at GC
        writer, reader = self._cache_writer, self._cache_reader
        if writer is not None:
            writer.abort()
        if reader is not None:
            reader.close()
        if hasattr(self.source, "close"):
            self.source.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _Accum:
    """Accumulate partial RowBlocks into a full batch."""

    def __init__(self, batch_rows: int):
        from ..data.row_block import RowBlockContainer
        self.batch_rows = batch_rows
        self._container_cls = RowBlockContainer
        self._c = RowBlockContainer()

    @property
    def rows(self) -> int:
        return self._c.size

    def add(self, piece):
        self._c.push_block(piece)
        if self._c.size >= self.batch_rows:
            blk = self._c.get_block()
            out = blk.slice(0, self.batch_rows)
            rest = blk.slice(self.batch_rows, blk.size)
            self._c = self._container_cls()
            if rest.size:
                self._c.push_block(rest)
            return out
        return None

    def flush(self):
        blk = self._c.get_block()
        self._c = self._container_cls()
        return blk
