"""Pack ragged CSR RowBlocks into fixed-shape device batches.

XLA compiles one program per shape (SURVEY §7: "static shapes"), so the
variable-length RowBlocks coming off the parsers must become **fixed-shape**
arrays before hitting the TPU.  Two layouts:

* :func:`pack_flat` — flat CSR: ``ids[nnz_cap]``, ``vals[nnz_cap]``,
  ``segments[nnz_cap]`` (row id per entry; padding entries get
  ``segment == batch_rows`` so a trailing scratch row absorbs them — see
  ``ops.csr``), plus ``labels/weights[batch_rows]``.  Rows whose values
  overflow ``nnz_cap`` are truncated (counted in ``truncated``).
* :func:`pack_ragged` — same flat layout as :func:`pack_flat` but **no
  tail zeroing and no truncation**: the nnz-sized arrays are
  ``np.empty`` capacity buffers valid only up to an explicit ``nnz_used``
  prefix word (``ops.ragged_csr`` consumes them; everything past the
  prefix is garbage by contract).  Batches are cut by *cumulative true
  nnz* against the capacity (:func:`ragged_slices`), so fill level — not
  a padding ceiling — sets throughput; a row that alone exceeds the
  capacity raises instead of being silently clipped.

Padding rows carry ``weight 0`` so losses ignore them without masking logic.

**Order is part of the contract.**  Every packer copies a row's ids in the
order the source gave them, repeats included, and never sorts or merges
them; a block without values (``values is None``: libsvm tokens written as
bare ids) packs ``vals`` of 1.  So a row may be a *document* and its ids its
*tokens*: ``row_ptr`` / ``segments`` are then the document boundaries of one
packed token stream (``models.hybrid_lm``).  The native packer and both wire
layouts keep the same contract (``tests/test_token_feed.py``).

Truncation is **surfaced** (ISSUE 6 satellite): any pack that drops
values bumps the process-global ``pipeline.pack.truncated_values`` /
``pipeline.pack.truncated_rows`` counters and logs a rate-limited
WARNING, so existing ``pack_flat`` users learn they are losing data
instead of discovering it in eval metrics.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from ..data.row_block import RowBlock
from ..utils.logging import IdOverflowError, log_warning
from ..utils.metrics import metrics

__all__ = ["pack_flat", "pack_ragged", "batch_slices", "ragged_slices",
           "dedup_ids", "PackStats", "IdOverflowError"]


@dataclass
class PackStats:
    rows: int = 0
    padded_rows: int = 0
    truncated_values: int = 0
    truncated_rows: int = 0
    # padding-ratio accounting (padded_nnz / true_nnz is the headline
    # padding tax): true_nnz = values the data actually holds, padded_nnz
    # = values the dense math reduces over (nnz_cap per flat batch; true
    # nnz per ragged batch — that is the whole point)
    true_nnz: int = 0
    padded_nnz: int = 0

    @property
    def padding_ratio(self) -> float:
        return self.padded_nnz / self.true_nnz if self.true_nnz else 1.0


_trunc_warn_lock = threading.Lock()
_trunc_warn_last = [0.0]
_TRUNC_WARN_EVERY_S = 60.0


def _note_truncation(values: int, rows: int, where: str) -> None:
    """Satellite fix for silent ``pack_flat`` truncation: bump the
    process-global counters and WARN (at most once per minute — packing
    runs per batch on the hot path)."""
    if values <= 0:
        return
    metrics.counter("pipeline.pack.truncated_values").add(values)
    metrics.counter("pipeline.pack.truncated_rows").add(rows)
    now = time.monotonic()
    with _trunc_warn_lock:
        fire = now - _trunc_warn_last[0] >= _TRUNC_WARN_EVERY_S
        if fire:
            _trunc_warn_last[0] = now
    if fire:
        log_warning(
            "%s dropped %d value(s) across %d row(s) that overflowed the "
            "batch capacity — data is being truncated; raise nnz_cap/k_cap "
            "or switch to the ragged path (pack_ragged / ragged ops), "
            "which never truncates (total drops: see "
            "pipeline.pack.truncated_values)", where, values, rows)


def _ids32(idx: np.ndarray, id_mod: int) -> np.ndarray:
    """uint64 feature ids → int32 device ids.  ``id_mod`` > 0 = feature
    hashing (documented remap); otherwise ids beyond int32 raise instead of
    silently wrapping negative (VERDICT r1 #5; reference keeps uint64 ids
    first-class, `src/data.cc:131-147`)."""
    if id_mod:
        return (idx.astype(np.uint64) % np.uint64(id_mod)).astype(np.int32)
    if len(idx) and int(idx.max()) > np.iinfo(np.int32).max:
        raise IdOverflowError(
            f"feature id {int(idx.max())} > 2^31-1 — pass id_mod (feature "
            f"hashing) or keep ids below int32 range")
    return idx.astype(np.int32)


def _waterfill(counts: np.ndarray, cap: int) -> np.ndarray:
    """keep[i] = min(counts[i], t) + at most 1, chosen so keep.sum() == cap
    exactly (when counts.sum() >= cap) with the fewest values dropped."""
    counts = counts.astype(np.int64)
    if counts.sum() <= cap:
        return counts
    order = np.argsort(counts)
    sorted_counts = counts[order]
    n = len(counts)
    # prefix[i] = sum of the i smallest counts
    prefix = np.concatenate([[0], np.cumsum(sorted_counts)])
    # with level t, usage = prefix[k] + (n - k) * t where k = #counts <= t;
    # scan candidate levels from the sorted values
    t = 0
    for k in range(n):
        remaining = n - k
        # max level if all rows >= this one are capped equally
        level = (cap - prefix[k]) // remaining
        if level <= sorted_counts[k]:
            t = max(t, level)
            break
        t = sorted_counts[k]
    keep = np.minimum(counts, t)
    leftover = cap - int(keep.sum())
    if leftover > 0:
        # hand spare slots to the rows still truncated, largest first
        cand = np.argsort(-(counts - keep))
        for i in cand[:leftover]:
            if counts[i] > keep[i]:
                keep[i] += 1
    return keep


def batch_slices(block: RowBlock, batch_rows: int) -> Iterator[RowBlock]:
    """Split a RowBlock into consecutive ≤batch_rows slices (O(1) views)."""
    for start in range(0, block.size, batch_rows):
        yield block.slice(start, min(start + batch_rows, block.size))


def pack_flat(block: RowBlock, batch_rows: int, nnz_cap: int,
              stats: Optional[PackStats] = None,
              id_mod: int = 0,
              want_segments: bool = True,
              want_fields: bool = False) -> Dict[str, np.ndarray]:
    """Flat-CSR fixed-shape batch; ``block.size`` must be ≤ batch_rows.

    ``want_segments=False`` skips materialising the per-value ``segments``
    array (the largest write in the pack) — the fused transfer path
    reconstructs segments on device from ``row_ptr``, so building them on
    host would be dead work.

    ``want_fields=True`` emits the libfm per-value field ids (int32, padding
    0) parallel to ``ids`` — the FFM model's third batch array (reference
    carries them the same way, `data.h:168`).  The source block must carry
    fields (libfm format)."""
    n = block.size
    assert n <= batch_rows, (n, batch_rows)
    if want_fields and block.fields is None:
        raise ValueError(
            "want_fields=True but the source RowBlock has no fields — "
            "parse with format='libfm'")
    offsets = block.offsets.astype(np.int64)
    rel = offsets - offsets[0]
    counts = np.diff(rel)
    total = int(rel[-1])

    ids = np.zeros(nnz_cap, np.int32)
    vals = np.zeros(nnz_cap, np.float32)
    segments = (np.full(nnz_cap, batch_rows, np.int32)  # padding → scratch
                if want_segments else None)
    fields = np.zeros(nnz_cap, np.int32) if want_fields else None
    row_ptr = np.empty(batch_rows + 1, np.int32)

    truncated = 0
    if total <= nnz_cap:
        take = total
        src_idx = slice(int(offsets[0]), int(offsets[0]) + take)
        ids[:take] = _ids32(block.indices[src_idx], id_mod)
        if block.values is not None:
            vals[:take] = block.values[src_idx]
        else:
            vals[:take] = 1.0
        if want_segments:
            segments[:take] = np.repeat(np.arange(n, dtype=np.int32), counts)
        if want_fields:
            fields[:take] = block.fields[src_idx]
        row_ptr[:n + 1] = rel
        row_ptr[n + 1:] = take
    else:
        # per-row truncation by water-filling: find the largest level t such
        # that sum(min(counts, t)) <= nnz_cap, then hand the remaining slots
        # one-by-one to the longest rows — short rows keep everything and
        # only the minimum number of values is dropped
        keep = _waterfill(counts, nnz_cap)
        trunc_rows = int(np.count_nonzero(keep < counts))
        pos = 0
        for r in range(n):
            k = int(keep[r])
            b = int(offsets[r])
            ids[pos:pos + k] = _ids32(block.indices[b:b + k], id_mod)
            if block.values is not None:
                vals[pos:pos + k] = block.values[b:b + k]
            else:
                vals[pos:pos + k] = 1.0
            if want_segments:
                segments[pos:pos + k] = r
            if want_fields:
                fields[pos:pos + k] = block.fields[b:b + k]
            pos += k
        truncated = total - pos
        _note_truncation(truncated, trunc_rows, "pack_flat")
        row_ptr[0] = 0
        row_ptr[1:n + 1] = np.cumsum(keep)
        row_ptr[n + 1:] = pos

    labels = np.zeros(batch_rows, np.float32)
    weights = np.zeros(batch_rows, np.float32)  # padding rows weigh 0
    labels[:n] = block.labels
    weights[:n] = (block.weights if block.weights is not None
                   else np.ones(n, np.float32))
    if stats is not None:
        stats.rows += n
        stats.padded_rows += batch_rows - n
        stats.truncated_values += truncated
        if truncated:
            stats.truncated_rows += trunc_rows
        stats.true_nnz += total - truncated
        stats.padded_nnz += nnz_cap
    out = {"ids": ids, "vals": vals, "row_ptr": row_ptr,
           "labels": labels, "weights": weights}
    if want_segments:
        out["segments"] = segments
    if want_fields:
        out["fields"] = fields
    return out


# ---------------------------------------------------------------------------
# ragged packing: capacity buffers + nnz_used prefix, never truncates
# ---------------------------------------------------------------------------

def ragged_slices(block: RowBlock, batch_rows: int,
                  nnz_cap: int) -> Iterator[RowBlock]:
    """Split a RowBlock into consecutive slices cut by **cumulative true
    nnz** against ``nnz_cap`` (and rows against ``batch_rows``) — the
    ragged twin of :func:`batch_slices`, whose cut points depend only on
    the row count.  O(1) views; a single row whose nnz exceeds
    ``nnz_cap`` raises ``ValueError`` (the ragged contract is *never
    truncate* — rows that would overflow start the next batch, and a row
    that cannot fit any batch is a config error, not data loss)."""
    offsets = block.offsets.astype(np.int64)
    rel = offsets - offsets[0]
    start = 0
    while start < block.size:
        # largest end with rel[end] - rel[start] <= nnz_cap
        end = int(np.searchsorted(rel, rel[start] + nnz_cap,
                                  side="right")) - 1
        end = min(end, start + batch_rows, block.size)
        if end <= start:
            raise ValueError(
                f"row {start} holds {int(rel[start + 1] - rel[start])} "
                f"values > nnz_cap={nnz_cap}; the ragged path never "
                f"truncates — raise the capacity")
        yield block.slice(start, end)
        start = end


def dedup_ids(ids: np.ndarray, nnz_used: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Dedup a ragged batch's live id prefix for the sharded-embedding
    wire: returns ``(uniq, pos)`` where ``uniq`` is the sorted unique
    int64 id set of ``ids[:nnz_used]`` and ``pos`` (int32, ``nnz_used``
    long) remaps each live entry into ``uniq``-space
    (``uniq[pos[i]] == ids[i]``).  A batch that references a hot id a
    thousand times then ships (and caches) its row once; the pooled
    gather runs over the compacted row matrix with ``pos`` as the id
    array.  Tail entries past ``nnz_used`` are garbage by the ragged
    contract and never inspected."""
    live = np.asarray(ids[:int(nnz_used)], dtype=np.int64)
    uniq, pos = np.unique(live, return_inverse=True)
    return uniq, pos.astype(np.int32, copy=False)


def pack_ragged(block: RowBlock, batch_rows: int, nnz_cap: int,
                stats: Optional[PackStats] = None,
                id_mod: int = 0,
                want_fields: bool = False) -> Dict[str, np.ndarray]:
    """Flat-CSR **capacity** batch: same keys/shapes as
    :func:`pack_flat` (so every downstream shape contract holds) plus
    the ``nnz_used`` / ``rows_used`` int32 prefix words, with the
    nnz-sized arrays allocated ``np.empty`` and written only up to
    ``nnz_used`` — no tail zeroing, which on wide capacities is most of
    ``pack_flat``'s host wall.  Entries past ``nnz_used`` are
    **garbage by contract**; consumers must mask (``ops.ragged_csr``)
    or slice.  Row-sized arrays (``row_ptr/labels/weights``) do get
    clean tails — they are small and a zero tail removes the NaN
    footgun for consumers that reduce over all rows.

    Raises instead of truncating when the block exceeds either capacity
    (cut upstream with :func:`ragged_slices`)."""
    n = block.size
    if n > batch_rows:
        raise ValueError(f"block rows {n} > batch_rows {batch_rows}")
    if want_fields and block.fields is None:
        raise ValueError(
            "want_fields=True but the source RowBlock has no fields — "
            "parse with format='libfm'")
    offsets = block.offsets.astype(np.int64)
    rel = offsets - offsets[0]
    total = int(rel[-1])
    if total > nnz_cap:
        raise ValueError(
            f"block nnz {total} > nnz_cap {nnz_cap}; the ragged path "
            f"never truncates — cut with ragged_slices")

    ids = np.empty(nnz_cap, np.int32)        # garbage tails by contract
    vals = np.empty(nnz_cap, np.float32)
    segments = np.empty(nnz_cap, np.int32)
    fields = np.empty(nnz_cap, np.int32) if want_fields else None
    src_idx = slice(int(offsets[0]), int(offsets[0]) + total)
    ids[:total] = _ids32(block.indices[src_idx], id_mod)
    if block.values is not None:
        vals[:total] = block.values[src_idx]
    else:
        vals[:total] = 1.0
    counts = np.diff(rel)
    segments[:total] = np.repeat(np.arange(n, dtype=np.int32), counts)
    if want_fields:
        fields[:total] = block.fields[src_idx]

    row_ptr = np.empty(batch_rows + 1, np.int32)
    row_ptr[:n + 1] = rel
    row_ptr[n + 1:] = total
    labels = np.zeros(batch_rows, np.float32)
    weights = np.zeros(batch_rows, np.float32)
    labels[:n] = block.labels
    weights[:n] = (block.weights if block.weights is not None
                   else np.ones(n, np.float32))

    if stats is not None:
        stats.rows += n
        stats.padded_rows += batch_rows - n
        stats.true_nnz += total
        stats.padded_nnz += total     # ragged math reduces true nnz only
    out = {"ids": ids, "vals": vals, "segments": segments,
           "row_ptr": row_ptr, "labels": labels, "weights": weights,
           "nnz_used": np.int32(total), "rows_used": np.int32(n)}
    if want_fields:
        out["fields"] = fields
    return out
