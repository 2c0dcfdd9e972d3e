"""Criteo-shaped libsvm text, written with vectorised numpy from a seed.

One row = one ad impression of the Criteo Display Advertising Challenge:
13 integer fields and 26 categorical fields, each present with probability
``1 - missing``.  A present categorical field gives one token ``id:1`` whose
id is that field's base plus a Zipf-ish rank inside its vocabulary
(``rank = floor(V**u)``, the arithmetic of ``benchmarks/realdata.py``
``_zipf_ids``); a present integer field ``f`` gives ``f:d.dd``, the value
``log1p(count)`` to two decimals of a log-normal count (the usual transform
of Criteo's integer features; raw counts would put FM logits in the
hundreds).  Ids are written raw: the loader's ``id_mod`` folds them.

The technique (digit planes written into one uint8 buffer) is
``chip_smoke.gen_corpus``'s.  The generator keeps its own truth — ids,
value codes and labels of every row — so the plain reference never goes
through the program's parser.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

POW10 = 10 ** np.arange(9, dtype=np.int64)
ONE = 100                  # value code of a categorical token: 1.00


@dataclasses.dataclass
class Corpus:
    """The generator's own record of what it wrote."""
    rows: int
    fields: int
    ids: np.ndarray        # [rows, fields] int32 raw ids (before id_mod)
    codes: np.ndarray      # [rows, fields] int16: value * 100
    present: np.ndarray    # [rows, fields] bool
    labels: np.ndarray     # [rows] uint8
    nbytes: int = 0

    @property
    def nnz(self) -> int:
        return int(self.present.sum())

    def rows_csr(self, lo: int, hi: int, id_mod: int):
        """Rows ``[lo, hi)`` as (ids, vals, row_ptr, labels): what a correct
        parse → fold gives, in file order."""
        m = self.present[lo:hi]
        ids = (self.ids[lo:hi][m].astype(np.int64) % id_mod).astype(np.int32)
        vals = self.codes[lo:hi][m].astype(np.float32) / np.float32(100.0)
        row_ptr = np.concatenate(([0], np.cumsum(m.sum(axis=1)))).astype(
            np.int64)
        return ids, vals, row_ptr, self.labels[lo:hi].astype(np.float32)

    def rows_padded(self, lo: int, hi: int, id_mod: int):
        """Rows ``[lo, hi)`` as dense ``[n, fields]`` (ids, vals) with absent
        fields at id 0 / value 0 — the shape the plain references take."""
        m = self.present[lo:hi]
        ids = np.where(m, self.ids[lo:hi].astype(np.int64) % id_mod, 0)
        vals = np.where(m, self.codes[lo:hi].astype(np.float32)
                        / np.float32(100.0), np.float32(0.0))
        return (ids.astype(np.int32), vals.astype(np.float32),
                self.labels[lo:hi].astype(np.float32))


def field_layout(shape: dict):
    """(bases, sizes) over all fields: integer field ``f`` owns id ``f``,
    categorical fields follow with their vocabularies back to back."""
    n_int = int(shape["integer_fields"])
    sizes = np.concatenate([np.ones(n_int, np.int64),
                            np.asarray(shape["categorical_vocab"], np.int64)])
    bases = np.concatenate(([0], np.cumsum(sizes)))[:-1]
    return bases, sizes


def generate(path, shape: dict, seed: int, chunk_rows: int = 32768,
             threads: int = 8) -> Corpus:
    """Write ``shape['rows']`` rows to ``path`` (``None``: draw the rows and
    write nothing) and return the truth.  Chunks
    are drawn from ``(seed, chunk index)`` on a few threads (numpy releases
    the interpreter lock) and written in order, so the file depends on the
    seed alone."""
    from concurrent.futures import ThreadPoolExecutor
    rows = int(shape["rows"])
    nf = len(field_layout(shape)[1])
    out = Corpus(rows, nf, np.empty((rows, nf), np.int32),
                 np.empty((rows, nf), np.int16),
                 np.empty((rows, nf), bool), np.empty(rows, np.uint8))
    los = list(range(0, rows, chunk_rows))

    def work(ci: int):
        lo = los[ci]
        return _chunk(shape, seed, ci, min(chunk_rows, rows - lo),
                      text=path is not None)

    with open(path or os.devnull, "wb") as f, \
            ThreadPoolExecutor(threads) as pool:
        for lo, (ids, codes, present, y, buf) in zip(
                los, pool.map(work, range(len(los)))):
            n = len(y)
            out.ids[lo:lo + n] = ids
            out.codes[lo:lo + n] = codes
            out.present[lo:lo + n] = present
            out.labels[lo:lo + n] = y
            if buf is not None:
                f.write(buf.data)
                out.nbytes += len(buf)
    return out


def _chunk(shape: dict, seed: int, ci: int, n: int, text: bool = True):
    n_int = int(shape["integer_fields"])
    bases, sizes = field_layout(shape)
    nf = len(sizes)
    rng = np.random.default_rng([int(seed), 0xC21E0, ci])
    # a fixed small teacher so labels carry a learnable signal at no cost
    teacher = np.random.default_rng([int(seed), 1]).standard_normal(
        4096).astype(np.float32)
    present = rng.random((n, nf)) >= shape["missing"]
    present[~present.any(axis=1), 0] = True    # no empty row
    ids = np.empty((n, nf), np.int64)
    ids[:, :n_int] = np.arange(n_int)
    log_sizes = np.log(sizes[n_int:].astype(np.float64))
    rank = np.floor(np.exp(rng.random((n, nf - n_int)) * log_sizes)
                    ).astype(np.int64) - 1
    ids[:, n_int:] = bases[n_int:] + np.clip(rank, 0, sizes[n_int:] - 1)
    codes = np.full((n, nf), ONE, np.int64)
    count = 1.0 + np.floor(np.exp(rng.normal(
        shape["count_log_mean"], shape["count_log_sigma"], (n, n_int))))
    codes[:, :n_int] = np.rint(100.0 * np.log1p(np.minimum(
        count, shape["count_max"]))).astype(np.int64)
    z = (teacher[ids % 4096] * present * (codes * 0.01)).sum(axis=1)
    rate = shape["label_rate"]
    y = (np.log(rate / (1 - rate)) + 0.5 * z / np.sqrt(nf)
         + rng.logistic(size=n) * 0.5) > 0
    return (ids, codes, present, y,
            _format(ids, codes, present, y, n_int) if text else None)


def _format(ids, codes, present, y, n_int: int) -> np.ndarray:
    """``<label>( <id>:<d>.<dd>| <id>:1)*\\n`` for every row, as bytes."""
    n, nf = ids.shape
    row_of = np.repeat(np.arange(n, dtype=np.int64), present.sum(axis=1))
    tid = ids[present]
    tcode = codes[present]
    cat = np.broadcast_to(np.arange(nf) >= n_int, (n, nf))[present]
    digits = 1 + (tid[:, None] >= POW10[1:]).sum(axis=1)
    tok_len = digits + np.where(cat, 3, 6)      # ' ' id ':' ('1' | 'd.dd')
    row_len = 2 + np.bincount(row_of, weights=tok_len, minlength=n
                              ).astype(np.int64)
    row_start = np.cumsum(row_len) - row_len
    # tokens lie back to back; each earlier row adds its label and newline
    start = (np.cumsum(tok_len) - tok_len) + 2 * row_of + 1
    buf = np.empty(int(row_len.sum()), np.uint8)
    buf[row_start] = 48 + y
    buf[row_start + row_len - 1] = 10
    buf[start] = 32
    for k in range(8):
        m = digits > k
        buf[start[m] + digits[m] - k] = 48 + (tid[m] // POW10[k]) % 10
    colon = start + digits + 1
    buf[colon] = 58
    buf[colon[cat] + 1] = 49
    num = ~cat
    c, v = colon[num], tcode[num]
    buf[c + 1] = 48 + v // 100
    buf[c + 2] = 46
    buf[c + 3] = 48 + (v // 10) % 10
    buf[c + 4] = 48 + v % 10
    return buf
