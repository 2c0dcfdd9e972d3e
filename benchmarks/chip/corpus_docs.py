"""Token documents as libsvm text, written with vectorised numpy from a
seed: one row = one document, ``0`` for a label and then its tokens in
order, value-less (``<id> <id> ...``; the parser reads a value of 1).

Every ``len(doc_lengths)`` consecutive documents have the lengths of the
template, in an order drawn from the seed, so a batch of that many rows
always holds the same number of tokens.  Tokens are Zipf over the
vocabulary slice (``P(id) ~ 1 / (id + 1)^s``), with repeats as they come.

The generator keeps its own truth — every token of every document — so the
plain reference never goes through the program's parser.
"""

from __future__ import annotations

import dataclasses

import numpy as np

POW10 = 10 ** np.arange(9, dtype=np.int64)


@dataclasses.dataclass
class Corpus:
    """The generator's own record of what it wrote."""
    lengths: np.ndarray        # [docs] int64
    tokens: np.ndarray         # [sum(lengths)] int32, document after document
    nbytes: int = 0

    @property
    def rows(self) -> int:
        return len(self.lengths)

    @property
    def nnz(self) -> int:
        return len(self.tokens)

    def docs(self, lo: int, hi: int):
        """Documents ``[lo, hi)`` as (tokens, row_ptr) of one packed
        stream."""
        start = np.concatenate(([0], np.cumsum(self.lengths)))
        row_ptr = start[lo:hi + 1] - start[lo]
        return self.tokens[start[lo]:start[hi]], row_ptr


def draw(shape: dict, docs: int, seed: int) -> Corpus:
    """``shape``: ``doc_lengths`` (the template), ``zipf_exponent``,
    ``categorical_vocab`` (one entry: the vocabulary slice)."""
    template = np.asarray(shape["doc_lengths"], np.int64)
    vocab = int(shape["categorical_vocab"][0])
    rng = np.random.default_rng([int(seed), 0xD0C5])
    groups = -(-docs // len(template))
    lengths = np.concatenate(
        [rng.permutation(template) for _ in range(groups)])[:docs]
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) \
        ** float(shape["zipf_exponent"])
    cdf = np.cumsum(p / p.sum())
    tokens = np.minimum(np.searchsorted(cdf, rng.random(int(lengths.sum()))),
                        vocab - 1).astype(np.int32)
    return Corpus(lengths, tokens)


def generate(path, shape: dict, docs: int, seed: int) -> Corpus:
    """Draw the corpus and, unless ``path`` is None, write it."""
    out = draw(shape, docs, seed)
    if path is not None:
        buf = _format(out.tokens, out.lengths)
        with open(path, "wb") as f:
            f.write(buf.data)
        out.nbytes = len(buf)
    return out


def _format(tokens: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """``0( <id>)*\\n`` for every document, as bytes (digit planes written
    into one buffer, as ``corpus._format`` does)."""
    tid = tokens.astype(np.int64)
    row_of = np.repeat(np.arange(len(lengths), dtype=np.int64), lengths)
    digits = 1 + (tid[:, None] >= POW10[1:]).sum(axis=1)
    tok_len = digits + 1                               # ' ' id
    row_len = 2 + np.bincount(row_of, weights=tok_len,
                              minlength=len(lengths)).astype(np.int64)
    row_start = np.cumsum(row_len) - row_len
    start = (np.cumsum(tok_len) - tok_len) + 2 * row_of + 1
    buf = np.empty(int(row_len.sum()), np.uint8)
    buf[row_start] = 48
    buf[row_start + row_len - 1] = 10
    buf[start] = 32
    for k in range(int(digits.max())):
        m = digits > k
        buf[start[m] + digits[m] - k] = 48 + (tid[m] // POW10[k]) % 10
    return buf
