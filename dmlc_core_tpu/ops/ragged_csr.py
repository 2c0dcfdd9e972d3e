"""Ragged CSR ops: static-capacity buffers, runtime ``nnz_used`` — no padding tax.

The padded path (:mod:`.csr` + ``pipeline.packing.pack_flat``) buys
XLA's one-compile-per-shape invariant by zero-filling every batch to
``nnz_cap`` and pointing the padding at a scratch row.  That costs host
cycles (zeroing the tail), H2D bytes (shipping it), and device FLOPs
(reducing it).  Following the Ragged Paged Attention approach on TPU
(PAPERS.md: arxiv 2604.15464), these ops keep the **capacity static**
(one compile per capacity, not per shape) while the **fill level is a
runtime scalar**: batches arrive as ``(ids[cap], vals[cap],
segments[cap], nnz_used)`` where entries past ``nnz_used`` are
*arbitrary garbage* — never read, never zeroed, never shipped with
meaning.  A single jitted entry point therefore serves any fill level,
and the batcher can pack by true nnz instead of bucket ceilings.

Two engines, same semantics:

* **xla** — mask the tail (``vals → 0``, ``segments → scratch row``,
  ``ids → 0``) and run the exact :mod:`.csr` segment-sum.  Because the
  live entries contribute in identical order and the masked tail adds
  literal ``0.0`` to the scratch row (sliced off), the result is
  **bit-identical** to ``pack_flat`` + padded ops — the equivalence
  sweep in ``tests/test_ragged.py`` asserts ``array_equal``, not just
  allclose.  The tail is still *reduced* (full-capacity FLOPs), so this
  engine retires the host/wire tax but not the device FLOPs.
* **pallas** — a DMA-ring gather kernel over the flat layout whose
  per-entry work is predicated on ``i < nnz_used``: tail entries issue
  **no DMA and no FLOP**, so the device cost tracks true nnz.  Chunked
  pallas_calls keep the ids/segments/vals scalar prefetch under an SMEM
  budget (``_SMEM_SCALARS_CAP``); partial
  per-chunk accumulators are summed outside, so the pallas result is
  allclose (not bit-identical — different summation order).

Engine selection is a stated function of backend and shape: ``auto``
resolves to pallas on a TPU backend when the table width is a multiple
of the 128-lane tile (:func:`mosaic_row_dma_ok` — Mosaic refuses the
per-row DMA at any other width) and to xla otherwise;
``DMLC_RAGGED_ENGINE=xla|pallas`` pins globally.  A pinned or explicit
``pallas`` is handed to the compiler as is: at a width Mosaic refuses,
its error reaches the caller — nothing downgrades quietly.  The
kernel's profitability against XLA's native gather is **not measured on
this round's chip** (ROADMAP S5).  On non-TPU backends the kernels run
``interpret=True`` (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_segment_sum", "ragged_dense_matvec", "ragged_embed_sum",
           "ragged_embed_grad", "ragged_fm_pairwise", "mask_ragged",
           "mask_batch"]

# DMA ring depth (a single ~512 B row DMA in flight is latency-bound) and
# the per-operand cap on scalar-prefetched entries: three operands (ids,
# segments, vals) share the 1 MB of SMEM a v5e core has, 384 KB at this
# cap; a longer batch is split into chunks of this many entries.
_SLOTS = 8
_SMEM_SCALARS_CAP = 32768

# f32 lane tile of the TPU's HBM/VMEM layout.  The kernel fetches one table
# row per DMA (``table_ref.at[pl.ds(idx, 1), :]``), and Mosaic refuses a
# slice of a tiled HBM ref whose minor dimension is not a whole number of
# lane tiles ("Slice shape along dimension 1 must be aligned to tiling
# (128)") — tests/test_tpu_compile.py compiles both sides of this rule for
# a described v5e.
_LANES = 128


def mosaic_row_dma_ok(D: int) -> bool:
    """The engine rule's shape half: the per-row DMA kernel lowers on Mosaic
    only when the embedding width is a multiple of the 128-lane tile.
    ``engine="auto"`` sends every other width to XLA; an explicit
    ``engine="pallas"`` (or the env pin) at such a width is passed to the
    compiler, whose error reaches the caller."""
    return D % _LANES == 0


# ---------------------------------------------------------------------------
# masking: the semantic core — everything past nnz_used is dead
# ---------------------------------------------------------------------------

def mask_ragged(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                nnz_used: jax.Array, num_rows: int):
    """Sanitize a ragged batch's value arrays: entries at ``i >=
    nnz_used`` become ``(id 0, val 0.0, segment num_rows)`` — exactly the
    padding convention of ``pack_flat``, so any padded-path consumer
    (``ops.csr``, every zoo model's flat forward) gets bit-identical
    inputs.  ``nnz_used`` may be a python int or a traced scalar."""
    live = jnp.arange(ids.shape[0], dtype=jnp.int32) < nnz_used
    return (jnp.where(live, ids, 0),
            jnp.where(live, vals, jnp.float32(0.0)),
            jnp.where(live, segments, jnp.int32(num_rows)))


def mask_batch(batch: dict) -> dict:
    """Ragged device batch → padded-convention batch for the zoo models.

    Consumes the ``pack_ragged`` / ragged-engine layout (``ids/vals/
    segments[cap]`` with garbage tails + ``nnz_used``/``rows_used``
    scalars) and returns a dict every flat ``model.forward`` accepts
    unchanged: tail values masked to the scratch row, tail rows' weights
    masked to 0.  Scalar words are dropped from the result (models
    iterate batch keys nowhere, but keeping the contract identical to
    ``pack_flat`` output costs nothing and documents itself)."""
    out = dict(batch)
    nnz_used = out.pop("nnz_used")
    rows_used = out.pop("rows_used", None)
    rows_cap = batch["labels"].shape[0]
    out["ids"], out["vals"], out["segments"] = mask_ragged(
        batch["ids"], batch["vals"], batch["segments"], nnz_used, rows_cap)
    if rows_used is not None:
        rlive = jnp.arange(rows_cap, dtype=jnp.int32) < rows_used
        out["weights"] = jnp.where(rlive, batch["weights"],
                                   jnp.float32(0.0))
        out["labels"] = jnp.where(rlive, batch["labels"], jnp.float32(0.0))
    return out


# ---------------------------------------------------------------------------
# XLA engine: masked tails + the reference segment-sum (bit-identical)
# ---------------------------------------------------------------------------

def ragged_segment_sum(data: jax.Array, segments: jax.Array,
                       nnz_used: jax.Array, num_rows: int) -> jax.Array:
    """Per-row sum of ``data[:nnz_used]`` grouped by ``segments``;
    ``data`` is [cap] or [cap, d], tails are garbage-tolerant."""
    live = jnp.arange(segments.shape[0], dtype=jnp.int32) < nnz_used
    segs = jnp.where(live, segments, jnp.int32(num_rows))
    zero = jnp.zeros((), data.dtype)
    d = jnp.where(live if data.ndim == 1 else live[:, None], data, zero)
    return jax.ops.segment_sum(d, segs,
                               num_segments=num_rows + 1)[:num_rows]


def ragged_dense_matvec(ids: jax.Array, vals: jax.Array,
                        segments: jax.Array, nnz_used: jax.Array,
                        w: jax.Array, num_rows: int) -> jax.Array:
    """Ragged twin of :func:`.csr.csr_dense_matvec` (always XLA: the
    gather is one f32 per entry — there is no DMA ring to win with)."""
    ids, vals, segments = mask_ragged(ids, vals, segments, nnz_used,
                                      num_rows)
    picked = w[ids] * vals
    return jax.ops.segment_sum(picked, segments,
                               num_segments=num_rows + 1)[:num_rows]


def _embed_sum_xla(ids, vals, segments, nnz_used, table, num_rows):
    ids, vals, segments = mask_ragged(ids, vals, segments, nnz_used,
                                      num_rows)
    gathered = table[ids] * vals[:, None]
    return jax.ops.segment_sum(gathered, segments,
                               num_segments=num_rows + 1)[:num_rows]


def _fm_pairwise_xla(ids, vals, segments, nnz_used, table, num_rows):
    ids, vals, segments = mask_ragged(ids, vals, segments, nnz_used,
                                      num_rows)
    vx = table[ids] * vals[:, None]
    s1 = jax.ops.segment_sum(vx, segments,
                             num_segments=num_rows + 1)[:num_rows]
    s2 = jax.ops.segment_sum(vx * vx, segments,
                             num_segments=num_rows + 1)[:num_rows]
    return 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)


# ---------------------------------------------------------------------------
# Pallas engine: predicated DMA ring over the flat layout
# ---------------------------------------------------------------------------

def _ragged_gather_kernel(nnz_ref, ids_ref, segs_ref, vals_ref, table_ref,
                          out1_ref, out2_ref, buf, sems, *, CHUNK: int,
                          D: int, fm: bool):
    """Grid step j owns entries [j·CHUNK, (j+1)·CHUNK) of the flat batch.

    Every DMA start and every accumulate is predicated on the entry
    index being below ``nnz_used`` — the ragged tail costs neither HBM
    traffic nor FLOPs.  Start/wait share the same monotone predicate, so
    no started copy is left un-waited.  Accumulation target is the
    (rows+1, D) block resident across the whole sequential grid
    (constant index map); the scratch row absorbs nothing here — tail
    entries are simply skipped — but keeping rows+1 preserves the
    padded-layout slice convention for the caller."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out1_ref[:] = jnp.zeros_like(out1_ref)
        if fm:
            out2_ref[:] = jnp.zeros_like(out2_ref)

    base = j * CHUNK
    nnz = nnz_ref[0]

    def cp(i, slot):
        idx = ids_ref[base + i]
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(idx, 1), :], buf.at[slot], sems.at[slot])

    for s in range(min(_SLOTS - 1, CHUNK)):   # prologue: fill the ring
        @pl.when(base + s < nnz)
        def _start(s=s):
            cp(s, s).start()

    def body(i, _):
        slot = jax.lax.rem(i, _SLOTS)
        kn = i + _SLOTS - 1

        @pl.when(jnp.logical_and(kn < CHUNK, base + kn < nnz))
        def _start_ahead():
            cp(kn, jax.lax.rem(kn, _SLOTS)).start()

        @pl.when(base + i < nnz)
        def _accumulate():
            cp(i, slot).wait()
            g = buf[slot]                     # (1, D)
            v = vals_ref[base + i]
            seg = segs_ref[base + i]
            out1_ref[pl.ds(seg, 1), :] += g * v
            if fm:
                out2_ref[pl.ds(seg, 1), :] += (g * g) * (v * v)
        return 0

    jax.lax.fori_loop(0, CHUNK, body, 0)


def _gather_pallas_one(ids, segs, vals, nnz_used, table, num_rows: int,
                       fm: bool, interpret: bool):
    cap = ids.shape[0]
    D = table.shape[1]
    chunk = min(cap, 512)
    shape = jax.ShapeDtypeStruct((num_rows + 1, D), jnp.float32)
    spec = pl.BlockSpec((num_rows + 1, D), lambda j, *pref: (0, 0))
    out_shapes = [shape, shape] if fm else shape
    out_specs = [spec, spec] if fm else spec
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # nnz_used, ids, segments, vals → SMEM
        grid=(pl.cdiv(cap, chunk),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # table in HBM
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, 1, D), jnp.float32),
            pltpu.SemaphoreType.DMA((_SLOTS,)),
        ],
    )
    kernel = functools.partial(_ragged_gather_kernel, CHUNK=chunk, D=D,
                               fm=fm)
    if not fm:
        def kernel(nnz_ref, ids_ref, segs_ref, vals_ref, table_ref,
                   out1_ref, buf, sems):
            _ragged_gather_kernel(nnz_ref, ids_ref, segs_ref, vals_ref,
                                  table_ref, out1_ref, None, buf, sems,
                                  CHUNK=chunk, D=D, fm=False)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shapes,
        interpret=interpret,
    )(jnp.asarray(nnz_used, jnp.int32).reshape(1),
      ids.astype(jnp.int32), segs.astype(jnp.int32),
      vals.astype(jnp.float32), table)
    return out if fm else (out,)


@functools.partial(jax.jit,
                   static_argnames=("num_rows", "fm", "interpret"))
def _gather_pallas(ids, segs, vals, nnz_used, table, num_rows: int,
                   fm: bool = False, interpret: bool = False):
    """Chunk the flat batch so each pallas_call's 3 scalar-prefetch
    operands stay under the SMEM budget; per-chunk partial accumulators
    sum outside (chunk count is static — jit-stable)."""
    cap = ids.shape[0]
    if cap <= _SMEM_SCALARS_CAP:
        parts = [_gather_pallas_one(ids, segs, vals, nnz_used, table,
                                    num_rows, fm, interpret)]
    else:
        step = _SMEM_SCALARS_CAP
        parts = []
        for s in range(0, cap, step):
            local = jnp.clip(jnp.asarray(nnz_used, jnp.int32) - s, 0,
                             min(step, cap - s))
            parts.append(_gather_pallas_one(
                ids[s:s + step], segs[s:s + step], vals[s:s + step],
                local, table, num_rows, fm, interpret))
    summed = [sum(p[k] for p in parts) for k in range(2 if fm else 1)]
    return summed if fm else summed[0]


def _resolve_engine(engine: str, D: int) -> str:
    from ..utils.parameter import get_env
    pinned = get_env("DMLC_RAGGED_ENGINE", None)
    if pinned:
        engine = pinned
    if engine == "auto":
        return ("pallas" if jax.default_backend() == "tpu"
                and mosaic_row_dma_ok(D) else "xla")
    if engine not in ("xla", "pallas"):
        raise ValueError(f"unknown ragged engine {engine!r}")
    return engine


# ---------------------------------------------------------------------------
# dispatching entry points (the public trio, mirroring ops.csr)
# ---------------------------------------------------------------------------

def ragged_embed_sum(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                     nnz_used: jax.Array, table: jax.Array, num_rows: int,
                     engine: str = "auto") -> jax.Array:
    """Ragged twin of :func:`.csr.csr_embed_sum`: out[r, :] = Σ vals[i] ·
    table[ids[i], :] over live entries with segments[i] == r."""
    engine = _resolve_engine(engine, table.shape[1])
    if engine == "xla":
        return _embed_sum_xla(ids, vals, segments, nnz_used, table,
                              num_rows)
    out = _gather_pallas(ids, segments, vals, nnz_used, table, num_rows,
                         fm=False,
                         interpret=jax.default_backend() != "tpu")
    return out[:num_rows]


def ragged_embed_grad(ids: jax.Array, vals: jax.Array, segments: jax.Array,
                      nnz_used: jax.Array, g_rows: jax.Array,
                      num_table_rows: int) -> jax.Array:
    """Backward twin of :func:`ragged_embed_sum` w.r.t. the table: given
    upstream gradients ``g_rows[num_rows, dim]`` for the pooled output,
    return ``grad[num_table_rows, dim]`` with ``grad[ids[i]] += vals[i] ·
    g_rows[segments[i]]`` summed over live entries.  XLA scatter-add only
    — the sparse-update path consumes a *dense over the referenced rows*
    gradient and re-sparsifies by unique id, so a predicated Pallas
    variant buys nothing here.  Tail entries are masked to ``(id 0, val
    0.0)`` and so contribute exact ``0.0`` to row 0: the result is a pure
    function of the live entries, whatever garbage sits past
    ``nnz_used``."""
    num_rows = g_rows.shape[0]
    ids, vals, segments = mask_ragged(ids, vals, segments, nnz_used,
                                      num_rows)
    # masked segments point at num_rows (one past the end of g_rows);
    # clamp for the gather — the masked val 0.0 kills the contribution
    seg = jnp.minimum(segments, jnp.int32(num_rows - 1))
    contrib = g_rows[seg] * vals[:, None]
    out = jnp.zeros((num_table_rows, g_rows.shape[1]), g_rows.dtype)
    return out.at[ids].add(contrib)


def ragged_fm_pairwise(ids: jax.Array, vals: jax.Array,
                       segments: jax.Array, nnz_used: jax.Array,
                       table: jax.Array, num_rows: int,
                       engine: str = "auto") -> jax.Array:
    """Ragged twin of :func:`.csr.fm_pairwise` — both FM reductions from
    one pass over the gathered rows (pallas) or two fused segment-sums
    (xla)."""
    engine = _resolve_engine(engine, table.shape[1])
    if engine == "xla":
        return _fm_pairwise_xla(ids, vals, segments, nnz_used, table,
                                num_rows)
    s1, s2 = _gather_pallas(ids, segments, vals, nnz_used, table,
                            num_rows, fm=True,
                            interpret=jax.default_backend() != "tpu")
    s1, s2 = s1[:num_rows], s2[:num_rows]
    return 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)
