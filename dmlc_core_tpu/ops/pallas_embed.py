"""Pallas TPU kernel: weighted embedding-bag over row-padded sparse batches.

The hot op of the sparse model family (logreg/FM wide features,
BASELINE.json north star: stage CSR batches into HBM and consume them without
host round trips).  XLA's ``table[ids] * vals → segment_sum`` materializes a
``[nnz, D]`` gathered intermediate in HBM; this kernel streams embedding rows
HBM→VMEM with double-buffered async DMA and accumulates in registers, so the
intermediate never exists and HBM traffic drops to ~1× gather + 1× output.

Layout: ids/vals are **row-padded** ``[B, K]`` (K = max nnz/row, padding id 0
with val 0; see ``pipeline.packing.pack_rowmajor``).  The table stays in HBM
(``memory_space=ANY``) — F is typically far larger than VMEM.

Grid: one program per 8-row block (the f32 sublane tile — Mosaic rejects
1-row output blocks); ids/vals ride scalar prefetch in SMEM, and each row
runs a K-step ``fori_loop`` with 2-slot DMA double buffering
(pallas_guide.md §Async DMA / §Double Buffering / §PrefetchScalarGridSpec).
Use ``interpret=True`` for CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["embed_bag", "embed_bag_pallas", "embed_bag_reference",
           "fm_embed_terms"]

# f32 lane tile of the TPU's HBM/VMEM layout.  Both kernels fetch one
# table row per DMA (``table_ref.at[pl.ds(idx, 1), :]``), and Mosaic
# refuses a slice of a tiled HBM ref whose minor dimension is not a whole
# number of lane tiles ("Slice shape along dimension 1 must be aligned to
# tiling (128)") — tests/test_tpu_compile.py compiles both sides of this
# rule for a described v5e.
_LANES = 128


def mosaic_row_dma_ok(D: int) -> bool:
    """The engine rule's shape half: the per-row DMA kernels here and in
    :mod:`.ragged_csr` lower on Mosaic only when the embedding width is a
    multiple of the 128-lane tile.  ``engine="auto"`` sends every other
    width to XLA; an explicit ``engine="pallas"`` (or the env pin) at such
    a width is passed to the compiler, whose error reaches the caller."""
    return D % _LANES == 0


_engine_time_cache: dict = {}


def _pallas_profitable(B: int, K: int, D: int, fused: bool) -> bool:
    """Deterministic shape-based engine choice (ADVICE r3 medium): every
    host on a shared mesh must pick the SAME engine for the same jitted
    step, so the default verdict is a pure function of the call shape —
    no wall-clock probes whose outcome can differ across hosts/runs.

    Not measured on this round's chip; default XLA.  (The per-(row,k)
    512-byte DMAs are expected to be latency-bound against XLA's native
    gather; ROADMAP S5 owes the kernel one chip verdict.)  So the
    deterministic default is **always XLA**; the pallas engine stays
    available via
    ``DMLC_EMBED_ENGINE=pallas`` (pin) or ``DMLC_EMBED_AUTOTUNE=1``
    (wall-clock probe — single-host bench use only, nondeterministic
    across hosts)."""
    from ..utils.parameter import parse_lenient_bool
    if parse_lenient_bool("DMLC_EMBED_AUTOTUNE"):
        return _pallas_faster_timed(B, K, D, fused)
    return False


def _pallas_faster_timed(B: int, K: int, D: int, fused: bool) -> bool:
    """Wall-clock probe per (K, D, fused) — only behind
    DMLC_EMBED_AUTOTUNE=1 (single-host bench use; nondeterministic across
    hosts, so never the default on a shared mesh)."""
    key = (K, D, fused)
    hit = _engine_time_cache.get(key)
    if hit is not None:
        return hit
    import time as _time

    import numpy as _np
    b = min(B, 1024)
    rng = _np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 4096, (b, K)), jnp.int32)
    vals = jnp.ones((b, K), jnp.float32)
    table = jnp.asarray(rng.standard_normal((4096, D)), jnp.float32)

    def timed(fn) -> float:
        jax.block_until_ready(fn(ids, vals, table))   # compile + warm
        t0 = _time.perf_counter()
        for _ in range(3):
            out = fn(ids, vals, table)
        jax.block_until_ready(out)
        return _time.perf_counter() - t0

    try:
        if fused:
            t_pal = timed(fm_terms_pallas)
            t_xla = timed(jax.jit(lambda i, v, t: (
                jnp.einsum("bk,bkd->bd", v, t[i]),
                jnp.einsum("bk,bkd->bd", v * v, t[i] * t[i]))))
        else:
            t_pal = timed(embed_bag_pallas)
            t_xla = timed(jax.jit(embed_bag_reference,
                                  static_argnames=("square",)))
        faster = t_pal < t_xla
    except Exception:  # noqa: BLE001 — timing must never break dispatch
        faster = False
    _engine_time_cache[key] = faster
    return faster


def _resolve_engine(engine: str, D: int, fused: bool = False,
                    B: int = 1024, K: int = 32) -> str:
    from ..utils.parameter import get_env
    pinned = get_env("DMLC_EMBED_ENGINE", None)
    if pinned:                       # multi-host escape hatch: pin globally
        engine = pinned
    if engine == "auto":
        if (jax.default_backend() == "tpu" and mosaic_row_dma_ok(D)
                and _pallas_profitable(B, K, D, fused)):
            return "pallas"
        return "xla"
    if engine not in ("xla", "pallas"):
        raise ValueError(f"unknown embed engine {engine!r}")
    return engine


def embed_bag(ids: jax.Array, vals: jax.Array, table: jax.Array,
              engine: str = "auto", square: bool = False) -> jax.Array:
    """Engine-dispatching weighted embedding bag over row-padded [B,K]
    batches (``pipeline.packing.pack_rowmajor``):
    ``out[b] = Σ_k vals[b,k] · f(table[ids[b,k]])`` with ``f = x²`` when
    ``square`` (the FM second-order term needs Σ v²x² — squaring the
    *gathered* rows inside the kernel, never the whole [F,D] table).

    ``engine``:
      * ``"xla"``     — gather + einsum (reference semantics, any backend)
      * ``"pallas"``  — the DMA double-buffered kernel; on non-TPU backends
        runs ``interpret=True`` (slow, for tests)
      * ``"auto"``    — xla, unless ``DMLC_EMBED_AUTOTUNE=1`` times the
        kernel faster on a TPU at a width it lowers for
        (:func:`mosaic_row_dma_ok`)

    Differentiable w.r.t. ``vals`` and ``table`` on every engine: the
    pallas forward carries a custom VJP whose backward is plain XLA
    (gather + scatter-add), since Mosaic kernels have no autodiff rules.
    """
    engine = _resolve_engine(engine, table.shape[1],
                             B=ids.shape[0], K=ids.shape[1])
    if engine == "xla":
        return embed_bag_reference(ids, vals, table, square=square)
    return _embed_bag_pallas_diff(
        ids, vals, table, square,
        interpret=jax.default_backend() != "tpu")


def fm_embed_terms(ids: jax.Array, vals: jax.Array, table: jax.Array,
                   engine: str = "auto"):
    """The FM pair ``(Σ_k v·x, Σ_k v²·x²)`` from ONE pass over the gathered
    rows — the factorization-machine second-order term needs both, and
    separate embed_bag calls would DMA every table row from HBM twice.

    Returns ``(s1[B,D], s2[B,D])``; differentiable w.r.t. (vals, table).
    """
    engine = _resolve_engine(engine, table.shape[1], fused=True,
                             B=ids.shape[0], K=ids.shape[1])
    if engine == "xla":
        g = table[ids]                       # [B,K,D], one gather
        s1 = jnp.einsum("bk,bkd->bd", vals, g)
        s2 = jnp.einsum("bk,bkd->bd", vals * vals, g * g)
        return s1, s2

    interpret = jax.default_backend() != "tpu"

    @jax.custom_vjp
    def f(vals, table):
        return fm_terms_pallas(ids, vals, table, interpret=interpret)

    def fwd(vals, table):
        return f(vals, table), (vals, table)

    def bwd(res, gs):                        # gs = (g1[B,D], g2[B,D])
        vals, table = res
        g1, g2 = gs
        x = table[ids]                       # [B,K,D] — backward-only
        v = vals[..., None]
        dvals = (jnp.einsum("bd,bkd->bk", g1, x)
                 + 2.0 * vals * jnp.einsum("bd,bkd->bk", g2, x * x))
        drows = v * g1[:, None, :] + 2.0 * v * v * x * g2[:, None, :]
        dtable = jnp.zeros_like(table).at[ids.reshape(-1)].add(
            drows.reshape(-1, table.shape[1]))
        return dvals, dtable

    f.defvjp(fwd, bwd)
    return f(vals, table)


def _embed_bag_pallas_diff(ids: jax.Array, vals: jax.Array, table: jax.Array,
                           square: bool, interpret: bool) -> jax.Array:
    """Pallas forward + XLA backward.  The custom_vjp closes over ``ids``
    (integer — no tangent), so the differentiable surface is exactly
    (vals, table)."""

    @jax.custom_vjp
    def f(vals, table):
        return embed_bag_pallas(ids, vals, table, square=square,
                                interpret=interpret)

    def fwd(vals, table):
        return f(vals, table), (vals, table)

    def bwd(res, g):                       # g: [B, D]
        vals, table = res
        gathered = table[ids]              # [B, K, D] — backward-only
        t = gathered * gathered if square else gathered
        dvals = jnp.einsum("bd,bkd->bk", g, t)
        coeff = (2.0 * vals[..., None] * gathered if square
                 else vals[..., None])
        drows = coeff * g[:, None, :]      # [B, K, D]
        dtable = jnp.zeros_like(table).at[ids.reshape(-1)].add(
            drows.reshape(-1, table.shape[1]))
        return dvals, dtable

    f.defvjp(fwd, bwd)
    return f(vals, table)


def embed_bag_reference(ids: jax.Array, vals: jax.Array, table: jax.Array,
                        square: bool = False) -> jax.Array:
    """XLA reference semantics: out[b] = Σ_k vals[b,k] · f(table[ids[b,k]])
    with f = x² when ``square`` (squares the GATHERED [B,K,D] rows only)."""
    g = table[ids]
    if square:
        g = g * g
    return jnp.einsum("bk,bkd->bd", vals, g)


# Rows handled per grid step.  f32 blocked operands must tile to (8, 128):
# an 8-row output block keeps the second-minor dimension a sublane multiple
# (Mosaic rejects (1, D) row blocks outright), and ids/vals ride scalar
# prefetch in SMEM so they need no blocked layout at all.
_ROWS = 8

# DMA ring depth: in-flight table-row fetches per row pipeline.  r4 hardware
# timing showed the 2-slot double buffer is latency-bound (one ~512B DMA
# in flight at a time); an 8-deep ring keeps up to 7 fetches in flight.
_SLOTS = 8

# Scalar-prefetch budget, in i32/f32 elements PER OPERAND.  ids+vals ride
# SMEM (1 MB/core on v5e): B*K beyond this overflows — the exact failure
# TPU_MICRO_r04 captured on hardware ("Allocation (size=8388608) would
# exceed memory (size=1048576)", K>=64 at B=4096).  32768 elements
# (128 KB x 2 operands) is the largest config PROVEN to compile and run
# on Mosaic (K=8, B=4096, 2026-07-31 window); batches larger than the cap
# are split into independent pallas_call chunks outside the kernel.
_SMEM_SCALARS_CAP = 32768


def _chunk_rows(K: int) -> int:
    """Rows per pallas_call so that rows*K scalars stay under the SMEM cap
    (multiple of _ROWS so chunk grids keep full output blocks).

    DMLC_PALLAS_SMEM_SCALARS is read at TRACE time: jit caches are keyed
    on shapes, so changing the env after a shape has been traced does not
    re-chunk that shape for the rest of the process — set it before the
    first call."""
    from ..utils.parameter import env_int
    cap = env_int("DMLC_PALLAS_SMEM_SCALARS", _SMEM_SCALARS_CAP)
    rows = max(cap // max(K, 1), _ROWS)
    return max((rows // _ROWS) * _ROWS, _ROWS)


def _kernel(ids_ref, vals_ref, table_ref, out_ref, buf, sems, *, K: int,
            D: int, B: int, square: bool):
    b = pl.program_id(0)
    for r in range(_ROWS):          # static unroll: one DMA pipeline per row
        # tail block of a non-multiple-of-8 batch: clamp to the last real
        # row (its ids are in-range; the duplicate output rows are dropped
        # by the block writeback mask)
        base = jnp.minimum(b * _ROWS + r, B - 1) * K

        def cp(k, slot, base=base):
            idx = ids_ref[base + k]
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(idx, 1), :], buf.at[slot], sems.at[slot])

        for s in range(min(_SLOTS - 1, K)):   # prologue: fill the ring
            cp(s, s).start()

        def body(k, acc, base=base, cp=cp):
            slot = jax.lax.rem(k, _SLOTS)
            # refill the slot freed at k-1 with the fetch for k+_SLOTS-1,
            # keeping _SLOTS-1 DMAs in flight
            @pl.when(k + _SLOTS - 1 < K)
            def _start_ahead():
                kn = k + _SLOTS - 1
                cp(kn, jax.lax.rem(kn, _SLOTS)).start()

            cp(k, slot).wait()
            g = buf[slot]                    # (1, D)
            if square:                       # static: traced once per variant
                g = g * g
            return acc + g * vals_ref[base + k]

        acc = jax.lax.fori_loop(0, K, body, jnp.zeros((1, D), jnp.float32))
        out_ref[pl.ds(r, 1), :] = acc


def _fm_kernel(ids_ref, vals_ref, table_ref, out1_ref, out2_ref, buf, sems,
               *, K: int, D: int, B: int):
    b = pl.program_id(0)
    for r in range(_ROWS):
        base = jnp.minimum(b * _ROWS + r, B - 1) * K

        def cp(k, slot, base=base):
            idx = ids_ref[base + k]
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(idx, 1), :], buf.at[slot], sems.at[slot])

        for s in range(min(_SLOTS - 1, K)):
            cp(s, s).start()

        def body(k, accs, base=base, cp=cp):
            a1, a2 = accs
            slot = jax.lax.rem(k, _SLOTS)

            @pl.when(k + _SLOTS - 1 < K)
            def _start_ahead():
                kn = k + _SLOTS - 1
                cp(kn, jax.lax.rem(kn, _SLOTS)).start()

            cp(k, slot).wait()
            g = buf[slot]                    # (1, D)
            v = vals_ref[base + k]
            return a1 + g * v, a2 + (g * g) * (v * v)

        zero = jnp.zeros((1, D), jnp.float32)
        a1, a2 = jax.lax.fori_loop(0, K, body, (zero, zero))
        out1_ref[pl.ds(r, 1), :] = a1
        out2_ref[pl.ds(r, 1), :] = a2


def _fm_terms_pallas_one(ids, vals, table, interpret: bool):
    """Single-chunk fused FM kernel: ids/vals SMALL ENOUGH for SMEM."""
    B, K = ids.shape
    F, D = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # flat ids + vals land in SMEM
        grid=(pl.cdiv(B, _ROWS),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],    # table in HBM
        out_specs=[pl.BlockSpec((_ROWS, D), lambda b, ids, vals: (b, 0)),
                   pl.BlockSpec((_ROWS, D), lambda b, ids, vals: (b, 0))],
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, 1, D), jnp.float32),
            pltpu.SemaphoreType.DMA((_SLOTS,)),
        ],
    )
    kernel = functools.partial(_fm_kernel, K=K, D=D, B=B)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D), jnp.float32)],
        interpret=interpret,
    )(ids.reshape(-1).astype(jnp.int32),
      vals.reshape(-1).astype(jnp.float32), table)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fm_terms_pallas(ids: jax.Array, vals: jax.Array, table: jax.Array,
                    interpret: bool = False):
    """One DMA pass per row, BOTH FM reductions: (Σ v·x, Σ v²·x²).

    Batches whose flat ids exceed the SMEM scalar-prefetch budget are split
    into independent row-chunk pallas_calls (TPU_MICRO_r04: B·K ≥ 256Ki
    scalars is a hard Mosaic OOM on v5e's 1 MB SMEM)."""
    B, K = ids.shape
    rows = _chunk_rows(K)
    if B <= rows:
        return _fm_terms_pallas_one(ids, vals, table, interpret)
    outs = [_fm_terms_pallas_one(ids[s:s + rows], vals[s:s + rows],
                                 table, interpret)
            for s in range(0, B, rows)]
    return (jnp.concatenate([o[0] for o in outs], axis=0),
            jnp.concatenate([o[1] for o in outs], axis=0))


def _embed_bag_pallas_one(ids, vals, table, square: bool, interpret: bool):
    """Single-chunk kernel invocation (ids/vals fit the SMEM budget)."""
    B, K = ids.shape
    F, D = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # flat ids + vals land in SMEM
        grid=(pl.cdiv(B, _ROWS),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],    # table in HBM
        out_specs=pl.BlockSpec((_ROWS, D), lambda b, ids, vals: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, 1, D), jnp.float32),  # DMA ring slots
            pltpu.SemaphoreType.DMA((_SLOTS,)),
        ],
    )
    kernel = functools.partial(_kernel, K=K, D=D, B=B, square=square)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(ids.reshape(-1).astype(jnp.int32),
      vals.reshape(-1).astype(jnp.float32), table)


@functools.partial(jax.jit, static_argnames=("square", "interpret"))
def embed_bag_pallas(ids: jax.Array, vals: jax.Array, table: jax.Array,
                     square: bool = False,
                     interpret: bool = False) -> jax.Array:
    """Ring-buffered DMA embedding bag.  ids,vals: [B,K]; table: [F,D] → [B,D].

    Splits oversized batches into SMEM-sized row chunks (see
    ``_chunk_rows``); each chunk is an independent pallas_call, concatenated
    on the way out.  Chunk count is static, so this stays jit-compatible."""
    B, K = ids.shape
    rows = _chunk_rows(K)
    if B <= rows:
        return _embed_bag_pallas_one(ids, vals, table, square, interpret)
    return jnp.concatenate(
        [_embed_bag_pallas_one(ids[s:s + rows], vals[s:s + rows], table,
                               square, interpret)
         for s in range(0, B, rows)], axis=0)
