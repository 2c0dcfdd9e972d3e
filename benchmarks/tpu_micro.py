"""On-chip micro-benchmarks (one process; run it through the chip tool).

Records to benchmarks/TPU_MICRO.json:
  * platform + device kind (proof of TPU execution)
  * bf16 matmul sustained TFLOP/s (MXU utilisation sanity)
  * host→device bandwidth for the fused int32 ingest buffer
  * the compact wire's decode alone, and beside its consumer in one and two
    dispatches
  * sequence-parallel attention and the GPipe tick on a one-device mesh

Usage: python benchmarks/tpu_micro.py [out.json]
Exits nonzero when JAX finds no accelerator.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(f"[tpu_micro +{time.monotonic() - T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.monotonic()


def build_v3_buffer(rows: int, nnz: int, wbits: int, seed: int):
    """Construct a v3 fused wire buffer (bit-packed ids, raw f32 values)
    in numpy — the inverse of ``pipeline.device_loader.make_decoder``'s
    unpack, used by the wire-decode fusion bench.  Module-level so a CPU
    test can round-trip it against the real decoder BEFORE a chip run
    spends time on it.  Returns (buf int32[words], meta, ids, vals)."""
    import numpy as np
    assert nnz % rows == 0, (
        "uniform row_ptr construction needs rows | nnz — a remainder "
        "would strand trailing values in the decoder's scratch row")
    meta = nnz | (wbits << 32)
    iw = (nnz * wbits + 31) // 32
    words = iw + nnz + 3 * rows + 1
    per_row = nnz // rows
    r = np.random.default_rng(seed)
    idsb = r.integers(0, 1 << wbits, nnz).astype(np.uint64)
    bitpos = np.arange(nnz, dtype=np.uint64) * wbits
    word = (bitpos >> np.uint64(5)).astype(np.int64)
    off = bitpos & np.uint64(31)
    packed = np.zeros(iw + 1, np.uint32)     # +1 = spill spare
    np.bitwise_or.at(
        packed, word,
        ((idsb << off) & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    hi = np.where(off > 0, idsb >> (np.uint64(32) - off), np.uint64(0))
    np.bitwise_or.at(packed, word + 1, hi.astype(np.uint32))
    buf = np.empty(words, np.int32)
    buf[:iw] = packed[:iw].view(np.int32)
    vals = r.random(nnz, dtype=np.float32)
    buf[iw:iw + nnz] = vals.view(np.int32)
    buf[iw + nnz:iw + nnz + rows + 1] = (
        np.arange(rows + 1, dtype=np.int32) * per_row)
    buf[iw + nnz + rows + 1:] = np.ones(2 * rows, np.float32).view(np.int32)
    return buf, meta, idsb, vals


def sync_value(y) -> float:
    """Force completion by reading a value back to the host: a
    device→host read of a reduction over the result cannot resolve early
    on any runtime — the bytes must exist.  Costs one round trip per
    call, so callers amortise it over ``iters``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    leaf = jax.tree_util.tree_leaves(y)[0]
    return float(np.asarray(jnp.sum(leaf.astype(jnp.float32))))


_SYNC_EST = [None]


def sync_overhead_s() -> float:
    """Measured cost of one ``sync_value`` round-trip on a trivial array —
    the fixed RTT floor that sits inside every timed window (one per
    timed_fb call, amortized over its iters).  Computed once, recorded in
    the artifact, and subtracted by timed_fb so sub-ms kernels aren't
    reported as pure link latency."""
    if _SYNC_EST[0] is None:
        import jax.numpy as jnp
        y = jnp.ones((8, 8), jnp.float32)
        sync_value(y)                        # compile the sum program
        t0 = time.perf_counter()
        n = 3
        for _ in range(n):
            sync_value(y)
        _SYNC_EST[0] = (time.perf_counter() - t0) / n
    return _SYNC_EST[0]


def timed_fb(fn, y0, *rest, warmup: int = 2, iters: int = 3) -> float:
    """Feedback timing: each dispatch consumes the PREVIOUS dispatch's
    output (fn must map its first arg to a same-shaped output), so no
    runtime can dedupe repeated identical (program, args) executions.
    Timing ends at a device→host value read (``sync_value``); the read's
    own fixed RTT (``sync_overhead_s``) is subtracted before dividing,
    clamped so a sub-RTT measurement degrades to 0-biased, not negative."""
    ovh = sync_overhead_s()
    y = y0
    for _ in range(warmup):
        y = fn(y, *rest)
    sync_value(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(y, *rest)
    sync_value(y)
    t = time.perf_counter() - t0
    # floor at 5% of the raw window (never 0.0): a sub-RTT measurement
    # degrades to a small positive upper bound instead of crashing the
    # TFLOP/s division or tripping falsy-zero checks downstream
    return max(t - ovh, 0.05 * t, 1e-9) / iters


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        REPO, "benchmarks", "TPU_MICRO.json")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_core_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    log(f"backend up: {dev.platform} / {dev.device_kind} x{len(devs)}")
    if dev.platform == "cpu":
        log("tpu_micro measures the chip and JAX found no accelerator "
            "— exiting 3")
        return 3
    result = {
        "platform": dev.platform,
        "device_kind": str(dev.device_kind),
        "num_devices": len(devs),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }

    result["sync_overhead_ms"] = round(sync_overhead_s() * 1e3, 3)
    log(f"sync RTT: {result['sync_overhead_ms']} ms (subtracted per "
        "timed_fb window)")

    # --- bf16 matmul TFLOP/s (MXU) ---
    # CHAINED matmuls inside one jit: a data-dependent chain forces every
    # multiply to actually run, and one dispatch amortises the launch.
    n, chain_len = 4096, 10
    x = (jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
         * (1.0 / np.sqrt(n))).astype(jnp.bfloat16)

    @jax.jit
    def mm_chain(a):
        def body(_, acc):
            return ((acc @ a) * jnp.bfloat16(0.125)).astype(jnp.bfloat16)
        return jax.lax.fori_loop(0, chain_len, body, a)

    dt = timed_fb(mm_chain, x, iters=3) / chain_len
    result["matmul_bf16_4096_tflops"] = round(2 * n**3 / dt / 1e12, 2)
    log(f"matmul: {result['matmul_bf16_4096_tflops']} TFLOP/s")

    # --- h2d bandwidth: the ingest fused buffer path ---
    for mb in (64,):
        buf = np.empty(mb * (1 << 20) // 4, np.int32)
        t0 = time.perf_counter()
        reps = 5
        for rep in range(reps):
            # distinct bytes per rep: repeated identical (args, device)
            # puts are exactly the shape the runtime dedupes (the reason
            # every kernel timing here carries feedback)
            buf[rep] = rep
            h = jax.device_put(buf, dev)
            jax.block_until_ready(h)
        # read one element back: device_put's ready-future resolving is not
        # proof the bytes landed (see sync_value) — a d2h read of the last
        # put is.  Its RTT is subtracted like every other timed window
        # here (same 5%-of-raw floor as timed_fb).
        int(np.asarray(h[:1])[0])
        t = time.perf_counter() - t0
        dt = max(t - sync_overhead_s(), 0.05 * t, 1e-9) / reps
        result[f"h2d_{mb}mb_gbps"] = round(mb / 1024 / dt, 3)
        log(f"h2d {mb}MB: {result[f'h2d_{mb}mb_gbps']} GB/s")

    # --- wire-v3 decode: cost + fusion headroom (VERDICT r4 #7) ---
    # The proposed fused decode+gather Mosaic kernel can win AT MOST
    # (decode cost) + (two-dispatch - fused-jit gap): the first is what a
    # kernel could theoretically hide under the gather's DMAs, the second
    # is what dispatch fusion alone already buys with XLA.  Measuring the
    # bound on hardware decides the kernel's fate without building it.
    try:
        from dmlc_core_tpu.ops.csr import fm_pairwise
        from dmlc_core_tpu.pipeline.device_loader import make_decoder
        rows_w, nnzw, wbits = 4096, 131072, 20
        meta = nnzw | (wbits << 32)

        def build_buf(seed: int):
            return build_v3_buffer(rows_w, nnzw, wbits, seed)[0]

        decode = make_decoder(rows_w, meta)
        decode_j = jax.jit(decode)
        table16 = jax.random.normal(jax.random.PRNGKey(0), (1 << wbits, 16),
                                    jnp.float32)

        def consume(d):
            return fm_pairwise(d["ids"], d["vals"], d["segments"], table16,
                               rows_w)

        fused_j = jax.jit(lambda b: consume(decode(b)))
        consume_j = jax.jit(consume)
        # seed 0 built once: its buffer seeds the device list AND its ids
        # drive the correctness gate (a second bitpack pass would waste
        # chip seconds)
        buf0, _, ids0, _ = build_v3_buffer(rows_w, nnzw, wbits, 0)
        bufs = [jax.device_put(buf0)] + [jax.device_put(build_buf(s))
                                         for s in range(1, 6)]
        np.testing.assert_array_equal(
            np.asarray(decode_j(bufs[0])["ids"]), ids0.astype(np.int64))
        # warm every program
        float(np.asarray(fused_j(bufs[0])).sum())
        float(np.asarray(consume_j(decode_j(bufs[0]))).sum())

        def rate(fn) -> float:
            """Per-buffer seconds over 5 DISTINCT buffers (distinct bytes
            defeat dispatch dedupe), one value read at the end as the
            completion proof."""
            acc = None
            t0 = time.perf_counter()
            for b in bufs[1:]:
                y = fn(b)
                acc = y if acc is None else acc + y
            float(np.asarray(acc).ravel()[0])
            return (time.perf_counter() - t0) / (len(bufs) - 1)

        t_decode = rate(lambda b: decode_j(b)["vals"].sum())
        t_two = rate(lambda b: consume_j(decode_j(b)).sum())
        t_fused = rate(lambda b: fused_j(b).sum())
        result["wire_decode_fusion"] = {
            "decode_only_us": round(t_decode * 1e6, 1),
            "two_dispatch_us": round(t_two * 1e6, 1),
            "fused_jit_us": round(t_fused * 1e6, 1),
            "fusion_headroom_us": round((t_two - t_fused) * 1e6, 1),
            "shape": f"rows={rows_w} nnz={nnzw} w={wbits} dim=16",
        }
        log(f"wire decode: {t_decode*1e6:.0f}us alone; decode+fm two-"
            f"dispatch {t_two*1e6:.0f}us vs fused {t_fused*1e6:.0f}us")
    except Exception as e:  # noqa: BLE001
        result["wire_decode_fusion_error"] = f"{type(e).__name__}: {e}"
        log(f"wire decode fusion bench failed: {e}")

    # --- sp/pp on the real backend, 1-device degenerate mesh (VERDICT r3
    # #7): shard_map + ppermute/all_to_all must lower through Mosaic/XLA-TPU
    # — the collective code paths compile and execute even at axis size 1,
    # which has caught real-backend-only bugs the 8-device CPU mesh cannot.
    try:
        from jax.sharding import Mesh

        from dmlc_core_tpu.ops.ring_attention import (make_ring_attention,
                                                      reference_attention)
        from dmlc_core_tpu.ops.ulysses import make_ulysses_attention
        mesh1 = Mesh(np.array(devs[:1]), ("sp",))
        B, T, H, D = 1, 1024, 8, 64
        # three DISTINCT tensors: identical q/k/v would let an operand-swap
        # or mis-routed collective still match the dense reference
        q, k_, v = (jax.random.normal(s, (B, T, H, D), jnp.float32)
                    for s in jax.random.split(jax.random.PRNGKey(2), 3))
        sp = {}
        ref = reference_attention(q, k_, v, causal=True)
        for name, maker in (("ring", make_ring_attention),
                            ("ulysses", make_ulysses_attention)):
            try:
                fn = maker(mesh1, "sp", causal=True)
                # tolerance sized for TPU, not CPU: TPU matmuls default to
                # bf16-mantissa passes, so the ring's blockwise softmax
                # reassociation can differ from dense by ~1 bf16 ulp
                # (TPU_MICRO_r04 measured max 5.4e-3 abs on 0.009% of
                # elements at the old 2e-3 — numerics, not a routing bug)
                np.testing.assert_allclose(np.asarray(fn(q, k_, v)),
                                           np.asarray(ref), rtol=1e-2,
                                           atol=1e-2)
                # feedback out->q: attention output is q-shaped, so each
                # dispatch differs and cannot be deduped by the runtime
                sp[name + "_us"] = round(
                    timed_fb(fn, q, k_, v, iters=3) * 1e6, 1)
                log(f"sp {name}: {sp[name + '_us']}us (matches dense)")
            except Exception as e:  # noqa: BLE001
                sp[name + "_error"] = f"{type(e).__name__}: {e}"
                log(f"sp {name} failed: {e}")
        result["sp_1dev"] = {**sp, "shape": f"B{B} T{T} H{H} D{D} causal"}
    except Exception as e:  # noqa: BLE001
        result["sp_error"] = f"{type(e).__name__}: {e}"
        log(f"sp bench failed: {e}")

    try:
        from jax.sharding import Mesh

        from dmlc_core_tpu.parallel.pipeline import (make_pipeline,
                                                     split_microbatches,
                                                     stack_stage_params)
        mesh1 = Mesh(np.array(devs[:1]), ("pp",))

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"])

        F, M, MB = 256, 4, 128
        wkey = jax.random.PRNGKey(3)
        params = stack_stage_params(
            [{"w": jax.random.normal(wkey, (F, F), jnp.float32) * 0.05}])
        xs = split_microbatches(
            jax.random.normal(wkey, (M * MB, F), jnp.float32), M)
        run = jax.jit(make_pipeline(mesh1, "pp", stage_fn))
        ys = run(params, xs)
        expect = jnp.tanh(xs @ params["w"][0])
        np.testing.assert_allclose(np.asarray(ys), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)
        result["pp_1dev"] = {
            # ys is xs-shaped (square stages): feed it back so repeat
            # dispatches differ (no runtime dedupe)
            "us": round(timed_fb(lambda y, p: run(p, y), xs, params,
                                 iters=3) * 1e6, 1),
            "shape": f"S1 M{M} mb{MB} F{F}"}
        log(f"pp 1-dev GPipe tick: {result['pp_1dev']['us']}us "
            "(matches direct)")
    except Exception as e:  # noqa: BLE001
        result["pp_error"] = f"{type(e).__name__}: {e}"
        log(f"pp bench failed: {e}")

    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    log(f"wrote {out_path}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
