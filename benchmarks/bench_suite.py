"""Extended benchmark suite covering BASELINE.json's config list — one JSON
line per config (the root ``bench.py`` stays the driver's single headline
number; this suite is for profiling the rest):

* ``libsvm``    — sparse text → device batches (same as bench.py)
* ``csv``       — dense HIGGS-style CSV → RowBlocks (host parse only)
* ``libfm``     — field-aware sparse (Criteo-style) → device batches
* ``recordio``  — .rec streaming: write then partitioned read MB/s
* ``stream``    — raw SeekStream read MB/s at several buffer sizes
* ``remote_ingest`` — disaggregated ingest: 2 worker subprocesses stream
                  fused wire frames to this process
* ``allreduce`` — mesh psum bus-bandwidth (GB/s) over available devices
* ``sharded``   — multi-partition libfm ingest (all parts on this host),
                  the single-host stand-in for multi-chip sharded InputSplit

Usage: ``python benchmarks/bench_suite.py [config ...]`` (default: all).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MB = 1 << 20
TARGET_MB = int(os.environ.get("DMLC_BENCH_MB", "64"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _gen_libsvm(path: str, libfm: bool = False) -> None:
    import numpy as np
    if os.path.exists(path) and os.path.getsize(path) >= TARGET_MB * MB * 0.9:
        return
    rng = np.random.default_rng(0)
    with open(path, "wb") as f:
        written = 0
        while written < TARGET_MB * MB:
            rows = []
            for i in range(10000):
                n = int(rng.integers(5, 40))
                idx = np.sort(rng.choice(1_000_000, size=n, replace=False))
                vals = rng.random(n)
                if libfm:
                    toks = b" ".join(b"%d:%d:%.4f" % (j % 40, j, v)
                                     for j, v in zip(idx.tolist(),
                                                     vals.tolist()))
                else:
                    toks = b" ".join(b"%d:%.4f" % (j, v)
                                     for j, v in zip(idx.tolist(),
                                                     vals.tolist()))
                rows.append(b"%d " % (i & 1) + toks)
            blob = b"\n".join(rows) + b"\n"
            f.write(blob)
            written += len(blob)


def _gen_csv(path: str, ncol: int = 29) -> None:
    import numpy as np
    if os.path.exists(path) and os.path.getsize(path) >= TARGET_MB * MB * 0.9:
        return
    rng = np.random.default_rng(0)
    with open(path, "wb") as f:
        written = 0
        while written < TARGET_MB * MB:
            block = rng.random((5000, ncol)).astype(np.float32)
            lines = [(b"%d," % (i & 1)) + b",".join(b"%.5f" % v for v in row)
                     for i, row in enumerate(block)]
            blob = b"\n".join(lines) + b"\n"
            f.write(blob)
            written += len(blob)


def _ingest_rate(uri: str, fmt: str, parts: int = 1) -> float:
    import bench
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader
    path = uri.split("://", 1)[-1].split("?")[0]
    size_mb = os.path.getsize(path) / MB
    # same parser discipline as the root bench: on a serial host the extra
    # parse thread only adds switches — and an un-threaded single-thread
    # parser is what lets the loader engage the fused streampack path
    cores = bench.host_cores()
    nthreads, threaded = (1, False) if cores == 1 else (cores, True)
    # batch shape: env pin > the root bench's persisted winner > built-in
    # default (the screened shape is part of its speed, and the suite's
    # job is to reflect the tuned pipeline, not a worst default)
    import jax as _jax
    from dmlc_core_tpu.pipeline.tuned import load_tuned
    tuned = load_tuned(_jax.default_backend()) or {}
    batch_rows = int(os.environ.get("DMLC_BENCH_ROWS", "0")) \
        or int(tuned.get("batch_rows", 4096))
    nnz_cap = int(os.environ.get("DMLC_BENCH_NNZ", "0")) \
        or int(tuned.get("nnz_cap", 131072))
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        acc = None
        for part in range(parts):
            # env knobs still win; otherwise the loader's "auto" defaults
            # inherit the persisted tuning
            kw = {}
            pt = int(os.environ.get("DMLC_BENCH_PUT_THREADS", "0"))
            if pt > 0:
                kw["put_threads"] = pt
            cm = os.environ.get("DMLC_BENCH_COMPACT")
            if cm is not None:
                kw["wire_compact"] = cm != "0"
            loader = DeviceLoader(
                create_parser(uri, part, parts, fmt, nthreads=nthreads,
                              threaded=threaded),
                batch_rows=batch_rows, nnz_cap=nnz_cap, prefetch=4, **kw)
            for batch in loader:
                # completion-proof accumulator (bench.consume_batch):
                # the final value read ends the timed window
                acc = bench.consume_batch(acc, batch)
            loader.close()
        bench.prove_consumed(acc)
        best = max(best, size_mb / (time.perf_counter() - t0))
    return best


def bench_libsvm() -> dict:
    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    v = _ingest_rate(f"file://{path}", "libsvm")
    return {"metric": "libsvm_ingest_to_device", "value": round(v, 1),
            "unit": "MB/s"}


def bench_ingest_cached() -> dict:
    """Packed-page epoch cache (`pipeline/page_cache.py`): one loader
    config measured three ways — cache-off baseline, epoch 1 with
    write-through, epoch ≥2 replaying mmap'd pages.  The headline value is
    the cached-epoch rate; the artifact carries the acceptance ratios
    (cached ≥ 2× uncached, write-through within 10% of baseline, pack ≤ 5%
    of cached-epoch wall)."""
    import shutil
    import tempfile

    import bench
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader
    from dmlc_core_tpu.utils.metrics import metrics

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    cores = bench.host_cores()
    nthreads, threaded = (1, False) if cores == 1 else (cores, True)
    batch_rows = int(os.environ.get("DMLC_BENCH_ROWS", "16384"))
    nnz_cap = int(os.environ.get("DMLC_BENCH_NNZ", str(512 * 1024)))

    def make_loader(cache=None):
        return DeviceLoader(
            create_parser(path, 0, 1, "libsvm", nthreads=nthreads,
                          threaded=threaded),
            batch_rows=batch_rows, nnz_cap=nnz_cap, prefetch=4,
            cache=cache)

    def epoch(loader) -> float:
        t0 = time.perf_counter()
        acc = None
        for b in loader:
            acc = bench.consume_batch(acc, b)
        bench.prove_consumed(acc)
        return time.perf_counter() - t0

    def stage_sec(name: str) -> float:
        return metrics.stage(name).total_sec

    # cache-off baseline, best of 2 epochs on one loader
    metrics.reset()
    loader = make_loader()
    base_wall = epoch(loader)
    loader.before_first()
    base_wall = min(base_wall, epoch(loader))
    loader.close()
    uncached = size_mb / base_wall

    tmp = tempfile.mkdtemp(prefix="dmlc_pagecache_")
    try:
        metrics.reset()
        loader = make_loader(cache=os.path.join(tmp, "pages"))
        wall1 = epoch(loader)                   # build (write-through)
        pack1 = stage_sec("device_loader.pack")
        write1 = stage_sec("device_loader.cache_write")
        metrics.reset()                         # per-epoch attribution
        loader.before_first()
        wall2 = epoch(loader)                   # cached replay
        pack2 = stage_sec("device_loader.pack")
        read2 = stage_sec("device_loader.cache_read")
        hits = int(metrics.counter("page_cache.hits").value)
        loader.before_first()
        wall_best = min(wall2, epoch(loader))   # best cached epoch
        loader.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    cached = size_mb / wall_best
    return {"metric": "ingest_cached", "value": round(cached, 1),
            "unit": "MB/s",
            "uncached_mbps": round(uncached, 1),
            "epoch1_mbps": round(size_mb / wall1, 1),
            "epoch2_mbps": round(size_mb / wall2, 1),
            "cached_over_uncached": round(cached / uncached, 2),
            "epoch1_over_uncached": round((size_mb / wall1) / uncached, 2),
            "pack_sec_epoch1": round(pack1, 3),
            "pack_sec_epoch2": round(pack2, 3),
            "pack_frac_epoch2": round(pack2 / wall2, 4),
            "cache_write_sec_epoch1": round(write1, 3),
            "cache_read_sec_epoch2": round(read2, 3),
            "cache_hits_epoch2": hits}


def bench_ingest_autotune() -> dict:
    """Cold-start convergence of the closed-loop autotuner (ISSUE 7):
    start from deliberately degraded defaults (parser threads 1,
    prefetch 1), let the controller hill-climb one knob per epoch, and
    report the steady-state rate it reaches plus how many epochs the
    climb took.  Acceptance: steady state within 10% of the hand-tuned
    reference measured in the same process (``ratio_vs_tuned >= 0.9``),
    and convergence well inside the epoch budget."""
    import bench
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader, autotune
    from dmlc_core_tpu.utils.metrics import metrics

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    cores = bench.host_cores()
    batch_rows = int(os.environ.get("DMLC_BENCH_ROWS", "16384"))
    nnz_cap = int(os.environ.get("DMLC_BENCH_NNZ", str(512 * 1024)))
    max_epochs = int(os.environ.get("DMLC_BENCH_AUTOTUNE_EPOCHS", "20"))

    def epoch_rate(cfg: dict) -> float:
        # same knob semantics as serve_ingest: parser_threads==1 keeps
        # the single-thread streampack fast path
        pt = int(cfg.get("parser_threads", 1))
        nthreads, threaded = (1, False) if pt <= 1 else (pt, True)
        loader = DeviceLoader(
            create_parser(path, 0, 1, "libsvm", nthreads=nthreads,
                          threaded=threaded),
            batch_rows=batch_rows, nnz_cap=nnz_cap,
            prefetch=int(cfg.get("prefetch", 2)))
        t0 = time.perf_counter()
        acc = None
        for b in loader:
            acc = bench.consume_batch(acc, b)
        loader.close()
        bench.prove_consumed(acc)
        return size_mb / (time.perf_counter() - t0)

    metrics.reset()
    metrics.gauge("slo.active_breaches").set(0)
    # hand-tuned reference: the non-degraded baselines, best of 2
    tuned_cfg = {k.name: k.value
                 for k in autotune.ingest_knob_space(cores=cores)}
    tuned_rate = max(epoch_rate(tuned_cfg), epoch_rate(tuned_cfg))
    # cold start from the worst rung; direct construction (key=None) so
    # the experiment never reads or writes the persisted winner file
    tuner = autotune.Autotuner(
        autotune.ingest_knob_space(cores=cores, degraded=True), key=None)
    cold_rate = 0.0
    epochs = 0
    for epochs in range(1, max_epochs + 1):
        cfg = tuner.begin_epoch()
        rate = epoch_rate(cfg)
        if epochs == 1:
            cold_rate = rate
        tuner.end_epoch(rate)
        if tuner.converged:
            break
    steady = epoch_rate(tuner.config())
    # steady_state_mb_s repeats the headline under a name the regression
    # gate classifies higher-better (check_regression's token list)
    return {"metric": "ingest_autotune", "value": round(steady, 1),
            "unit": "MB/s",
            "steady_state_mb_s": round(steady, 1),
            "epochs_to_converge": epochs,
            "converged": bool(tuner.converged),
            "cold_start_mbps": round(cold_rate, 1),
            "tuned_ref_mbps": round(tuned_rate, 1),
            "ratio_vs_tuned": round(steady / tuned_rate, 3),
            "best_knobs": tuner.best_config(),
            "mutations": int(metrics.counter("autotune.mutations").value),
            "accepted": int(metrics.counter("autotune.accepted").value)}


def bench_ingest_ragged() -> dict:
    """Ragged vs padded device batches at **equal batch budget**
    (ISSUE 6): the same file, the same (batch_rows, nnz_cap), once
    through the production padded path and once with ``ragged=True``
    (nnz-packed batches + ``nnz_used`` prefix words, no tail zeroing,
    never truncates).  Headline is ragged rows/s; the artifact carries
    both rates, a python-pack padded rate (same code family as the
    ragged packer — isolates the layout effect from the C++ packer),
    and the measured padding ratio (padded-nnz / true-nnz) before and
    after."""
    import bench
    from dmlc_core_tpu import native
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader
    from dmlc_core_tpu.utils.metrics import metrics

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    cores = bench.host_cores()
    nthreads, threaded = (1, False) if cores == 1 else (cores, True)
    batch_rows = int(os.environ.get("DMLC_BENCH_ROWS", "4096"))
    nnz_cap = int(os.environ.get("DMLC_BENCH_NNZ", "131072"))

    def run(ragged: bool, force_python: bool = False):
        """(rows/s best-of-2, rows, true_nnz, batches) for one config."""
        real_has_packer = native.has_packer
        if force_python:
            native.has_packer = lambda: False
        try:
            best = 0.0
            rows = true_nnz = batches = 0
            for _ in range(2):
                metrics.reset()
                loader = DeviceLoader(
                    create_parser(path, 0, 1, "libsvm",
                                  nthreads=nthreads, threaded=threaded),
                    batch_rows=batch_rows, nnz_cap=nnz_cap, prefetch=4,
                    ragged=ragged)
                t0 = time.perf_counter()
                acc = None
                for b in loader:
                    acc = bench.consume_batch(acc, b)
                bench.prove_consumed(acc)
                wall = time.perf_counter() - t0
                rows = loader.stats.rows
                true_nnz = loader.stats.true_nnz
                batches = int(
                    metrics.counter("device_loader.batches").value)
                loader.close()
                best = max(best, rows / wall)
            return best, rows, true_nnz, batches
        finally:
            native.has_packer = real_has_packer

    padded_rps, rows, _, pbatches = run(ragged=False)
    pypad_rps, _, py_nnz, pybatches = run(ragged=False,
                                          force_python=True)
    ragged_rps, rrows, r_nnz, rbatches = run(ragged=True)
    assert rrows == rows, (rrows, rows)        # ragged never drops rows
    # padded FLOP basis: every batch reduces the full nnz_cap
    pad_ratio = (pybatches * nnz_cap) / max(1, py_nnz)
    return {"metric": "ingest_ragged", "value": round(ragged_rps, 1),
            "unit": "rows/s",
            "padded_rows_per_s": round(padded_rps, 1),
            "python_padded_rows_per_s": round(pypad_rps, 1),
            "ragged_rows_per_s": round(ragged_rps, 1),
            "ragged_over_python_padded": round(
                ragged_rps / max(pypad_rps, 1e-9), 2),
            "padding_ratio_padded": round(pad_ratio, 2),
            "padding_ratio_ragged": 1.0,
            "rows": rows,
            "true_nnz": r_nnz,
            "batches_padded": pbatches,
            "batches_ragged": rbatches}


def bench_libfm() -> dict:
    path = "/tmp/bench_suite.libfm"
    _gen_libsvm(path, libfm=True)
    v = _ingest_rate(f"file://{path}", "libfm")
    return {"metric": "libfm_ingest_to_device", "value": round(v, 1),
            "unit": "MB/s"}


def bench_sharded() -> dict:
    """All 4 partitions ingested on this host — single-host stand-in for the
    multi-chip sharded InputSplit config."""
    path = "/tmp/bench_suite.libfm"
    _gen_libsvm(path, libfm=True)
    v = _ingest_rate(f"file://{path}", "libfm", parts=4)
    return {"metric": "libfm_sharded4_ingest", "value": round(v, 1),
            "unit": "MB/s"}


def bench_fm_train() -> dict:
    """Full-framework training throughput: libsvm text → parse → pack →
    h2d → jitted FM train step (grad + adam), one chip.  The reference has
    no training path — this is the net-new end-to-end number proving the
    ingest feed keeps a compute consumer busy (ingest overlaps the step:
    batch N+1 transfers while step N runs)."""
    import jax
    import optax
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.models import FactorizationMachine, make_train_step
    from dmlc_core_tpu.pipeline import DeviceLoader

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    model = FactorizationMachine(num_features=1 << 20, dim=32)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = make_train_step(model, opt)
    kstep = int(os.environ.get("DMLC_TRAIN_KSTEP", "16"))
    fused_state = {"trainer": None}
    ckpt_every = 8
    saves_done = 0

    def run_epochs(n_runs: int, ckpt_mode: str = "off",
                   max_steps: int = 0):
        """ckpt_mode: 'off' | 'sync' | 'async' — mid-train checkpointing
        every ``ckpt_every`` steps, quantifying what save_async buys over
        a blocking save at the same cadence.  ``max_steps`` > 0 bounds an
        epoch (the ckpt-mode passes use it: full-corpus ckpt epochs can
        blow the per-config timeout — a step-capped pass measures the
        same sync-vs-async delta in bounded time)."""
        nonlocal params, opt_state, saves_done
        import shutil
        import tempfile

        from dmlc_core_tpu.models import FusedTrainer
        from dmlc_core_tpu.utils import CheckpointManager
        best_rows = best_mb = best_feed = 0.0
        loss = None
        # the headline ('off') pass uses the k-step fused dispatch like
        # _train_rate; the ckpt passes keep the per-step loop (they measure
        # the per-step save-cadence delta, not throughput)
        use_fused = ckpt_mode == "off" and kstep > 1
        for _ in range(n_runs):
            ckdir = (tempfile.mkdtemp(prefix="bench_ck")
                     if ckpt_mode != "off" else None)
            mgr = CheckpointManager(ckdir) if ckdir else None
            loader = DeviceLoader(
                create_parser(f"file://{path}", 0, 1, "libsvm"),
                batch_rows=4096, nnz_cap=131072, prefetch=4, id_mod=1 << 20,
                emit="host" if use_fused else "device")
            try:
                rows = 0
                nstep = 0
                t0 = time.perf_counter()
                if use_fused:
                    tr = fused_state["trainer"]
                    if tr is None:
                        tr = FusedTrainer(model, opt, loader, k=kstep,
                                          params=params,
                                          opt_state=opt_state)
                        fused_state["trainer"] = tr
                    else:
                        tr.loader = loader
                    for item in loader:
                        tr.feed(item)
                        rows += loader.batch_rows
                    tr.flush()
                    dt_submit = time.perf_counter() - t0
                    params, opt_state, loss = (tr.params, tr.opt_state,
                                               tr.losses[-1])
                    float(loss)
                    dt = time.perf_counter() - t0
                    best_rows = max(best_rows, rows / dt)
                    best_feed = max(best_feed, rows / dt_submit)
                    best_mb = max(best_mb, size_mb / dt)
                    continue
                for batch in loader:
                    params, opt_state, loss = step(params, opt_state, batch)
                    rows += int(batch["labels"].shape[0])
                    nstep += 1
                    if mgr is not None and nstep % ckpt_every == 0:
                        state = {"params": params, "opt_state": opt_state}
                        if ckpt_mode == "sync":
                            mgr.save(nstep, state)
                        else:
                            mgr.save_async(nstep, state)
                        saves_done += 1
                    if max_steps and nstep >= max_steps:
                        break
                dt_submit = time.perf_counter() - t0
                if mgr is not None:
                    mgr.wait()
                # value read-back (see _train_rate) ends the timed window
                float(loss)
                dt = time.perf_counter() - t0
            finally:
                loader.close()
                if ckdir:
                    shutil.rmtree(ckdir, ignore_errors=True)
            best_rows = max(best_rows, rows / dt)
            best_feed = max(best_feed, rows / dt_submit)
            best_mb = max(best_mb, size_mb / dt)
        return best_rows, best_mb, best_feed, loss

    import bench
    best_rows, best_mb, best_feed, loss = run_epochs(3, "off")
    # best-of-2 per mode, STEP-CAPPED (32 steps = 131k rows, 4 saves at
    # ckpt_every=8): a single noisy epoch would swamp the sync-vs-async
    # delta, and uncapped ckpt epochs can blow the per-config timeout.
    # best_mb is only meaningful from
    # the uncapped pass — capped passes report rows-based rates only.
    # 36, not 32: a cap that lands ON a save boundary gives the last
    # async save zero steps to overlap with (25% of saves paying full
    # blocking cost would attenuate the very delta this measures); four
    # post-save steps keep the tail overlapped like the uncapped epoch
    sync_rows, _, _, _ = run_epochs(2, "sync", max_steps=36)
    async_rows, _, _, _ = run_epochs(2, "async", max_steps=36)
    r = {"metric": "fm_train_stream", "value": round(best_rows, 0),
         "unit": "rows/s", "text_mbps": round(best_mb, 1),
         "feed_rows_s": round(best_feed, 0),
         "kstep": kstep if kstep > 1 else 1,
         "final_loss": round(float(loss), 4),
         "ckpt_sync_rows_s": round(sync_rows, 0),
         "ckpt_async_rows_s": round(async_rows, 0),
         "ckpt_saves": saves_done, "ckpt_every": ckpt_every,
         "ckpt_host_cores": bench.host_cores()}
    if saves_done == 0:
        # tiny corpus (< ckpt_every steps/run): the comparison measured
        # nothing — say so instead of implying zero-cost checkpointing
        r["ckpt_note"] = "corpus too small: no checkpoint fired"
    elif bench.host_cores() == 1:
        # honest caveat: with no spare core the background writer steals
        # cycles from parse/train, so async can LOSE to sync here — its
        # overlap win needs a host core to absorb the writer
        r["ckpt_note"] = ("1-core host: async writer contends with the "
                          "train/parse thread; overlap benefit requires "
                          "spare host cores")
    return r


def _step_flops(model, opt, batch_rows: int = 4096,
                nnz_cap: int = 131072) -> float:
    """XLA's own FLOP estimate for one train step (grad + adam) on a
    representative flat batch — the denominator for model-level MFU
    (VERDICT r4 weak #7: single-chip MFU evidence was microbench-only).
    Returns 0.0 when cost analysis is unavailable."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.models import make_train_step
    try:
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        batch = {
            "ids": jnp.zeros(nnz_cap, jnp.int32),
            "vals": jnp.zeros(nnz_cap, jnp.float32),
            "segments": jnp.full(nnz_cap, batch_rows, jnp.int32),
            "row_ptr": jnp.zeros(batch_rows + 1, jnp.int32),
            "labels": jnp.zeros(batch_rows, jnp.float32),
            "weights": jnp.ones(batch_rows, jnp.float32),
        }
        step = make_train_step(model, opt, donate=False)
        cost = step.lower(params, opt_state, batch).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", 0.0))
    except Exception as e:  # noqa: BLE001 — MFU is telemetry, not a gate
        log(f"cost analysis unavailable: {type(e).__name__}: {e}")
        return 0.0


# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.  A
# device that is not in the table is an error, not a default: a ratio
# against another chip's peak is not a utilization.
PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gb_s": 819.0,
                    "source": 'Google Cloud documentation, "TPU v5e": '
                              "197 TFLOP/s bf16, 819 GB/s HBM per chip"},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} (have {sorted(PEAKS)}); add the "
                       f"chip with its source rather than borrow another's")
    return PEAKS[device_kind]


def _mfu_fields(model, opt, rows_s: float, batch_rows: int = 4096) -> dict:
    """f32-heavy models run well below the bf16 peak by design — the MFU
    column is context for the MXU-dominated configs (dcn/deepfm)."""
    import jax
    peak = device_peaks(jax.devices()[0].device_kind)["bf16_tflops"]
    f = _step_flops(model, opt, batch_rows=batch_rows)
    if not f or not rows_s:
        return {}
    tflops_s = f * (rows_s / batch_rows) / 1e12
    return {"step_gflops": round(f / 1e9, 2),
            "tflops_s": round(tflops_s, 4),
            "mfu_vs_bf16_peak": round(tflops_s / peak, 5)}


def _train_rate(model, path: str, fmt: str, *, fields: bool = False,
                id_mod: int = 1 << 20, runs: int = 2):
    """Best-of-``runs`` epoch throughput of text → parse → pack → h2d →
    jitted train step for any model in the family (shared by the
    deepfm/dcn/ffm configs; fm_train keeps its own loop for the checkpoint
    comparison it also measures).

    Default path is the k-step fused dispatch (``DMLC_TRAIN_KSTEP``,
    default 16): k batches ship as one stacked put and run as one scanned
    dispatch, so a per-dispatch round trip amortizes ×k (whether the
    direct-attached chip has such a gap is ROADMAP S1's question — not
    measured).  ``DMLC_TRAIN_KSTEP=1``
    restores the per-step loop.  The fields=True (ffm) config has no fused
    wire region for field ids and always runs per-step."""
    import jax
    import optax
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.models import FusedTrainer, make_train_step
    from dmlc_core_tpu.pipeline import DeviceLoader

    kstep = int(os.environ.get("DMLC_TRAIN_KSTEP", "16"))
    use_fused = kstep > 1 and not fields
    kstep_used = kstep if use_fused else 1
    size_mb = os.path.getsize(path) / MB
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = None if use_fused else make_train_step(model, opt)
    trainer = None
    best_rows = best_mb = best_feed = 0.0
    loss = None
    for _ in range(runs):
        loader = DeviceLoader(
            create_parser(f"file://{path}", 0, 1, fmt),
            batch_rows=4096, nnz_cap=131072, prefetch=4, id_mod=id_mod,
            fields=fields, emit="host" if use_fused else "device")
        try:
            rows = 0
            t0 = time.perf_counter()
            if use_fused:
                if trainer is None:
                    trainer = FusedTrainer(model, opt, loader, k=kstep,
                                           params=params,
                                           opt_state=opt_state)
                else:
                    trainer.loader = loader  # keep the jit cache warm
                for item in loader:
                    trainer.feed(item)
                    rows += loader.batch_rows
                trainer.flush()
                dt_submit = time.perf_counter() - t0
                loss = trainer.losses[-1]
            else:
                for batch in loader:
                    params, opt_state, loss = step(params, opt_state, batch)
                    rows += int(batch["labels"].shape[0])
                dt_submit = time.perf_counter() - t0
            # two rates from one epoch: loop exit = last step SUBMITTED
            # (host feed ceiling), loss read-back = last step COMPLETE.
            # The headline is the value-read completion rate and the feed
            # rate is recorded beside it.
            float(loss)
            dt = time.perf_counter() - t0
        finally:
            loader.close()
        best_rows = max(best_rows, rows / dt)
        best_feed = max(best_feed, rows / dt_submit)
        best_mb = max(best_mb, size_mb / dt)
    return best_rows, best_mb, best_feed, float(loss), kstep_used


def bench_deepfm_train() -> dict:
    """DeepFM end-to-end training stream (VERDICT r3 #3: at least one
    FFM/DeepFM step must complete on TPU): same feed as fm_train plus the
    dense tower — the config whose step actually exercises the MXU."""
    from dmlc_core_tpu.models.deep import DeepFM

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    rows_s, mbps, feed_s, loss, kstep_used = _train_rate(
        DeepFM(num_features=1 << 20, dim=32, layers=2), path, "libsvm")
    import optax as _optax
    r = {"metric": "deepfm_train_stream", "value": round(rows_s, 0),
         "unit": "rows/s",
         "kstep": kstep_used, "text_mbps": round(mbps, 1),
         "feed_rows_s": round(feed_s, 0), "final_loss": round(loss, 4)}
    r.update(_mfu_fields(DeepFM(num_features=1 << 20, dim=32, layers=2),
                         _optax.adam(1e-3), rows_s))
    return r


def bench_dcn_train() -> dict:
    """DCNv2 end-to-end training stream: one sparse gather then L dense
    [D,D] cross matmuls per step — the family member whose per-step work
    is almost entirely MXU."""
    from dmlc_core_tpu.models.dcn import DCNv2

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    rows_s, mbps, feed_s, loss, kstep_used = _train_rate(
        DCNv2(num_features=1 << 20, dim=32, layers=3), path, "libsvm")
    import optax as _optax
    r = {"metric": "dcn_train_stream", "value": round(rows_s, 0),
         "unit": "rows/s",
         "kstep": kstep_used, "text_mbps": round(mbps, 1),
         "feed_rows_s": round(feed_s, 0), "final_loss": round(loss, 4)}
    r.update(_mfu_fields(DCNv2(num_features=1 << 20, dim=32, layers=3),
                         _optax.adam(1e-3), rows_s))
    return r


def bench_ffm_train() -> dict:
    """FieldAwareFM training stream over libfm data with the per-value
    field ids shipped to the device (fields=True path — the libfm third
    coordinate finally consumed on chip, VERDICT r3 #3)."""
    from dmlc_core_tpu.models.ffm import FieldAwareFM

    path = "/tmp/bench_suite.libfm"
    _gen_libsvm(path, libfm=True)
    # id_mod bounds the [F, nf, d] factor table (+ its two adam moments)
    # to ~0.5 GB on chip; the generator's fields are j % 40
    rows_s, mbps, feed_s, loss, kstep_used = _train_rate(
        FieldAwareFM(num_features=1 << 18, num_fields=40, dim=4),
        path, "libfm", fields=True, id_mod=1 << 18)
    return {"metric": "ffm_train_stream", "value": round(rows_s, 0),
            "unit": "rows/s",
            "kstep": kstep_used, "text_mbps": round(mbps, 1),
            "feed_rows_s": round(feed_s, 0), "final_loss": round(loss, 4)}


def bench_a1a_train() -> dict:
    """a1a-shaped real-data config (VERDICT r4 #4; zero-egress image, so
    the corpus is a documented distribution-matched generator —
    benchmarks/realdata.py): tiny Adult-style one-hot rows through the
    full train path, reporting HELD-OUT accuracy/AUC beside the rate
    (the eval split is generated with a different sample seed over the
    same fixed ground-truth weights, mirroring the real a1a/a1a.t train/
    test pair)."""
    import jax
    import optax
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.models import (FactorizationMachine, evaluate_stream,
                                      make_train_step)
    from dmlc_core_tpu.pipeline import DeviceLoader
    from benchmarks.realdata import gen_a1a

    path = "/tmp/bench_a1a.libsvm"
    test_path = "/tmp/bench_a1a_test.libsvm"
    gen_a1a(path)
    gen_a1a(test_path, rows=800, seed=11)
    model = FactorizationMachine(num_features=124, dim=8)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(5e-2)
    opt_state = opt.init(params)
    step = make_train_step(model, opt)
    t0 = time.perf_counter()
    rows = 0
    loss = None
    for _ in range(5):                       # tiny corpus: 5 epochs
        loader = DeviceLoader(create_parser(f"file://{path}", 0, 1,
                                            "libsvm"),
                              batch_rows=256, nnz_cap=8192)
        try:
            for batch in loader:
                params, opt_state, loss = step(params, opt_state, batch)
                rows += int(batch["labels"].shape[0])
        finally:
            loader.close()
    float(loss)                              # value read-back = completion
    dt = time.perf_counter() - t0
    loader = DeviceLoader(create_parser(f"file://{test_path}", 0, 1,
                                        "libsvm"),
                          batch_rows=256, nnz_cap=8192)
    try:
        ev = evaluate_stream(model, params, loader)
    finally:
        loader.close()
    return {"metric": "a1a_train_stream", "value": round(rows / dt, 0),
            "unit": "rows/s", "data": "a1a-shaped",
            "heldout_accuracy": round(ev["accuracy"], 4),
            "heldout_auc": round(ev.get("auc", 0.0), 4)}


def bench_higgs_csv() -> dict:
    """HIGGS-shaped dense CSV parse (VERDICT r4 #4): 28 physics columns at
    full float precision through the native chunk parser — the dense-parse
    benchmark the reference runs on the real HIGGS file."""
    from benchmarks.realdata import gen_higgs_csv

    path = "/tmp/bench_higgs.csv"
    gen_higgs_csv(path, target_mb=TARGET_MB)
    size_mb = os.path.getsize(path) / MB
    from dmlc_core_tpu.data import create_parser
    best = 0.0
    rows = 0
    for _ in range(3):
        p = create_parser(f"file://{path}?format=csv&label_column=0", 0, 1,
                          "csv")
        t0 = time.perf_counter()
        rows = sum(c.get_block().size for c in p)
        dt = time.perf_counter() - t0
        p.close()
        best = max(best, size_mb / dt)
    return {"metric": "higgs_csv_parse", "value": round(best, 1),
            "unit": "MB/s", "data": "HIGGS-shaped", "rows": rows}


def _wire_v4_projection(path: str, fmt: str, batch_rows: int = 4096) -> dict:
    """Measure what delta-coded ids (the rejected wire v4) WOULD save on
    this corpus, from the parsed CSR itself (no wire implementation
    needed for a keep/reject decision).

    v3 ships every id at ``w = bits(max_id_in_batch)``.  The v4 proposal:
    per row, first id absolute at w bits, subsequent ids as (delta-1) at
    ``d = bits(max_within_row_delta_in_batch)`` — batch-global widths,
    like v3 (`NOTES_r04.md` item 3 rejected this on uniform ids because a
    single max-gap row drags d up to ~w; field-clustered data is the case
    it was deferred to)."""
    import numpy as np

    from dmlc_core_tpu.data import create_parser

    id_bits_v3 = id_bits_v4 = 0
    total_nnz = total_first = 0
    batches = 0
    p = create_parser(f"file://{path}", 0, 1, fmt)
    try:
        ids_acc, off_acc = [], [0]
        for c in p:
            blk = c.get_block()
            lo = int(blk.offsets[0])
            ids_acc.append(np.asarray(blk.indices, np.int64)[
                lo:int(blk.offsets[-1])])
            off_acc.extend((np.asarray(blk.offsets, np.int64)[1:]
                            - lo + off_acc[-1]).tolist())
            while len(off_acc) - 1 >= batch_rows:
                cut = off_acc[batch_rows]
                flat = np.concatenate(ids_acc)
                batch_ids, rest = flat[:cut], flat[cut:]
                rp = np.array(off_acc[:batch_rows + 1], np.int64)
                off_acc = [0] + [o - cut for o in off_acc[batch_rows + 1:]]
                ids_acc = [rest]
                nnz = len(batch_ids)
                if nnz == 0:
                    continue
                w = max(1, int(np.max(batch_ids)).bit_length())
                deltas = np.diff(batch_ids)
                # row-first positions are absolute, not deltas
                firsts = rp[:-1][np.diff(rp) > 0]
                mask = np.ones(max(nnz - 1, 0), bool)
                mask[firsts[firsts > 0] - 1] = False
                d = max(1, int(np.max(deltas[mask] - 1)).bit_length()) \
                    if mask.any() else 1
                n_first = len(firsts)
                id_bits_v3 += nnz * w
                id_bits_v4 += n_first * w + (nnz - n_first) * d
                total_nnz += nnz
                total_first += n_first
                batches += 1
    finally:
        p.close()
    ratio = id_bits_v4 / max(id_bits_v3, 1)
    return {"batches": batches, "nnz": total_nnz,
            "v3_id_bits_per_value": round(id_bits_v3 / max(total_nnz, 1), 2),
            "v4_id_bits_per_value": round(id_bits_v4 / max(total_nnz, 1), 2),
            "v4_over_v3_id_bytes": round(ratio, 3)}


def bench_criteo_ingest() -> dict:
    """Criteo-shaped field-clustered libfm ingest (VERDICT r4 #4) + the
    wire-v4 delta-coding re-evaluation on the id distribution it was
    deferred to.  The verdict rides in the artifact: adopt only if the
    projected id-region saving moves TOTAL wire bytes by >10% (ids are
    roughly half the compact wire; values/row_ptr/labels are untouched by
    v4)."""
    from benchmarks.realdata import gen_criteo_libfm

    path = "/tmp/bench_criteo.libfm"
    gen_criteo_libfm(path, target_mb=TARGET_MB)
    v = _ingest_rate(f"file://{path}", "libfm")
    proj = _wire_v4_projection(path, "libfm")
    uniform = "/tmp/bench_suite.libfm"
    _gen_libsvm(uniform, libfm=True)
    proj_uniform = _wire_v4_projection(uniform, "libfm")
    # id region ≈ half the wire → total saving ≈ (1 - ratio) / 2
    total_saving = (1.0 - proj["v4_over_v3_id_bytes"]) / 2.0
    verdict = "adopt" if total_saving > 0.10 else "reject"
    return {"metric": "criteo_libfm_ingest", "value": round(v, 1),
            "unit": "MB/s", "data": "criteo-shaped",
            "wire_v4": {**proj, "uniform_corpus_ratio":
                        proj_uniform["v4_over_v3_id_bytes"],
                        "projected_total_wire_saving":
                            round(total_saving, 3),
                        "verdict": verdict}}


def bench_integrity() -> dict:
    """Bit-exact end-to-end data integrity through the DEVICE path.
    Host-side parsed blocks and on-device decoded batches are checksummed with
    wrapping-int32 sums over the exact bit patterns (bitcast f32→i32;
    order- and padding-immune: pad ids/vals/labels/weights are all 0),
    through the stress transfer config (fused native parse→pack, compact
    v3 bit-pack + dict encode, 4-thread put pool, jit decode).  A single
    flipped bit anywhere in that chain fails the compare."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader

    M32 = 0xFFFFFFFF

    def wsum(a) -> int:                  # wrapping 32-bit reference sum
        return int(np.sum(np.asarray(a).astype(np.int64)) & M32)

    bits = np.float32(1.0).view(np.int32)          # weights default

    def host_sums(path: str, fmt: str, want_fields: bool) -> dict:
        """Host-side reference checksums of one corpus."""
        keys = ("ids", "vals", "labels", "weights") + (
            ("fields",) if want_fields else ())
        host = dict.fromkeys(keys + ("nnz", "rows"), 0)
        p = create_parser(f"file://{path}", 0, 1, fmt)
        try:
            for c in p:
                blk = c.get_block()
                # slice the CSR payload via offsets, exactly like
                # pack_flat does: a view-backed block must not leak
                # out-of-block elements into the host checksum — that
                # would be a false corruption alarm, not a detection
                lo, hi = int(blk.offsets[0]), int(blk.offsets[-1])
                host["ids"] = (host["ids"]
                               + wsum(blk.indices[lo:hi])) & M32
                host["vals"] = (host["vals"] + wsum(
                    blk.values[lo:hi].view(np.int32))) & M32
                host["labels"] = (host["labels"]
                                  + wsum(blk.labels.view(np.int32))) & M32
                w = (blk.weights.view(np.int32) if blk.weights is not None
                     else np.full(blk.size, bits, np.int32))
                host["weights"] = (host["weights"] + wsum(w)) & M32
                if want_fields:
                    host["fields"] = (host["fields"]
                                      + wsum(blk.fields[lo:hi])) & M32
                host["nnz"] += hi - lo
                host["rows"] += blk.size
        finally:
            p.close()
        return host

    def check_one(path: str, fmt: str, want_fields: bool) -> dict:
        keys = ("ids", "vals", "labels", "weights") + (
            ("fields",) if want_fields else ())
        host = host_sums(path, fmt, want_fields)

        @jax.jit
        def batch_sums(b):
            i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731
            out = [jnp.sum(b["ids"]), jnp.sum(i32(b["vals"])),
                   jnp.sum(i32(b["labels"])), jnp.sum(i32(b["weights"]))]
            if want_fields:
                out.append(jnp.sum(b["fields"]))
            if "row_ptr" in b:
                out.append(b["row_ptr"][-1])
            else:
                # per-array path ships segments, not row_ptr; padding
                # entries point at the scratch row (== batch_rows)
                out.append(jnp.sum(
                    (b["segments"] < b["labels"].shape[0])
                    .astype(jnp.int32)))
            return tuple(out)

        dev = dict.fromkeys(keys + ("nnz",), 0)
        # nnz_cap sized so no row is truncated anywhere: host ref has no
        # truncation
        loader = DeviceLoader(create_parser(f"file://{path}", 0, 1, fmt),
                              batch_rows=4096, nnz_cap=262144, prefetch=4,
                              put_threads=4, wire_compact=not want_fields,
                              fields=want_fields)
        try:
            for b in loader:
                s = [int(np.asarray(x)) for x in batch_sums(b)]
                for k, v in zip(keys, s):
                    dev[k] = (dev[k] + (v & M32)) & M32
                dev["nnz"] += s[-1]
            rows = loader.stats.rows
        finally:
            loader.close()

        mismatch = {k: {"host": host[k], "device": dev[k]}
                    for k in keys + ("nnz",) if host[k] != dev[k]}
        if rows != host["rows"]:
            mismatch["rows"] = {"host": host["rows"], "device": rows}
        out = {"ok": not mismatch, "rows": host["rows"],
               "nnz": host["nnz"]}
        if mismatch:
            out["mismatch"] = mismatch
        return out

    libsvm = "/tmp/bench_suite.libsvm"
    libfm = "/tmp/bench_suite.libfm"
    _gen_libsvm(libsvm)
    _gen_libsvm(libfm, libfm=True)
    # two sub-checks cover both transfer paths a consumer can configure:
    # the fused compact wire (libsvm) and the per-array fields path
    # (libfm, fields=True — field arrays bypass the fused wire by design)
    res = {"libsvm_compact": check_one(libsvm, "libsvm", False),
           "libfm_fields": check_one(libfm, "libfm", True)}
    ok = all(v["ok"] for v in res.values())
    return {"metric": "ingest_integrity", "value": 1.0 if ok else 0.0,
            "unit": "ok", "paths": res}


def bench_cache_build() -> dict:
    """Disk-cache build + replay throughput — the reference's
    ``disk_row_iter.h:117-140`` self-report ("MB/sec per 64MB page",
    BASELINE.md instrumentation table), the one baseline hook the suite
    did not yet reproduce.  Build: one parse of the libsvm corpus into
    cache pages; replay: epochs off the cache through the prefetch
    thread, best-of-2 (page deserialization + ThreadedIter, no parsing).
    Pure host/disk path — never touches a device."""
    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.data.iterators import DiskRowIter

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    cache = "/tmp/bench_suite.cache"
    for sfx in ("", ".meta"):
        try:
            os.remove(cache + sfx)
        except OSError:
            pass
    t0 = time.perf_counter()
    it = DiskRowIter(create_parser(f"file://{path}", 0, 1, "libsvm"), cache)
    build_mbps = size_mb / (time.perf_counter() - t0)
    best_dt = float("inf")
    rows = 0
    try:
        for _ in range(2):
            it.before_first()
            rows = 0
            t0 = time.perf_counter()
            for blk in it:
                rows += blk.size
            best_dt = min(best_dt, time.perf_counter() - t0)
    finally:
        it.close()
    cache_mb = os.path.getsize(cache) / MB
    # two replay normalizations, both labeled: source-equivalent answers
    # "how much faster than re-parsing the text" (same denominator as the
    # build rate), cache-bytes is comparable to stream_read/recordio raw
    # IO rates
    return {"metric": "cache_build_replay", "value": round(build_mbps, 1),
            "unit": "MB/s",
            "replay_src_equiv_mbps": round(size_mb / best_dt, 1),
            "replay_cache_mbps": round(cache_mb / best_dt, 1),
            "rows": rows, "cache_mb": round(cache_mb, 1)}


def bench_csv() -> dict:
    path = "/tmp/bench_suite.csv"
    _gen_csv(path)
    from dmlc_core_tpu.data import create_parser
    size_mb = os.path.getsize(path) / MB
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        p = create_parser(f"file://{path}?label_column=0", 0, 1, "csv")
        for _blk in p:
            pass
        p.close()
        best = max(best, size_mb / (time.perf_counter() - t0))
    return {"metric": "csv_parse_rowblocks", "value": round(best, 1),
            "unit": "MB/s"}


def bench_recordio() -> dict:
    """.rec streaming: write records, then partitioned read (reference
    recordio_test.cc + split_read_test.cc instrumentation)."""
    import numpy as np
    from dmlc_core_tpu.io import RecordIOWriter, create_input_split
    path = "/tmp/bench_suite.rec"
    rng = np.random.default_rng(0)
    if not (os.path.exists(path)
            and os.path.getsize(path) >= TARGET_MB * MB * 0.9):
        with open(path, "wb") as f:
            w = RecordIOWriter(f)
            written = 0
            while written < TARGET_MB * MB:
                rec = rng.integers(0, 256, size=int(rng.integers(
                    1 << 10, 64 << 10)), dtype=np.uint8).tobytes()
                w.write_record(rec)
                written += len(rec)
    size_mb = os.path.getsize(path) / MB
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        total = 0
        for part in range(2):
            sp = create_input_split(f"file://{path}", part, 2, "recordio",
                                    threaded=True)
            while True:
                rec = sp.next_record()
                if rec is None:
                    break
                total += len(rec)
            sp.close()
        best = max(best, (total / MB) / (time.perf_counter() - t0))
    return {"metric": "recordio_partitioned_read", "value": round(best, 1),
            "unit": "MB/s"}


def _remote_ingest_rate(nworkers: int, attempts: int = 3) -> float:
    """Spawn ``nworkers`` ingest worker subprocesses (one partition each)
    and measure MB/s into device batches at the trainer, whose own parse
    stays idle — the tf.data-service shape."""
    import socket
    import subprocess
    import sys as _sys
    import bench
    from dmlc_core_tpu.pipeline import RemoteIngestLoader

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    ports = []
    for _ in range(nworkers):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    workers = [subprocess.Popen(
        [_sys.executable, "-m", "dmlc_core_tpu.pipeline.ingest_service",
         f"file://{path}", str(i), str(nworkers), "libsvm", str(port),
         "batch_rows=4096", "nnz_cap=131072"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for i, port in enumerate(ports)]
    try:
        # wait for the workers' listeners before timing anything
        deadline = time.monotonic() + 120
        for port in ports:
            while True:
                try:
                    socket.create_connection(("127.0.0.1", port),
                                             timeout=2).close()
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"ingest worker :{port} never came up")
                    time.sleep(0.5)
        best = 0.0
        for attempt in range(attempts):
            loader = RemoteIngestLoader(
                [("127.0.0.1", p) for p in ports], batch_rows=4096,
                connect_timeout=120.0)
            acc = None
            t0 = time.perf_counter()
            for b in loader:
                acc = bench.consume_batch(acc, b)
            bench.prove_consumed(acc)
            dt = time.perf_counter() - t0
            loader.close()
            best = max(best, size_mb / dt)
        return best
    finally:
        for w in workers:
            w.kill()


def bench_remote_ingest() -> dict:
    """Disaggregated ingest at the r2/r3 artifact shape (2 workers).  NOT
    in the default run order — ingest_scale's workers_2 point measures the
    same configuration; this stays invocable by name for artifact
    continuity."""
    best = _remote_ingest_rate(2)
    return {"metric": "remote_ingest_2workers", "value": round(best, 1),
            "unit": "MB/s"}


def bench_ingest_scale() -> dict:
    """Worker-count scaling curve (VERDICT r3 #5): local parse vs N ingest
    workers feeding a parse-idle trainer, N = 1/2/4.  On a multi-core host
    2+ workers must beat 1 worker AND local; on a 1-core host every
    configuration time-slices the same core, so the curve records the
    disaggregation overhead, not the scaling — stamped via host_cores."""
    import bench
    cores = bench.host_cores()
    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    curve = {"local": round(_ingest_rate(f"file://{path}", "libsvm"), 1)}
    for n in (1, 2, 4):
        curve[f"workers_{n}"] = round(_remote_ingest_rate(n, attempts=2), 1)
    r = {"metric": "ingest_worker_scaling", "value": curve["workers_2"],
         "unit": "MB/s", "curve": curve, "host_cores": cores}
    if cores == 1:
        r["note"] = ("1-core host: trainer and all workers share one core; "
                     "curve measures disaggregation overhead, not scaling")
    return r


def _merge_child_telemetry(tag: str, states=None, trace_files=()) -> None:
    """Fold child-process telemetry into parent artifacts when
    ``--telemetry-out`` is live: ``<prefix>_<tag>.fleet_metrics.json``
    (``merge_states`` over the rank-tagged registry states) and
    ``<prefix>_<tag>.fleet_trace.json`` (child Chrome traceEvents
    concatenated into one Perfetto-openable timeline).  Never raises —
    telemetry must not fail a bench."""
    prefix = os.environ.get("DMLC_TELEMETRY_OUT")
    if not prefix:
        return
    try:
        from dmlc_core_tpu import telemetry
        if states:
            path = f"{prefix}_{tag}.fleet_metrics.json"
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"ranks": sorted(states),
                           "merged": telemetry.merge_states(states)},
                          f, indent=2, sort_keys=True, default=str)
            os.replace(tmp, path)
        events = []
        for p in trace_files:
            try:
                with open(p, "r", encoding="utf-8") as f:
                    events.extend(json.load(f).get("traceEvents", []))
            except (OSError, ValueError):
                continue  # child died before its dump — merge the rest
        if events:
            path = f"{prefix}_{tag}.fleet_trace.json"
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                          f)
            os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 — telemetry never fails a run
        log(f"fleet telemetry merge failed: {e}")


def _fleet_ingest_rate(nworkers: int, num_parts: int = 6,
                       attempts: int = 2, batch_rows: int = 4096) -> float:
    """One dispatcher + ``nworkers`` data-service worker subprocesses
    pulling shard leases for a shared dataset; measure aggregate MB/s of
    fused host frames arriving at a single ``DataServiceLoader``
    consumer.  Differs from ``_remote_ingest_rate`` in the control
    plane: parts are leased dynamically (any worker can serve any
    shard), not statically assigned one-per-worker."""
    import subprocess
    import sys as _sys
    from dmlc_core_tpu.pipeline.data_service import (DataServiceLoader,
                                                     Dispatcher)

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    # generous TTL/heartbeat: a loaded 1-core host must not trip the
    # chaos machinery (a re-grant mid-bench would double-serve bytes and
    # corrupt the MB/s number via dup-frame discards)
    disp = Dispatcher(lease_ttl_s=600.0, heartbeat_timeout_s=120.0)
    disp.start()
    workers = [subprocess.Popen(
        [_sys.executable, "-m", "dmlc_core_tpu.pipeline.data_service.worker",
         f"127.0.0.1:{disp.port}"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for _ in range(nworkers)]
    try:
        deadline = time.monotonic() + 120
        while len(disp.workers_alive()) < nworkers:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"only {len(disp.workers_alive())}/{nworkers} "
                    f"data-service workers registered")
            time.sleep(0.25)
        spec = {"uri": f"file://{path}", "fmt": "libsvm",
                "num_parts": num_parts, "batch_rows": batch_rows,
                "nnz_cap": 131072}
        best = 0.0
        for _ in range(attempts):
            loader = DataServiceLoader((disp.host, disp.port), spec,
                                       connect_timeout=120.0, emit="host")
            frames = 0
            t0 = time.perf_counter()
            for _kind, buf, _meta, _rows in loader:
                frames += 1
                loader.recycle(buf)
            dt = time.perf_counter() - t0
            loader.close()
            if frames == 0:
                raise RuntimeError("fleet epoch delivered no frames")
            best = max(best, size_mb / dt)
        return best
    finally:
        if os.environ.get("DMLC_TELEMETRY_OUT"):
            # grab the heartbeat-pushed registry states BEFORE teardown,
            # then SIGTERM (not SIGKILL) so each worker's exit hook dumps
            # its own metrics/trace pair for the fleet merge
            try:
                states = disp.worker_states()
            except Exception:  # noqa: BLE001 — telemetry never fails a run
                states = {}
            for w in workers:
                w.terminate()
            for w in workers:
                try:
                    w.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    w.kill()
            prefix = os.environ["DMLC_TELEMETRY_OUT"]
            # the exit-dump state sidecars are authoritative: complete
            # final states, present even when the run ended before any
            # heartbeat push reached the dispatcher.  Counting a worker
            # via BOTH its sidecar and its heartbeat state would double
            # its counters in the merge, so sidecars replace wholesale.
            sidecars = {}
            for w in workers:
                try:
                    p = f"{prefix}.dsworker.{w.pid}.state.json"
                    with open(p, "r", encoding="utf-8") as f:
                        sidecars[f"pid{w.pid}"] = json.load(f)
                except (OSError, ValueError):
                    continue
            _merge_child_telemetry(
                f"ingest_fleet.{nworkers}w", states=sidecars or states,
                trace_files=[f"{prefix}.dsworker.{w.pid}.trace.json"
                             for w in workers])
        else:
            for w in workers:
                w.kill()
        disp.stop()


def _fleet_failover_s(num_parts: int = 6) -> float:
    """Dispatcher HA drill: run the dispatcher as a *subprocess* with a
    journal, SIGKILL it after the consumer has taken its first frames,
    restart it on the same port + journal, and measure kill→recovered
    (new process answering a ``status`` RPC with the epoch's state
    replayed).  The consumer keeps iterating across the outage — its
    control-plane retries ride over the dead window — so the epoch also
    completing (frames > 0 after the kill) is part of the drill, not a
    separate test."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    from dmlc_core_tpu.pipeline.data_service import DataServiceLoader
    from dmlc_core_tpu.pipeline.data_service.dispatcher import dispatcher_rpc

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    tmp = tempfile.mkdtemp(prefix="dmlc_failover_")
    journal = os.path.join(tmp, "dispatch")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           # fast re-registration beats: the drill's clock includes the
           # worker noticing the new dispatcher
           "DMLC_DATA_HEARTBEAT_TIMEOUT": "3"}

    def _spawn_dispatcher(port: int) -> Tuple[subprocess.Popen, int]:
        proc = subprocess.Popen(
            [_sys.executable, "-m",
             "dmlc_core_tpu.pipeline.data_service.dispatcher",
             f"port={port}", f"journal={journal}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
        line = proc.stdout.readline()
        return proc, int(json.loads(line)["port"])

    disp, port = _spawn_dispatcher(0)
    worker = subprocess.Popen(
        [_sys.executable, "-m", "dmlc_core_tpu.pipeline.data_service.worker",
         f"127.0.0.1:{port}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    # the consumer must out-retry the dead window: the default policy
    # gives up in ~seconds and the breaker would stop redialing the
    # (innocent) worker while its completions bounce off a dead control
    # plane
    chaos_env = {"DMLC_DATA_CLIENT_RETRIES": "40",
                 "DMLC_DATA_CLIENT_BREAKER_THRESHOLD": "1000",
                 "DMLC_DS_CTRL_RETRIES": "40"}
    saved = {k: os.environ.get(k) for k in chaos_env}
    os.environ.update(chaos_env)
    try:
        # the worker's interpreter start-up is seconds on a loaded host;
        # the consumer's first start_epoch must not race it to the
        # registry
        deadline = time.monotonic() + 120
        while True:
            try:
                if dispatcher_rpc(("127.0.0.1", port),
                                  {"cmd": "list_workers"},
                                  timeout=2.0)["workers"]:
                    break
            except (OSError, ValueError, KeyError):
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("data-service worker never registered "
                                   "for the failover drill")
            time.sleep(0.25)
        spec = {"uri": f"file://{path}", "fmt": "libsvm",
                "num_parts": num_parts, "batch_rows": 4096,
                "nnz_cap": 131072}
        loader = DataServiceLoader(("127.0.0.1", port), spec,
                                   connect_timeout=120.0, emit="host")
        it = iter(loader)
        frames = 0
        for _kind, buf, _meta, _rows in it:
            frames += 1
            loader.recycle(buf)
            if frames >= 2:
                break  # mid-epoch: leases granted, parts outstanding
        disp.kill()
        disp.wait()
        t0 = time.perf_counter()
        disp, port2 = _spawn_dispatcher(port)
        deadline = time.monotonic() + 120
        while True:
            try:
                st = dispatcher_rpc(("127.0.0.1", port2),
                                    {"cmd": "status", "key": loader.key},
                                    timeout=2.0)
                if int(st.get("epoch", 0)) >= 1:
                    break  # journal replayed: the epoch survived the crash
            except (OSError, ValueError, KeyError):
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("restarted dispatcher never recovered")
            time.sleep(0.05)
        failover = time.perf_counter() - t0
        for _kind, buf, _meta, _rows in it:
            frames += 1
            loader.recycle(buf)
        loader.close()
        if frames <= 2:
            raise RuntimeError("epoch did not resume after failover")
        return failover
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        worker.kill()
        disp.kill()
        worker.wait()
        disp.wait()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_ingest_fleet() -> dict:
    """Data-service fleet scaling + HA: dispatcher + N leased workers
    feeding one consumer, N = 1/2/3, plus a SIGKILL failover drill
    against a journaled dispatcher subprocess.

    On a multi-core host 3 workers should deliver ≥ 1.6× the 1-worker
    aggregate MB/s; on a host with fewer cores than workers every
    process time-slices the same core, so the curve records the
    lease/control-plane overhead, not fleet scaling — in that case the
    ``speedup_3v1`` keys are OMITTED (not stamped at ~1.0), so the
    regression gate never judges scaling a core-starved host cannot
    exhibit (host_cores records why).  The parser-bound variant shrinks
    ``batch_rows`` 8× so per-batch parse/framing overhead dominates the
    wire — the regime where extra workers pay off first."""
    import bench
    cores = bench.host_cores()
    curve = {}
    for n in (1, 2, 3):
        curve[f"workers_{n}"] = round(_fleet_ingest_rate(n), 1)
    parser = {}
    for n in (1, 3):
        parser[f"workers_{n}"] = round(
            _fleet_ingest_rate(n, batch_rows=512), 1)
    r = {"metric": "ingest_fleet_mb_s", "value": curve["workers_3"],
         "unit": "MB/s", "curve": curve, "curve_parser_bound": parser,
         "dispatcher_failover_s": round(_fleet_failover_s(), 3),
         "host_cores": cores}
    if cores >= 3:
        r["speedup_3v1"] = round(curve["workers_3"]
                                 / max(1e-9, curve["workers_1"]), 2)
        r["parser_speedup_3v1"] = round(parser["workers_3"]
                                        / max(1e-9, parser["workers_1"]), 2)
    else:
        r["note"] = (f"{cores}-core host: dispatcher, consumer and all "
                     "workers share the core(s); curve measures "
                     "data-service overhead, not fleet scaling — "
                     "speedup keys omitted")
    return r


def _colocated_rate(mode: str, epochs: int = 1) -> Tuple[float, dict]:
    """One dispatcher + ONE worker subprocess on this host, one consumer;
    measure MB/s of the LAST epoch under a transport mode:

    * ``tcp``    — lanes disabled (`DMLC_TRANSPORT_LANE=0`), the seed's
      per-connection TCP path;
    * ``uds``    — default negotiation: colocated consumer dials the
      worker's UNIX lane, payload still streamed;
    * ``fdpass`` — UNIX lane + a page-cache-backed shard: epoch 1 builds
      the cache, epoch 2 ships one SCM_RIGHTS descriptor per shard.
    """
    import subprocess
    import sys as _sys
    from dmlc_core_tpu.pipeline.data_service import (DataServiceLoader,
                                                     Dispatcher)
    from dmlc_core_tpu.utils.metrics import metrics as _metrics

    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    overrides = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    if mode == "tcp":
        overrides["DMLC_TRANSPORT_LANE"] = "0"
    spec = {"uri": f"file://{path}", "fmt": "libsvm", "num_parts": 1,
            "batch_rows": 4096, "nnz_cap": 131072}
    if mode == "fdpass":
        spec["cache"] = f"/tmp/bench_colocated_{os.getpid()}.pages"
        epochs = max(2, epochs)
    old_env = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    disp = Dispatcher(lease_ttl_s=600.0, heartbeat_timeout_s=120.0)
    disp.start()
    worker = subprocess.Popen(
        [_sys.executable, "-m",
         "dmlc_core_tpu.pipeline.data_service.worker",
         f"127.0.0.1:{disp.port}"],
        env={**os.environ, **overrides},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    extras = {}
    try:
        deadline = time.monotonic() + 120
        while len(disp.workers_alive()) < 1:
            if time.monotonic() > deadline:
                raise RuntimeError("colocated worker never registered")
            time.sleep(0.25)
        z0 = _metrics.counter("transport.bytes_zero_copy").value
        u0 = _metrics.counter("transport.lane.uds").value
        rate = 0.0
        loader = DataServiceLoader((disp.host, disp.port), spec,
                                   connect_timeout=120.0, emit="host")
        try:
            for _ in range(epochs):
                frames = 0
                t0 = time.perf_counter()
                for _kind, buf, _meta, _rows in loader:
                    frames += 1
                    loader.recycle(buf)
                dt = time.perf_counter() - t0
                if frames == 0:
                    raise RuntimeError("colocated epoch had no frames")
                rate = size_mb / dt
        finally:
            loader.close()
        extras["uds_dials"] = int(
            _metrics.counter("transport.lane.uds").value - u0)
        extras["zero_copy_bytes"] = int(
            _metrics.counter("transport.bytes_zero_copy").value - z0)
        return rate, extras
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        worker.kill()
        disp.stop()
        if mode == "fdpass":
            for suffix in ("", ".meta.json"):
                try:
                    os.remove(spec["cache"] + suffix)
                except OSError:
                    pass


def bench_ingest_colocated() -> dict:
    """Transport-lane comparison (ISSUE 15): same host, same dataset, one
    worker feeding one consumer over (a) per-connection TCP, (b) the
    negotiated UNIX-domain lane, (c) the lane with SCM_RIGHTS fd-passing
    of the packed-page cache.  The lane must not lose to TCP; fd-passing
    removes the payload bytes from the wire entirely."""
    import bench
    tcp, _ = _colocated_rate("tcp")
    uds, uex = _colocated_rate("uds")
    fdp, fex = _colocated_rate("fdpass")
    return {"metric": "ingest_colocated_uds_mb_s", "value": round(uds, 1),
            "unit": "MB/s",
            "tcp_mb_s": round(tcp, 1), "uds_mb_s": round(uds, 1),
            "fdpass_mb_s": round(fdp, 1),
            "uds_vs_tcp_speedup": round(uds / max(tcp, 1e-9), 2),
            "fdpass_vs_tcp_speedup": round(fdp / max(tcp, 1e-9), 2),
            "uds_dials": uex["uds_dials"],
            "fdpass_zero_copy_bytes": fex["zero_copy_bytes"],
            "host_cores": bench.host_cores()}


def bench_stream() -> dict:
    """Raw SeekStream read throughput at several buffer sizes (reference
    `test/stream_read_test.cc:16-43` instrumentation) — isolates the L3
    byte-pump from parse/pack so a regression there is attributable."""
    from dmlc_core_tpu.io import open_seek_stream_for_read
    path = "/tmp/bench_suite.libsvm"
    _gen_libsvm(path)
    size_mb = os.path.getsize(path) / MB
    out = {}
    for buf_kb in (4, 64, 1024):
        best = 0.0
        for _ in range(3):
            s = open_seek_stream_for_read(f"file://{path}")
            t0 = time.perf_counter()
            while s.read(buf_kb << 10):
                pass
            best = max(best, size_mb / (time.perf_counter() - t0))
            s.close()
        out[f"buf{buf_kb}k_mbps"] = round(best, 1)
    return {"metric": "stream_read", "unit": "MB/s",
            "value": out["buf1024k_mbps"], **out}


def bench_allreduce() -> dict:
    """psum bus-bandwidth over all available devices (ICI on a pod; this
    host's devices otherwise). Bus BW = 2*(n-1)/n * bytes / time.

    Single-chip interpretation (defined per VERDICT r1 #7): with one
    device there is no inter-chip traffic to measure, so the config
    reports on-device copy bandwidth (d2d) instead — the upper bound any
    1-chip collective could move — and labels itself accordingly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    n = len(devs)
    elems = (TARGET_MB * MB) // 4
    if n == 1:
        # feedback chain + value read-back, RTT-corrected: 5 identical
        # copy(x) dispatches behind block_until_ready read 6661 GB/s on a
        # v5e (~0.8 TB/s HBM) in the 03:20 window — dedupe + early-resolving
        # ready-futures, the same two holes tpu_micro.timed_fb closes
        x = jnp.ones((elems,), jnp.float32)
        bump = jax.jit(lambda v: v + 1.0)     # full HBM read + write
        y = bump(x)
        float(y[0])                            # compile + land

        def rtt() -> float:
            t0 = time.perf_counter()
            float(y[0])
            return time.perf_counter() - t0

        rtt_s = min(rtt() for _ in range(3))
        reps = 256
        t0 = time.perf_counter()
        for _ in range(reps):
            y = bump(y)
        float(y[0])
        t = time.perf_counter() - t0
        dt = max(t - rtt_s, 0.05 * t, 1e-9)
        bw = reps * 2 * elems * 4 / dt / (1 << 30)
        return {"metric": "allreduce_singleton_d2d_bw", "value": round(bw, 2),
                "unit": "GB/s", "devices": 1, "reps": reps,
                "rtt_ms": round(rtt_s * 1e3, 1),
                "note": "1 device: no ICI traffic; reporting on-device "
                        "copy bandwidth as the collective upper bound"}
    mesh = Mesh(np.array(devs), ("dp",))
    x = jnp.ones((elems,), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(None)))

    @jax.jit
    def psum_all(v):
        # the +1.0 rides INSIDE the jitted program (fused by XLA, no
        # extra eager HBM pass) and keeps every dispatch's operand
        # distinct so the runtime cannot dedupe repeats
        return jax.shard_map(lambda t: jax.lax.psum(t, "dp") + 1.0, mesh=mesh,
                         in_specs=P(None), out_specs=P(None),
                         check_vma=False)(v)

    ys = psum_all(xs)                         # compile
    float(ys[0])

    def rtt() -> float:
        t0 = time.perf_counter()
        float(ys[0])
        return time.perf_counter() - t0

    rtt_s = min(rtt() for _ in range(3))
    reps = 16
    t0 = time.perf_counter()
    for _ in range(reps):
        ys = psum_all(ys)
    float(ys[0])                              # completion proof
    t = time.perf_counter() - t0
    dt = max(t - rtt_s, 0.05 * t, 1e-9)       # same floor as the n==1 branch
    bus = reps * (2 * (n - 1) / max(n, 1)) * (elems * 4) / dt / (1 << 30)
    return {"metric": "allreduce_bus_bw", "value": round(bus, 2),
            "unit": "GB/s", "devices": n, "reps": reps,
            "rtt_ms": round(rtt_s * 1e3, 1)}


def bench_allreduce_mesh8() -> dict:
    """8-way virtual-mesh psum wall time (VERDICT r2 weak#5): fixed-size
    collective on the forced-host 8-device mesh, so round-over-round
    movement of the collective path is visible even with one real chip.
    Runs in a subprocess — the virtual-device flag is process-global."""
    import subprocess
    code = (
        "import jax\n"
        "import time, numpy as np, jax.numpy as jnp\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "devs = jax.devices(); n = len(devs)\n"
        "mesh = Mesh(np.array(devs), ('dp',))\n"
        "x = jax.device_put(jnp.ones((4 << 20,), jnp.float32),\n"
        "                   NamedSharding(mesh, P('dp')))\n"
        "f = jax.jit(jax.shard_map(lambda t: jax.lax.psum(t, 'dp'), mesh=mesh,\n"
        "            in_specs=P('dp'), out_specs=P('dp'), check_vma=False))\n"
        "f(x).block_until_ready()\n"
        "best = 1e9\n"
        "for _ in range(5):\n"
        "    t0 = time.perf_counter(); f(x).block_until_ready()\n"
        "    best = min(best, time.perf_counter() - t0)\n"
        "print('RESULT', n, best)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"mesh8 child rc={out.returncode}: "
                           f"{out.stderr[-500:]}")
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.startswith("RESULT")), None)
    if line is None:
        raise RuntimeError(f"mesh8 child produced no RESULT; stderr: "
                           f"{out.stderr[-500:]}")
    _, n, sec = line.split()
    return {"metric": "allreduce_mesh8_psum_wall", "value": round(
        float(sec) * 1e3, 2), "unit": "ms", "devices": int(n),
        "note": "16MiB psum on the 8-device virtual host mesh"}


def bench_sp_mesh8() -> dict:
    """Sequence-parallel attention wall time on the 8-device virtual mesh:
    ring (ppermute + online softmax) vs Ulysses (all-to-all) on the same
    sharded QKV — the long-context analog of allreduce_mesh8, so the sp
    layer's round-over-round movement is visible with one real chip."""
    import subprocess
    code = (
        "import jax\n"
        "import time, numpy as np, jax.numpy as jnp\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "from dmlc_core_tpu.ops.ring_attention import make_ring_attention\n"
        "from dmlc_core_tpu.ops.ulysses import make_ulysses_attention\n"
        "devs = jax.devices(); n = len(devs)\n"
        "mesh = Mesh(np.array(devs), ('sp',))\n"
        "B, H, S, D = 1, 8, 2048, 64\n"
        "rng = np.random.default_rng(0)\n"
        "sh = NamedSharding(mesh, P(None, None, 'sp', None))\n"
        "qkv = [jax.device_put(rng.standard_normal((B, H, S, D),\n"
        "       dtype=np.float32), sh) for _ in range(3)]\n"
        "out = {}\n"
        "for name, mk in (('ring', make_ring_attention),\n"
        "                 ('ulysses', make_ulysses_attention)):\n"
        "    f = mk(mesh, 'sp', causal=True)\n"
        "    f(*qkv)[0].block_until_ready()\n"
        "    best = 1e9\n"
        "    for _ in range(5):\n"
        "        t0 = time.perf_counter(); f(*qkv).block_until_ready()\n"
        "        best = min(best, time.perf_counter() - t0)\n"
        "    out[name] = best\n"
        "print('RESULT', n, out['ring'], out['ulysses'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"sp_mesh8 child rc={out.returncode}: "
                           f"{out.stderr[-500:]}")
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.startswith("RESULT")), None)
    if line is None:
        raise RuntimeError(f"sp_mesh8 child produced no RESULT; stderr: "
                           f"{out.stderr[-500:]}")
    _, n, ring_s, uly_s = line.split()
    return {"metric": "sp_mesh8_attention_wall",
            "value": round(float(ring_s) * 1e3, 2), "unit": "ms",
            "ulysses_ms": round(float(uly_s) * 1e3, 2), "devices": int(n),
            "note": "B1 H8 S2048 D64 causal attention, seq sharded 8-way"}


_RESHARD_CHILD = r"""
import sys, time
import numpy as np
from dmlc_core_tpu.parallel import RabitContext
from dmlc_core_tpu.parallel.reshard import snapshot_tree, redistribute
from dmlc_core_tpu.utils.checkpoint import CheckpointManager

uri, port, jobid, tmp, mode = sys.argv[1:6]
ctx = RabitContext(uri, int(port), jobid=jobid)
mgr = CheckpointManager(tmp)
world = ctx.world_size
if mode == "reshard":
    snap = None
    if ctx.rank != world - 1:            # rank world-1 plays the reborn
        _, state = mgr.restore(step=0)
        snap = snapshot_tree(state)
    ctx.allreduce(np.zeros(1))           # align: measure the protocol,
    t0 = time.perf_counter()             # not rank start skew
    restored, st = redistribute(ctx, snap, generation=0)
    wall = time.perf_counter() - t0
    assert restored
    print("WALL %d %.6f %d %d %d" % (ctx.rank, wall, st.bytes_moved,
                                     st.leaves_from_peers,
                                     st.leaves_from_checkpoint), flush=True)
else:                                    # the old path: full reload
    ctx.allreduce(np.zeros(1))
    t0 = time.perf_counter()
    _, state = mgr.restore(step=0)
    for a in state.values():
        a[0, 0]                          # fault in, apples-to-apples
    wall = time.perf_counter() - t0
    print("WALL %d %.6f 0 0 0" % (ctx.rank, wall), flush=True)
ctx.shutdown()
import os
_prefix = os.environ.get("DMLC_TELEMETRY_OUT")
if _prefix:                              # --telemetry-out parity: each
    import json                          # rank leaves a metrics/trace
    from dmlc_core_tpu import telemetry  # pair + mergeable state for the
    from dmlc_core_tpu.utils.metrics import metrics  # parent fleet merge
    _p = "%s.reshard.%s.%s" % (_prefix, mode, jobid)
    telemetry.dump_artifacts(_p)
    with open(_p + ".state.json", "w") as f:
        json.dump(metrics.state(), f, default=str)
"""


def bench_elastic_reshard() -> dict:
    """Checkpoint-free recovery cost (ISSUE 9): wall time for the elastic
    resharder to hand a reborn rank the full state live from survivors,
    against the old path — every rank of the restarted cohort reloading
    the full checkpoint from disk (the restore stampede).  3 real worker
    PROCESSES over the tracker + loopback sockets (threads would share
    one GIL and throttle both sides of the transfer); state is replicated
    (the elastic-averaging layout of examples/elastic_train.py), the
    last rank plays the reborn non-holder.  Cost = the slowest rank's
    wall, barrier-aligned inside each child."""
    import subprocess
    import tempfile

    import numpy as np

    from dmlc_core_tpu.parallel import RabitTracker
    from dmlc_core_tpu.utils.checkpoint import CheckpointManager

    world = 3
    # default 4x the suite's data target: recovery cost only matters once
    # the state is big enough that a full-cohort reload visibly stalls
    # training, and fixed protocol costs (tracker rounds, ownership
    # broadcast, final allreduce) would dominate a tiny transfer
    state_mb = int(os.environ.get("DMLC_BENCH_RESHARD_MB",
                                  str(4 * TARGET_MB)))
    nleaves, cols = 8, 256
    rows = max(1, (state_mb * MB) // (4 * cols * nleaves))
    rng = np.random.default_rng(7)
    state = {f"layer{i}": rng.random((rows, cols), dtype=np.float32)
             for i in range(nleaves)}
    nbytes = sum(a.nbytes for a in state.values())

    def cohort(tmp: str, mode: str, extra_env=None):
        tracker = RabitTracker(num_workers=world, host_ip="127.0.0.1")
        tracker.start()
        envd = tracker.worker_envs()
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   **(extra_env or {}))
        procs = [subprocess.Popen(
            [sys.executable, "-c", _RESHARD_CHILD,
             envd["DMLC_TRACKER_URI"], str(envd["DMLC_TRACKER_PORT"]),
             f"b{i}", tmp, mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for i in range(world)]
        walls, reborn = {}, (0, 0, 0)
        for p in procs:
            out, err = p.communicate(timeout=180)
            if p.returncode != 0:
                raise RuntimeError(f"reshard child rc={p.returncode}: "
                                   f"{err[-500:]}")
            for ln in out.splitlines():
                if ln.startswith("WALL "):
                    _, r, w, b, fp, fc = ln.split()
                    walls[int(r)] = float(w)
                    if int(fp) or int(b):
                        reborn = (int(b), int(fp), int(fc))
        return max(walls.values()), reborn

    try:
        with tempfile.TemporaryDirectory(prefix="bench_reshard_") as tmp:
            CheckpointManager(tmp).save(0, state)
            reload_wall, _ = cohort(tmp, "reload")
            reshard_wall, (bytes_moved, from_peers, from_ckpt) = cohort(
                tmp, "reshard")
            # schedule comparison (ISSUE 15): the same recovery with the
            # round planner disabled — one unbounded blast of fetches,
            # the seed's behavior — against the planned default above
            oneshot_wall, _ = cohort(
                tmp, "reshard",
                extra_env={"DMLC_RESHARD_PER_HOLDER": "0",
                           "DMLC_RESHARD_MAX_BYTES": str(1 << 40)})
    finally:
        # --telemetry-out parity: fold whatever rank dumps made it to
        # disk (even from a cohort that died mid-run) into one merged
        # snapshot + Chrome trace for the whole bench
        prefix = os.environ.get("DMLC_TELEMETRY_OUT")
        if prefix:
            states = {}
            for mode in ("reload", "reshard"):
                for i in range(world):
                    p = f"{prefix}.reshard.{mode}.b{i}.state.json"
                    try:
                        with open(p, "r", encoding="utf-8") as f:
                            states[f"{mode}.b{i}"] = json.load(f)
                    except (OSError, ValueError):
                        continue
            _merge_child_telemetry(
                "elastic_reshard", states=states,
                trace_files=[f"{prefix}.reshard.{mode}.b{i}.trace.json"
                             for mode in ("reload", "reshard")
                             for i in range(world)])

    return {"metric": "reshard_wall_s", "value": round(reshard_wall, 4),
            "unit": "s", "state_mb": round(nbytes / MB, 1), "world": world,
            "leaves": nleaves,
            "ckpt_reload_wall_s": round(reload_wall, 4),
            "reshard_vs_reload_speedup": round(reload_wall
                                               / max(reshard_wall, 1e-9), 2),
            "oneshot_wall_s": round(oneshot_wall, 4),
            "planned_vs_oneshot_speedup": round(
                oneshot_wall / max(reshard_wall, 1e-9), 2),
            "bytes_moved": int(bytes_moved),
            "leaves_from_peers": int(from_peers),
            "leaves_from_checkpoint": int(from_ckpt)}


_EMBED_CHILD = r"""
import sys, time
import numpy as np
from dmlc_core_tpu.parallel import RabitContext
from dmlc_core_tpu.embed import ShardedEmbeddingTable
from dmlc_core_tpu.utils.metrics import metrics

uri, port, jobid, rows_s, dim_s, steps_s, brows_s = sys.argv[1:8]
num_rows, dim = int(rows_s), int(dim_s)
steps, batch_rows = int(steps_s), int(brows_s)
ctx = RabitContext(uri, int(port), jobid=jobid)
rank, world = ctx.rank, ctx.world_size
t = ShardedEmbeddingTable(num_rows, dim, rank=rank, world=world,
                          replicas=1, seed=3, serve=True)
t.sync_addresses(ctx)
nnz = batch_rows * 16
rng = np.random.default_rng(100 + rank)
batches = []
for _ in range(steps):
    ids = rng.integers(0, num_rows, nnz)
    # half the traffic keys a hot 1% of rows: dedup + the hot-row cache
    # have real work, like production id distributions
    ids[: nnz // 2] = rng.integers(0, max(1, num_rows // 100), nnz // 2)
    batches.append({
        "ids": ids.astype(np.int64),
        "vals": rng.random(nnz).astype(np.float32),
        "segments": np.sort(rng.integers(0, batch_rows, nnz)).astype(
            np.int32),
        "labels": np.zeros(batch_rows, np.float32),
        "weights": np.ones(batch_rows, np.float32),
        "nnz_used": np.int32(nnz), "rows_used": np.int32(batch_rows)})
g = np.ones((batch_rows, dim), np.float32)
t.lookup(batches[0]); t.backward(batches[0], g)     # compile outside
ctx.allreduce(np.zeros(1))                          # align cohort start
t0 = time.perf_counter()
for b in batches:
    t.backward(b, g * 0 + t.lookup(b) * 0 + 1)      # lookup feeds grad
t.flush(ctx)
wall = time.perf_counter() - t0
snap = t.build_snapshot()                           # None over budget
print("EMB %d %.6f %d %d %d %d %d" % (
    rank, wall, steps * batch_rows,
    metrics.counter("embed.exchange_bytes").value,
    metrics.counter("embed.cache_hits").value,
    t.resident_bytes, 0 if snap is None else 1), flush=True)
ctx.allreduce(np.zeros(1))                          # all reads done
t.close()
ctx.shutdown()
"""


def bench_embed_shard() -> dict:
    """Sharded embedding lookup/update throughput (ISSUE 12): a 3-rank
    cohort cooperatively trains ONE table whose total bytes exceed a
    single rank's ``DMLC_RESHARD_MAX_BYTES`` snapshot budget — no rank
    could hold (or even snapshot) the whole table, which is the point of
    the subsystem.  Each rank streams skewed ragged batches through
    lookup (dedup → cache → fan-out exchange) + backward, then one
    collective flush.  Headline is cohort looked-up rows/s; the paired
    lower-better metric is wire bytes per looked-up row (what dedup and
    the hot-row cache exist to shrink)."""
    import subprocess

    from dmlc_core_tpu.parallel import RabitTracker

    world, dim = 3, 64
    table_mb = int(os.environ.get("DMLC_BENCH_EMBED_MB", str(TARGET_MB)))
    num_rows = (table_mb * MB) // (4 * dim)
    total_bytes = num_rows * dim * 4
    # budget below the full table, above one rank's 2/3 resident share:
    # every rank CAN snapshot what it holds, none could hold it all
    budget = int(total_bytes * 0.85)
    steps, batch_rows = 24, 256

    tracker = RabitTracker(num_workers=world, host_ip="127.0.0.1")
    tracker.start()
    envd = tracker.worker_envs()
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DMLC_RESHARD_MAX_BYTES=str(budget))
    procs = [subprocess.Popen(
        [sys.executable, "-c", _EMBED_CHILD,
         envd["DMLC_TRACKER_URI"], str(envd["DMLC_TRACKER_PORT"]),
         f"em{i}", str(num_rows), str(dim), str(steps), str(batch_rows)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for i in range(world)]
    walls, resident, exch, hits, snap_ok = {}, {}, 0, 0, True
    rows_done = 0
    for p in procs:
        out, err = p.communicate(timeout=300)
        if p.returncode != 0:
            raise RuntimeError(f"embed child rc={p.returncode}: "
                               f"{err[-500:]}")
        for ln in out.splitlines():
            if ln.startswith("EMB "):
                _, r, w, rows, xb, ch, res, ok = ln.split()
                walls[int(r)] = float(w)
                rows_done += int(rows)
                exch += int(xb)
                hits += int(ch)
                resident[int(r)] = int(res)
                snap_ok = snap_ok and bool(int(ok))
    tracker.join(timeout=30)
    wall = max(walls.values())
    if max(resident.values()) >= total_bytes:
        raise RuntimeError("embed bench invariant broken: a rank resides "
                           "the full table")
    return {"metric": "embed_lookup_rows_s",
            "value": round(rows_done / wall, 1), "unit": "rows/s",
            "world": world, "table_mb": round(total_bytes / MB, 1),
            "num_rows": int(num_rows), "dim": dim,
            "snapshot_budget_mb": round(budget / MB, 1),
            "per_rank_resident_mb": round(max(resident.values()) / MB, 1),
            "resident_frac_of_table": round(
                max(resident.values()) / total_bytes, 3),
            "per_rank_snapshot_fits": bool(snap_ok),
            "exchange_bytes_per_row": round(exch / max(rows_done, 1), 1),
            "cache_hits": int(hits),
            "batches": steps, "batch_rows": batch_rows}


# Run order = dict order: the headline device configs first, the host-only
# configs (which never touch the chip) last.  DMLC_SUITE_PRIORITY reorders
# at run time (see main) without forking this registry.
#
# Each entry registers (config fn, headline metric).  Error/skip rows must
# carry the SAME metric key as the success path, so a reader can pair a
# config's error row with its measured row from another run.  allreduce's
# registered key is its 1-device metric; a multi-device run emits
# allreduce_bus_bw, a deliberately distinct key.
ALL = {
    "libsvm": (bench_libsvm, "libsvm_ingest_to_device"),
    "ingest_cached": (bench_ingest_cached, "ingest_cached"),
    "ingest_autotune": (bench_ingest_autotune, "ingest_autotune"),
    "ingest_ragged": (bench_ingest_ragged, "ingest_ragged"),
    "fm_train": (bench_fm_train, "fm_train_stream"),
    "deepfm_train": (bench_deepfm_train, "deepfm_train_stream"),
    "ffm_train": (bench_ffm_train, "ffm_train_stream"),
    "dcn_train": (bench_dcn_train, "dcn_train_stream"),
    "integrity": (bench_integrity, "ingest_integrity"),
    "a1a": (bench_a1a_train, "a1a_train_stream"),
    "criteo": (bench_criteo_ingest, "criteo_libfm_ingest"),
    "higgs": (bench_higgs_csv, "higgs_csv_parse"),
    "libfm": (bench_libfm, "libfm_ingest_to_device"),
    "sharded": (bench_sharded, "libfm_sharded4_ingest"),
    "allreduce": (bench_allreduce, "allreduce_singleton_d2d_bw"),
    "remote_ingest": (bench_remote_ingest, "remote_ingest_2workers"),
    "ingest_scale": (bench_ingest_scale, "ingest_worker_scaling"),
    "ingest_fleet": (bench_ingest_fleet, "ingest_fleet_mb_s"),
    "ingest_colocated": (bench_ingest_colocated,
                         "ingest_colocated_uds_mb_s"),
    "csv": (bench_csv, "csv_parse_rowblocks"),
    "cache": (bench_cache_build, "cache_build_replay"),
    "recordio": (bench_recordio, "recordio_partitioned_read"),
    "stream": (bench_stream, "stream_read"),
    "allreduce_mesh8": (bench_allreduce_mesh8, "allreduce_mesh8_psum_wall"),
    "sp_mesh8": (bench_sp_mesh8, "sp_mesh8_attention_wall"),
    "elastic_reshard": (bench_elastic_reshard, "reshard_wall_s"),
    "embed_shard": (bench_embed_shard, "embed_lookup_rows_s"),
}


# Configs that run on the forced-host 8-device virtual mesh (their own
# subprocesses, CPU-pinned) and never touch the chip.  Their platform is
# stamped "cpu_mesh8" so a by-design virtual-mesh number is never mistaken
# for a device config.
CPU_MESH = {"allreduce_mesh8", "sp_mesh8"}
# Raw host IO / parse-only configs: no device work at all, so they pin
# JAX to the CPU (stamped "host") and a one-chip host's chip stays free.
#  ingest_cached is CPU-pinned by design: the page-cache acceptance gates
#  (cached ≥ 2× uncached, pack ≤ 5% of cached wall) are host-path
#  properties — measuring them through a device link would mix transfer
#  latency into a disk/pack comparison.
#  ingest_autotune is CPU-pinned for the same reason: the convergence
#  experiment compares host parse/pack rates against themselves.
#  elastic_reshard is host-path by construction: it measures the control
#  plane (tracker + loopback sockets + disk), not the device.
#  ingest_fleet is host-path by construction too: dispatcher, workers and
#  consumer all live on loopback and the consumer drains host frames —
#  the number is wire+lease throughput, no device in the loop.
#  embed_shard is host-path by construction like elastic_reshard: the
#  number is dedup + loopback-exchange + flush throughput over the
#  control plane; the per-batch pooled gather is a CPU-jitted kernel.
HOST_ONLY = {"stream", "csv", "recordio", "cache", "higgs", "ingest_cached",
             "ingest_ragged", "ingest_autotune", "elastic_reshard",
             "ingest_fleet", "ingest_colocated", "embed_shard"}
# superseded in the default order (ingest_scale measures workers_2 too);
# still runnable by explicit name
DEFAULT_SKIP = {"remote_ingest"}

if os.environ.get("DMLC_SUITE_TEST_HANG") == "1":
    # test-only config simulating a wedged child (pending >1h): proves
    # the per-config timeout kills a hung child and the NEXT config
    # still runs (tests/test_bench_probe.py::test_suite_hang_isolation)
    def _bench_hang() -> dict:
        time.sleep(3600)
        return {"metric": "_hang"}

    ALL["_hang"] = (_bench_hang, "_hang")
    HOST_ONLY.add("_hang")


# derived, never hand-maintained: the registry is the single source of truth
METRIC_OF = {name: metric for name, (_, metric) in ALL.items()}


NO_CHIP_RC = 3


def run_one(name: str) -> None:
    """``--one`` mode: run a single config in THIS process, print its JSON.

    Host-only and virtual-mesh configs pin the CPU with plain
    ``JAX_PLATFORMS=cpu`` (set before JAX is imported).  Every other
    config is a device config: it needs an accelerator and the native
    library, and exits ``NO_CHIP_RC`` without one — it never carries on
    on the CPU."""
    stamp = {}
    if name in CPU_MESH | HOST_ONLY:
        os.environ["JAX_PLATFORMS"] = "cpu"
        stamp["platform"] = "cpu_mesh8" if name in CPU_MESH else "host"
    else:
        from dmlc_core_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        import jax
        devices = jax.devices()
        if devices[0].platform == "cpu":
            log(f"{name} is a device config and JAX found no accelerator "
                f"— exiting {NO_CHIP_RC}")
            sys.exit(NO_CHIP_RC)
        from dmlc_core_tpu import native
        native.require()
        stamp = {"platform": devices[0].platform,
                 "device_kind": devices[0].device_kind,
                 "device_count": len(devices)}
    log(f"{name}: running on {stamp}")
    try:
        try:
            r = ALL[name][0]()
        except Exception as e:  # noqa: BLE001 - report and continue
            r = {"metric": METRIC_OF.get(name, name), "error": str(e)}
    finally:
        # flush telemetry in a finally: a scenario that dies mid-run
        # (SIGINT, OOM-killed worker raising SystemExit, a BaseException
        # the reporting path can't survive) is EXACTLY the run whose
        # telemetry you need on disk
        prefix = os.environ.get("DMLC_TELEMETRY_OUT")
        if prefix:
            # per-config observability artifact: the full registry
            # snapshot + Chrome trace of whatever spans the config
            # produced (each config is its own process, so the dump is
            # per-config by construction)
            try:
                from dmlc_core_tpu import telemetry
                telemetry.dump_artifacts(f"{prefix}_{name}")
            except Exception as e:  # noqa: BLE001 — telemetry never
                log(f"telemetry dump failed: {e}")    # fails a run
    r.update(stamp)
    print(json.dumps(r), flush=True)


def resolve_picks(argv) -> list:
    """Config run list: explicit argv wins verbatim; otherwise the registry
    default order, optionally reordered by DMLC_SUITE_PRIORITY (listed
    configs run first, the REST keep their default order — the registry
    stays the single source of truth, so configs added later still run
    even if the env var goes stale; unknown names fail loudly)."""
    picks = list(argv) or [n for n in ALL if n not in DEFAULT_SKIP]
    prio = [p for p in os.environ.get("DMLC_SUITE_PRIORITY", "").split(",")
            if p]
    if prio and not argv:
        unknown = [p for p in prio if p not in ALL]
        if unknown:
            raise SystemExit(f"DMLC_SUITE_PRIORITY names unknown configs: "
                             f"{unknown} (have: {list(ALL)})")
        picks = [p for p in prio if p in picks] + [p for p in picks
                                                   if p not in prio]
    return picks


def main() -> None:
    argv = sys.argv[1:]
    if "--telemetry-out" in argv:
        # ride to the per-config children via env — each child dumps
        # <prefix>_<config>.metrics.json / .trace.json from run_one
        i = argv.index("--telemetry-out")
        os.environ["DMLC_TELEMETRY_OUT"] = argv[i + 1]
        del argv[i:i + 2]
    if argv[:1] == ["--one"]:
        run_one(argv[1])
        return
    picks = resolve_picks(argv)
    # each config runs in its own timeout-bounded subprocess: a wedged
    # config costs itself, not the rest of the suite.  This parent never
    # imports JAX — a parent that has touched JAX holds the chip, and the
    # ``--one`` child that needs it would then fail or hang.
    timeout_s = int(os.environ.get("DMLC_SUITE_CONFIG_TIMEOUT", "1500"))
    env = dict(os.environ)
    import subprocess
    results = []
    no_chip = False
    out = os.environ.get("DMLC_BENCH_SUITE_OUT")

    def write_artifact(platform: str) -> None:
        # rewritten after EVERY config: an outer timeout (or a SIGKILL on
        # a wedged child) must not erase the configs that already completed
        if out:
            with open(out, "w") as f:
                json.dump({"platform": platform, "results": results},
                          f, indent=1)

    def platform_of(rs) -> str:
        plats = sorted({r["platform"] for r in rs if "platform" in r})
        return "tpu" if "tpu" in plats else "+".join(plats) or "none"

    for name in picks:
        if no_chip and name not in CPU_MESH | HOST_ONLY:
            r = {"metric": METRIC_OF.get(name, name),
                 "error": "skipped: no accelerator"}
            results.append(r)
            print(json.dumps(r), flush=True)
            write_artifact(platform_of(results))
            continue
        log(f"running {name} (isolated, timeout {timeout_s}s) ...")
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", name],
                capture_output=True, text=True, timeout=timeout_s, env=env)
            sys.stderr.write(p.stderr)
            line = next((ln for ln in reversed(p.stdout.strip().splitlines())
                         if ln.startswith("{")), None)
            if p.returncode == NO_CHIP_RC and line is None:
                r = {"metric": METRIC_OF.get(name, name),
                     "error": f"no accelerator (rc {NO_CHIP_RC})"}
                no_chip = True
            elif line is None:
                r = {"metric": METRIC_OF.get(name, name),
                     "error": f"no JSON from config (rc {p.returncode})"}
            else:
                r = json.loads(line)
        except subprocess.TimeoutExpired:
            r = {"metric": METRIC_OF.get(name, name),
                 "error": f"timeout after {timeout_s}s"}
        results.append(r)
        print(json.dumps(r), flush=True)
        write_artifact(platform_of(results))
    if out:
        log(f"wrote {out}")
    if no_chip:
        # device configs were asked for and there is no chip: the error
        # rows say so, and so does the exit code
        sys.exit(NO_CHIP_RC)


if __name__ == "__main__":
    main()
