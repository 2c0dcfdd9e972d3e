"""chip_smoke.py — ingest → train → checkpoint → predict → serve, once, on the chip.

The quickest proof that the system still starts on a TPU: one process
drives the main path through the entry points a user would call
(``create_parser`` → ``DeviceLoader``, ``models.cli.main``,
``InferenceEngine`` + ``PredictionServer`` + ``PredictClient``) at the
full width of one model the repo supports (FM 2^20 × 32, random weights
from ``--seed``), checks every phase by the repo's own means, and exits
non-zero on any failure.  A phase that raises is not caught.

    python chip_smoke.py             one chip: all phases
    python chip_smoke.py --chips 4   only the dp=2 × mp=2 sharded train
                                     step against a one-device run

It refuses to run unless JAX reports a TPU, builds the native library
from source, reads no untracked file, starts no child that imports JAX,
and prints as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Rates on the earlier lines are labelled "smoke, not a benchmark": they
include warm-up and sit on whatever else the host is doing.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

M32 = 0xFFFFFFFF


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase scales by.  ``REAL`` is what the chip runs;
    tests/test_chip_smoke.py rehearses the same phases at a tiny size."""
    rows: int = 1_000_000          # ≥ 200 steps of 4096 rows in one epoch
    features: int = 1 << 20
    dim: int = 32
    batch_rows: int = 4096
    nnz_cap: int = 131072
    lr: float = 0.001              # the train CLI's default
    min_steps: int = 200
    requests: int = 200
    kernel_features: int = 1 << 17
    mesh_steps: int = 50


REAL = Sizes()
KERNEL_DIM = 128    # the one width class the engine rule sends to Pallas


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

def compile_counts():
    """(backend-compile seconds, programs, persistent-cache hits) so far:
    the program's own counters, fed by the listeners that
    ``enable_compile_cache`` installs."""
    from dmlc_core_tpu.utils.metrics import metrics
    return (metrics.histogram("xla.backend_compile_seconds").sum,
            metrics.counter("xla.backend_compiles").value,
            metrics.counter("xla.persistent_cache_hits").value)


def peak_hbm() -> str:
    import jax
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "n/a"
    return f"{stats['peak_bytes_in_use'] / (1 << 20):.0f}MiB"


@contextlib.contextmanager
def phase(name: str, facts: dict):
    """Times one phase and prints its line; the body may leave a ``rate``
    (printed with the smoke label) and a ``show`` dict in ``facts``.  An
    exception in the body propagates — nothing here catches a failed
    phase."""
    c0, p0, h0 = compile_counts()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, p1, h1 = compile_counts()
    rate = facts.get("rate")
    say(f"[{name}] wall={wall:.2f}s compile={c1 - c0:.2f}s "
        f"({p1 - p0} programs, {h1 - h0} cache hits) peak_hbm={peak_hbm()}"
        + (f" rate={rate} (smoke, not a benchmark)" if rate else "")
        + "".join(f" {k}={v}" for k, v in facts.get("show", {}).items()))


def wsum(a) -> int:
    """Wrapping 32-bit sum of an integer array (the host side of the
    integrity compare)."""
    return int(np.sum(np.asarray(a).astype(np.int64)) & M32)


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def gen_corpus(path: str, rows: int, features: int, seed: int) -> dict:
    """The root bench's libsvm shape (``bench.py`` ``gen_data``: 5–39
    sorted unique ``id:0.dddd`` tokens per row, ids uniform over the
    feature space) written with vectorised numpy, plus labels from a seeded
    linear teacher with P(y=1) ≈ ¼ so that a falling loss means learning.
    Returns the generator's own truth (rows, nnz, wrapping id sum) — an
    independent check on the parser."""
    rng = np.random.default_rng(seed)
    teacher = np.random.default_rng(seed + 1).standard_normal(
        features).astype(np.float32)
    pow10 = 10 ** np.arange(7, dtype=np.int64)
    truth = {"rows": 0, "nnz": 0, "ids": 0, "bytes": 0}
    with open(path, "wb") as f:
        for lo in range(0, rows, 100_000):
            n_rows = min(100_000, rows - lo)
            counts = rng.integers(5, 40, size=n_rows)
            row_of = np.repeat(np.arange(n_rows, dtype=np.int64), counts)
            # one sort orders ids inside every row; equal neighbours are
            # the (rare) duplicate ids of a row and are dropped
            key = np.sort(row_of * features
                          + rng.integers(0, features, size=len(row_of)))
            key = key[np.concatenate(([True], key[1:] != key[:-1]))]
            row_of, ids = key // features, key % features
            counts = np.bincount(row_of, minlength=n_rows)
            val4 = rng.integers(0, 10_000, size=len(ids))   # 0.dddd
            z = np.bincount(row_of, weights=teacher[ids] * (val4 * 1e-4),
                            minlength=n_rows) / np.sqrt(counts * 0.33)
            y = (1.5 * z - 1.6 + rng.logistic(size=n_rows)) > 0

            digits = 1 + (ids[:, None] >= pow10[1:]).sum(axis=1)
            tok_len = digits + 8              # ' ' id ':' '0.dddd'
            start = (np.cumsum(tok_len) - tok_len) + 2 * row_of + 1
            row_len = 2 + np.bincount(row_of, weights=tok_len,
                                      minlength=n_rows).astype(np.int64)
            row_start = np.cumsum(row_len) - row_len
            buf = np.empty(int(row_len.sum()), np.uint8)
            buf[row_start] = 48 + y
            buf[row_start + row_len - 1] = 10
            buf[start] = 32
            for k in range(7):
                m = digits > k
                buf[start[m] + digits[m] - k] = 48 + (ids[m] // pow10[k]) % 10
            colon = start + digits + 1
            buf[colon] = 58
            buf[colon + 1] = 48
            buf[colon + 2] = 46
            for j in range(4):
                buf[colon + 3 + j] = 48 + (val4 // pow10[3 - j]) % 10
            f.write(buf.tobytes())
            truth["rows"] += n_rows
            truth["nnz"] += len(ids)
            truth["ids"] = (truth["ids"] + wsum(ids)) & M32
            truth["bytes"] += len(buf)
    return truth


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_native() -> None:
    """Build the native library from ``native/dmlc_native.cpp`` — whatever
    ``.so`` is on disk is overwritten, never trusted — and require it."""
    from dmlc_core_tpu import native
    with phase("native", {}):
        if not native.build(verbose=True):
            raise RuntimeError("native library failed to build from source")
        if not (native.available() and native.has_sppack()
                and native.has_compact()):
            raise RuntimeError("native library built but did not load whole")


def _host_sums(path: str) -> dict:
    """Host-parsed reference checksums (bench_suite's ``integrity`` idea:
    wrapping int32 sums over exact bit patterns, so order- and
    padding-immune — pad ids/vals/labels/weights are all 0)."""
    from dmlc_core_tpu.data import create_parser
    one = np.float32(1.0).view(np.int32)
    host = dict.fromkeys(("ids", "vals", "labels", "weights", "nnz", "rows"),
                         0)
    parser = create_parser(f"file://{path}", 0, 1, "libsvm")
    try:
        for c in parser:
            blk = c.get_block()
            lo, hi = int(blk.offsets[0]), int(blk.offsets[-1])
            w = (blk.weights.view(np.int32) if blk.weights is not None
                 else np.full(blk.size, one, np.int32))
            for k, a in (("ids", blk.indices[lo:hi]),
                         ("vals", blk.values[lo:hi].view(np.int32)),
                         ("labels", blk.labels.view(np.int32)),
                         ("weights", w)):
                host[k] = (host[k] + wsum(a)) & M32
            host["nnz"] += hi - lo
            host["rows"] += blk.size
    finally:
        parser.close()
    return host


def phase_ingest(corpus: str, truth: dict, sz: Sizes) -> None:
    """``create_parser`` → ``DeviceLoader``: one full pass to device memory
    per wire layout, device-decoded bytes checksummed on the device and
    compared with the host parse (and the ids with the generator)."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.pipeline import DeviceLoader
    from dmlc_core_tpu.pipeline import device_loader as dl

    host = _host_sums(corpus)
    for k in ("rows", "nnz", "ids"):
        if host[k] != truth[k]:
            raise AssertionError(f"host parse {k}={host[k]} != generated "
                                 f"{truth[k]}")

    @jax.jit
    def fold(acc, b):
        i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731
        return acc + jnp.stack([
            jnp.sum(b["ids"]), jnp.sum(i32(b["vals"])),
            jnp.sum(i32(b["labels"])), jnp.sum(i32(b["weights"])),
            b["row_ptr"][-1]])

    size_mb = os.path.getsize(corpus) / (1 << 20)
    for compact in (False, True):
        facts: dict = {}
        with phase(f"ingest wire_compact={compact}", facts):
            before = set(dl._unpack_cache)
            loader = DeviceLoader(
                create_parser(f"file://{corpus}", 0, 1, "libsvm"),
                batch_rows=sz.batch_rows, nnz_cap=sz.nnz_cap,
                wire_compact=compact)
            try:
                acc = jnp.zeros(5, jnp.int32)   # wraps like the host's & M32
                batches = 0
                t0 = time.perf_counter()
                for b in loader:
                    acc = fold(acc, b)
                    batches += 1
                dev = [int(x) & M32 for x in np.asarray(acc)]
                dt = time.perf_counter() - t0
                stats = loader.stats
            finally:
                loader.close()
            got = dict(zip(("ids", "vals", "labels", "weights", "nnz"), dev),
                       rows=stats.rows)
            bad = {k: (host[k], got[k]) for k in got if host[k] != got[k]}
            if bad or stats.truncated_values:
                raise AssertionError(
                    f"ingest integrity (host, device): {bad}, truncated "
                    f"{stats.truncated_values}")
            programs = sorted(set(dl._unpack_cache) - before)
            facts["rate"] = (f"{host['rows'] / dt:.0f} rows/s "
                             f"{size_mb / dt:.1f} MB/s")
            facts["show"] = {
                "batches": batches, "checksum": "ok",
                # jnp slices/bitcasts/gathers only: no engine to choose
                "decode": f"xla ({len(programs)} programs, id widths "
                          f"{sorted({dl._decode_meta(m)[1] for _, m in programs})})",
            }


_LOSS = re.compile(r"^epoch \d+ step (\d+) loss (\S+)$")


def _run_cli(argv) -> str:
    """``dmlc_core_tpu.models.cli.main`` in this process, stdout captured
    (every 20th line echoed to stderr so a chip log shows progress)."""
    from dmlc_core_tpu.models import cli
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    text = buf.getvalue()
    for i, line in enumerate(text.splitlines()):
        if i % 20 == 0 or not _LOSS.match(line):
            print("  cli| " + line, file=sys.stderr, flush=True)
    if rc != 0:
        raise RuntimeError(f"dmlc-train {argv} exited {rc}")
    return text


def _model_args(sz: Sizes):
    return ["model=fm", f"features={sz.features}", f"dim={sz.dim}",
            f"batch_rows={sz.batch_rows}", f"nnz_cap={sz.nnz_cap}"]


def phase_train(corpus: str, work: str, sz: Sizes, kstep: int) -> dict:
    """The train CLI, per-step (``kstep=1``) or fused k-step dispatch;
    returns the loss trajectory ``{step: loss}`` and the checkpoint dir."""
    ckpt = os.path.join(work, f"ckpt_k{kstep}")
    facts: dict = {"ckpt_dir": ckpt}
    with phase(f"train kstep={kstep}", facts):
        t0 = time.perf_counter()
        text = _run_cli([f"data=file://{corpus}", *_model_args(sz),
                         f"lr={sz.lr}", "epochs=1", f"kstep={kstep}",
                         "log_every=1", f"ckpt_dir={ckpt}",
                         # the AUC pass rides the per-step run only
                         f"eval_auc={'true' if kstep == 1 else 'false'}"])
        dt = time.perf_counter() - t0
        losses = {int(m.group(1)): float(m.group(2))
                  for m in map(_LOSS.match, text.splitlines()) if m}
        steps = max(losses)
        if steps < sz.min_steps:
            raise AssertionError(f"only {steps} steps < {sz.min_steps}")
        seq = [losses[s] for s in sorted(losses)]
        if not all(math.isfinite(x) for x in seq):
            raise AssertionError("non-finite loss in trajectory")
        third = max(1, len(seq) // 3)
        first, last = np.mean(seq[:third]), np.mean(seq[-third:])
        if not last < first:
            raise AssertionError(f"loss not falling: first third "
                                 f"{first:.5f}, last third {last:.5f}")
        if f"checkpoint step {steps} -> {ckpt}" not in text:
            raise AssertionError("train CLI reported no final checkpoint")
        facts["losses"] = losses
        facts["rate"] = f"{min(steps * sz.batch_rows, sz.rows) / dt:.0f} rows/s"
        facts["show"] = {"steps": steps, "loss": f"{first:.4f}->{last:.4f}",
                         "logged": len(seq)}
    return facts


def check_trajectories(k1: dict, k8: dict) -> None:
    """Fused k-step dispatch must follow the per-step trajectory: the
    tolerance is tests/test_models.py::test_fused_kstep_matches_per_step's
    (|Δloss| < 1e-4), at every step both runs logged."""
    a, b = k1["losses"], k8["losses"]
    common = sorted(set(a) & set(b))
    if len(common) < 3 or max(a) != max(b):
        raise AssertionError(f"trajectories share {len(common)} steps; "
                             f"ends {max(a)} vs {max(b)}")
    worst = max(abs(a[s] - b[s]) for s in common)
    say(f"[train] kstep=1 vs kstep=8: {len(common)} common steps, "
        f"max |dloss|={worst:.2e} (tolerance 1e-4)")
    if not worst < 1e-4:
        raise AssertionError(f"fused trajectory diverged: {worst}")


def step_program_text(sz: Sizes) -> str:
    """StableHLO of the per-step train program the CLI builds
    (``make_train_step`` on the registry's FM at the smoke's shapes) —
    lowered, not compiled again: a Pallas kernel shows as a
    ``tpu_custom_call`` custom call either way."""
    import jax
    import jax.numpy as jnp
    import optax

    from dmlc_core_tpu.models import FactorizationMachine, make_train_step
    model = FactorizationMachine(num_features=sz.features, dim=sz.dim)
    opt = optax.adam(sz.lr)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    batch = {"ids": S((sz.nnz_cap,), i32), "vals": S((sz.nnz_cap,), f32),
             "segments": S((sz.nnz_cap,), i32),
             "row_ptr": S((sz.batch_rows + 1,), i32),
             "labels": S((sz.batch_rows,), f32),
             "weights": S((sz.batch_rows,), f32)}
    return make_train_step(model, opt).lower(params, opt_state,
                                             batch).as_text()


def phase_predict(corpus: str, work: str, sz: Sizes, ckpt: str) -> np.ndarray:
    """``mode=predict`` from the checkpoint; one score per corpus row."""
    out = os.path.join(work, "preds.txt")
    facts: dict = {}
    with phase("predict", facts):
        t0 = time.perf_counter()
        _run_cli([f"data=file://{corpus}", *_model_args(sz), "mode=predict",
                  f"ckpt_dir={ckpt}", f"output={out}"])
        preds = np.loadtxt(out, dtype=np.float64)
        if preds.shape != (sz.rows,) or not np.isfinite(preds).all():
            raise AssertionError(f"predict wrote {preds.shape}, want "
                                 f"({sz.rows},) finite scores")
        if not ((preds > 0) & (preds < 1)).all() or preds.std() < 1e-4:
            raise AssertionError("predict scores are not varied "
                                 "probabilities")
        facts["rate"] = f"{sz.rows / (time.perf_counter() - t0):.0f} rows/s"
    return preds


def _head_rows(corpus: str, n: int):
    """First ``n`` corpus rows as one CSR triple (host parse)."""
    from dmlc_core_tpu.data import create_parser
    ids, vals, counts = [], [], []
    parser = create_parser(f"file://{corpus}", 0, 1, "libsvm")
    try:
        for c in parser:
            blk = c.get_block()
            off = np.asarray(blk.offsets, np.int64)
            ids.append(np.asarray(blk.indices[off[0]:off[-1]], np.int32))
            vals.append(np.asarray(blk.values[off[0]:off[-1]], np.float32))
            counts.append(np.diff(off))
            if sum(map(len, counts)) >= n:
                break
    finally:
        parser.close()
    counts = np.concatenate(counts)[:n]
    row_ptr = np.concatenate(([0], np.cumsum(counts)))
    return (np.concatenate(ids)[:row_ptr[-1]],
            np.concatenate(vals)[:row_ptr[-1]], row_ptr)


def fm_reference(params, ids, vals, row_ptr) -> np.ndarray:
    """Plain float32 ``jax.numpy`` FM over row-padded ``[R, K]`` gathers —
    written from the model's formula, sharing nothing with ``ops.csr``'s
    segment sums or the serving engine."""
    import jax
    import jax.numpy as jnp
    counts = np.diff(row_ptr)
    R, K = len(counts), int(counts.max())
    col = np.arange(len(ids)) - np.repeat(row_ptr[:-1], counts)
    row = np.repeat(np.arange(R), counts)
    pid = np.zeros((R, K), np.int32)
    px = np.zeros((R, K), np.float32)
    pid[row, col], px[row, col] = ids, vals
    px = jnp.asarray(px)
    vx = params["v"][pid] * px[..., None]                  # [R, K, D]
    pair = 0.5 * jnp.sum(jnp.sum(vx, 1) ** 2 - jnp.sum(vx * vx, 1), -1)
    lin = jnp.sum(params["w"][pid] * px, 1)
    return np.asarray(jax.nn.sigmoid(params["w0"] + lin + pair))


def phase_serve(corpus: str, sz: Sizes, ckpt: str, preds: np.ndarray) -> None:
    """``InferenceEngine`` + ``PredictionServer`` built the way
    ``serving.server.serve_main`` builds them (in-process, on threads),
    ``watch_checkpoints`` on the trainer's dir, requests through
    ``serving.client`` in both ladders; every score must equal
    predict-mode's and the jnp reference to 1e-5, with zero compiles
    inside the request window."""
    import jax

    from dmlc_core_tpu.models.cli import MODEL_REGISTRY, TrainParams
    from dmlc_core_tpu.serving import (InferenceEngine, PredictClient,
                                       PredictionServer)
    from dmlc_core_tpu.utils import load_for_inference

    # request r takes the next 1..16 rows, so the window covers them all
    sizes = [1 + (r % 16) for r in range(sz.requests)]
    cuts = np.concatenate(([0], np.cumsum(sizes)))
    ids, vals, row_ptr = _head_rows(corpus, int(cuts[-1]))

    p = TrainParams()
    p.init({"data": "unused", "model": "fm", "features": str(sz.features),
            "dim": str(sz.dim)})
    model = MODEL_REGISTRY[p.model](p)
    _, trained, _ = load_for_inference(
        ckpt, template=model.init(jax.random.PRNGKey(0)))
    ref = fm_reference(trained, ids, vals, row_ptr)
    want = preds[:len(ref)]
    if not np.allclose(ref, want, rtol=0, atol=1e-5):
        raise AssertionError(
            f"predict mode vs jnp reference: max "
            f"{np.abs(ref - want).max():.2e} > 1e-5")

    for ragged in (False, True):
        facts: dict = {}
        with phase(f"serve ragged={int(ragged)}", facts):
            engine = InferenceEngine(
                model, model.init(jax.random.PRNGKey(0)),
                postprocess="sigmoid", ragged=ragged)
            srv = PredictionServer(engine, host="127.0.0.1", port=0,
                                   max_delay_s=0.002, max_queue=256)
            srv.watch_checkpoints(ckpt, interval_s=1.0)
            srv.start()
            try:
                if engine.params_version < 1:
                    raise AssertionError("server did not load the "
                                         "checkpoint (serving random init)")
                compiles = engine.compile_count
                got = np.empty(len(ref), np.float32)
                t0 = time.perf_counter()
                with PredictClient(srv.host, srv.port) as client:
                    def req(r):
                        a, b = row_ptr[cuts[r]], row_ptr[cuts[r + 1]]
                        return (ids[a:b], vals[a:b],
                                row_ptr[cuts[r]:cuts[r + 1] + 1] - a)
                    half = sz.requests // 2
                    for r in range(half):            # blocking, one by one
                        got[cuts[r]:cuts[r + 1]] = client.predict(
                            *req(r), timeout=60.0)
                    futs = [client.submit(*req(r))   # pipelined → batched
                            for r in range(half, sz.requests)]
                    for r, fut in zip(range(half, sz.requests), futs):
                        got[cuts[r]:cuts[r + 1]] = fut.result(timeout=60.0)
                dt = time.perf_counter() - t0
                if engine.compile_count != compiles:
                    raise AssertionError(
                        f"{engine.compile_count - compiles} compiles inside "
                        f"the request window")
                for name, other in (("predict mode", want),
                                    ("jnp reference", ref)):
                    if not np.allclose(got, other, rtol=0, atol=1e-5):
                        raise AssertionError(
                            f"served scores vs {name}: max "
                            f"{np.abs(got - other).max():.2e} > 1e-5")
                kernel = any("tpu_custom_call" in exe.as_text()
                             for exe in engine._compiled.values())
                facts["rate"] = f"{sz.requests / dt:.0f} req/s"
                facts["show"] = {
                    "requests": sz.requests, "rows": len(ref),
                    "buckets": compiles, "window_compiles": 0,
                    "max_err": f"{np.abs(got - ref).max():.1e}",
                    # every zoo forward serves through ops.csr (XLA)
                    "engine": "xla", "tpu_custom_call": kernel}
                if kernel:
                    raise AssertionError("a bucket program holds a Pallas "
                                         "kernel the engine rule never "
                                         "chose")
            finally:
                srv.stop()


def phase_kernels(sz: Sizes, seed: int) -> None:
    """The ragged gather kernels at the width the engine rule sends to
    Pallas on a TPU (``ops.ragged_csr``; users reach them through
    ``embed.table``): ``engine="auto"`` must resolve as the rule says, the
    program must hold ``tpu_custom_call`` exactly when it says Pallas, and
    the result must match XLA's."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import ragged_csr

    D, F = KERNEL_DIM, sz.kernel_features
    rows, cap = sz.batch_rows, sz.nnz_cap
    rng = np.random.default_rng(seed)
    nnz = int(cap * 0.7)                     # ragged: the tail is garbage
    ids = jnp.asarray(rng.integers(0, F, cap), jnp.int32)
    vals = jnp.asarray(rng.random(cap), jnp.float32)
    segs = jnp.asarray(np.sort(rng.integers(0, rows, cap)), jnp.int32)
    table = jax.random.normal(jax.random.PRNGKey(seed), (F, D), jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    for name, op in (("ragged_embed_sum", ragged_csr.ragged_embed_sum),
                     ("ragged_fm_pairwise", ragged_csr.ragged_fm_pairwise)):
        facts: dict = {}
        with phase(f"kernel {name} D={D}", facts):
            want = "pallas" if on_tpu and ragged_csr.mosaic_row_dma_ok(D) else "xla"
            engine = ragged_csr._resolve_engine("auto", D)
            if engine != want:
                raise AssertionError(f"{name}: auto -> {engine}, rule "
                                     f"says {want}")
            auto = jax.jit(lambda i, v, s, n, t, op=op: op(
                i, v, s, n, t, rows, engine="auto"))
            xla = jax.jit(lambda i, v, s, n, t, op=op: op(
                i, v, s, n, t, rows, engine="xla"))
            args = (ids, vals, segs, jnp.int32(nnz), table)
            has_call = "tpu_custom_call" in auto.lower(*args).as_text()
            if has_call != (engine == "pallas"):
                raise AssertionError(f"{name}: engine {engine} but "
                                     f"tpu_custom_call={has_call}")
            got, ref = np.asarray(auto(*args)), np.asarray(xla(*args))
            if not (np.isfinite(got).all() and np.allclose(
                    got, ref, rtol=1e-4, atol=1e-4)):
                raise AssertionError(
                    f"{name}: {engine} vs xla max "
                    f"{np.abs(got - ref).max():.2e}")
            facts["show"] = {"engine": engine, "tpu_custom_call": has_call,
                             "max_err": f"{np.abs(got - ref).max():.1e}"}
    # and the rule's other side, at the width the smoke trains
    say(f"[kernel] engine rule at D={sz.dim}: "
        f"{ragged_csr._resolve_engine('auto', sz.dim)} "
        f"(mosaic_row_dma_ok={ragged_csr.mosaic_row_dma_ok(sz.dim)})")


def run_one_chip(work: str, sz: Sizes, seed: int) -> None:
    phase_native()
    corpus = os.path.join(work, "train.libsvm")
    facts: dict = {}
    with phase("corpus", facts):
        truth = gen_corpus(corpus, sz.rows, sz.features, seed)
        facts["show"] = {"rows": truth["rows"], "nnz": truth["nnz"],
                         "MB": round(truth["bytes"] / (1 << 20), 1)}
    phase_ingest(corpus, truth, sz)
    k1 = phase_train(corpus, work, sz, kstep=1)
    k8 = phase_train(corpus, work, sz, kstep=8)
    check_trajectories(k1, k8)
    say(f"[train] step program fm {sz.features}x{sz.dim}: ops.csr "
        f"segment-sum (xla, no engine switch on the flat layout), "
        f"tpu_custom_call={'tpu_custom_call' in step_program_text(sz)}")
    preds = phase_predict(corpus, work, sz, k1["ckpt_dir"])
    phase_serve(corpus, sz, k1["ckpt_dir"], preds)
    phase_kernels(sz, seed)


# ---------------------------------------------------------------------------
# --chips 4: data-parallel step with a sharded factor table
# ---------------------------------------------------------------------------

def run_four_chips(work: str, sz: Sizes, seed: int) -> None:
    """FM at the smoke's width, ``make_train_step(model, opt, mesh)`` on a
    dp=2 × mp=2 mesh, against a one-device run of the same steps on the
    same batches.  Nothing else runs in this mode."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P, SingleDeviceSharding

    from dmlc_core_tpu.data import create_parser
    from dmlc_core_tpu.models import (FactorizationMachine, batch_sharding,
                                      make_train_step, param_shardings,
                                      shard_params)
    from dmlc_core_tpu.parallel import make_mesh
    from dmlc_core_tpu.pipeline import DeviceLoader

    phase_native()
    devices = jax.devices()[:4]
    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs four devices, JAX has "
                           f"{len(jax.devices())}")
    mesh = make_mesh("dp=2,mp=2", devices)
    say(f"[mesh] shape={dict(mesh.shape)} devices="
        f"{[str(d) for d in mesh.devices.flat]}")
    corpus = os.path.join(work, "mesh.libsvm")
    gen_corpus(corpus, sz.mesh_steps * sz.batch_rows, sz.features, seed)
    model = FactorizationMachine(num_features=sz.features, dim=sz.dim)
    opt = optax.adam(sz.lr)

    def run(mesh_or_none, name):
        # both sides take the per-array transfer path (a sharding is
        # given), so they see byte-identical cap-padded batches
        sharding = (batch_sharding(mesh_or_none) if mesh_or_none is not None
                    else SingleDeviceSharding(devices[0]))
        params = model.init(jax.random.PRNGKey(seed))
        if mesh_or_none is None:
            params = jax.device_put(params, devices[0])
        else:
            params = shard_params(params, param_shardings(model, params,
                                                          mesh_or_none))
        opt_state = opt.init(params)
        step = make_train_step(model, opt, mesh_or_none)
        facts: dict = {}
        losses, text = [], None
        with phase(f"mesh {name}", facts):
            loader = DeviceLoader(
                create_parser(f"file://{corpus}", 0, 1, "libsvm"),
                batch_rows=sz.batch_rows, nnz_cap=sz.nnz_cap,
                sharding=sharding)
            try:
                t0 = time.perf_counter()
                for batch in loader:
                    if text is None:
                        step = step.lower(params, opt_state, batch).compile()
                        text = step.as_text()
                    params, opt_state, loss = step(params, opt_state, batch)
                    losses.append(loss)
                losses = [float(x) for x in losses]
                dt = time.perf_counter() - t0
            finally:
                loader.close()
            facts["rate"] = f"{len(losses) * sz.batch_rows / dt:.0f} rows/s"
            facts["show"] = {"steps": len(losses),
                             "loss": f"{losses[0]:.4f}->{losses[-1]:.4f}",
                             "all_reduce": "all-reduce" in text}
        return losses, params, text

    single, _, _ = run(None, "one device")
    meshed, params, text = run(mesh, "dp=2 x mp=2")
    if len(single) != sz.mesh_steps or len(meshed) != sz.mesh_steps:
        raise AssertionError(f"steps {len(single)}/{len(meshed)}, want "
                             f"{sz.mesh_steps}")
    if not np.isfinite(meshed).all():
        raise AssertionError("non-finite loss on the mesh")
    # tests/test_models.py::test_sharded_step_matches_single_device's bound
    np.testing.assert_allclose(single, meshed, rtol=2e-4, atol=2e-5)
    v = params["v"]
    shard_devices = {s.device for s in v.addressable_shards}
    if v.sharding.spec != P(None, "mp") or len(shard_devices) != 4:
        raise AssertionError(f"factor table on {len(shard_devices)} devices "
                             f"as {v.sharding.spec}")
    if "all-reduce" not in text:
        raise AssertionError("compiled mesh step holds no all-reduce")
    say(f"[mesh] losses agree over {sz.mesh_steps} steps (max |d|="
        f"{np.abs(np.subtract(single, meshed)).max():.2e}); v "
        f"{v.sharding.spec} shards {[s.data.shape for s in v.addressable_shards]}"
        f" on {len(shard_devices)} distinct devices; all-reduce present")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp=2 x mp=2 train step vs one device")
    args = ap.parse_args(argv)

    from dmlc_core_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports platform="
              f"{dev.platform!r} — refusing to run", file=sys.stderr)
        return 2
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except (ImportError, AttributeError):
        libtpu_version = "?"
    say(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"compile_cache={cache}")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    # the loader's "auto" knobs read a tuned-config file: point it at a
    # fresh path so no stale untracked .dmlc_tuned.json steers this run
    os.environ["DMLC_TUNED_CONFIG"] = os.path.join(work, "tuned.json")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(work, REAL, args.seed)
        else:
            run_one_chip(work, REAL, args.seed)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    seconds, programs, hits = compile_counts()
    say(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s; "
        f"compile total={seconds:.2f}s ({programs} programs, {hits} from "
        f"the persistent cache)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
