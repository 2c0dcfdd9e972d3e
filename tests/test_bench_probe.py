"""The root bench's multi-combo probe control flow (put_threads × compact
× batch shape, screen-then-confirm) — exercised on the CPU backend via
platform_override so a regression can't hide until a chip run — and the
no-fallback contract: device benches exit non-zero without a chip."""

import importlib.util
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench_mod(tmp_path_factory):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_under_test"] = mod
    spec.loader.exec_module(mod)
    # small corpus: the probe runs ~20 passes over it
    data = tmp_path_factory.mktemp("bench") / "probe.libsvm"
    rng = np.random.default_rng(0)
    with open(data, "w") as f:
        for r in range(4000):
            idx = np.sort(rng.choice(50_000, size=12, replace=False))
            f.write(f"{r % 2} " + " ".join(
                f"{j}:{rng.random():.4f}" for j in idx) + "\n")
    mod.DATA = str(data)
    return mod


def test_probe_flow_tpu_configspace_on_cpu(bench_mod, capfd):
    mean, runs, (pt, cm, rows), platform = bench_mod.measure_ours(
        platform_override="tpu")
    err = capfd.readouterr().err
    assert platform == "tpu"
    # tpu mode runs 5 timed pairs (drift-bounding, bench.py) vs cpu's 3
    assert len(runs) == 5 and all(r > 0 for r in runs)
    assert mean > 0
    # the full config space was screened: 3 pt × 2 compact × 3 shapes
    assert "config probe:" in err
    probe_line = [ln for ln in err.splitlines() if "config probe:" in ln][0]
    assert probe_line.count("pt=") >= 18, probe_line
    for frag in ("rows=16384", "rows=49152", "rows=147456",
                 "compact=1", "compact=0"):
        assert frag in probe_line, (frag, probe_line)
    # the winner is one of the probed configs
    assert pt in (1, 2, 4) and cm in (True, False)
    assert rows in (16384, 49152, 147456)


def test_probe_flow_pinned_by_env(bench_mod, capfd, monkeypatch):
    monkeypatch.setenv("DMLC_BENCH_PUT_THREADS", "1")
    monkeypatch.setenv("DMLC_BENCH_COMPACT", "0")
    monkeypatch.setenv("DMLC_BENCH_ROWS", "8192")
    monkeypatch.setenv("DMLC_BENCH_NNZ", "131072")
    mean, runs, (pt, cm, rows), _ = bench_mod.measure_ours(
        platform_override="tpu")
    err = capfd.readouterr().err
    assert "config probe:" not in err       # single pinned combo, no probe
    assert (pt, cm, rows) == (1, False, 8192)
    assert mean > 0


def test_suite_error_rows_use_headline_metric_keys():
    """Error/skip rows must carry the config's HEADLINE metric name, not
    the config name: readers pair rows by metric key, so a "libfm" error
    row beside a measured "libfm_ingest_to_device" row would never be
    matched with the measured entry.
    METRIC_OF is derived from the registry, so the real risk is a
    registered name drifting from what the config fn emits — cross-check
    the cheap host-only config end-to-end."""
    import benchmarks.bench_suite as bs

    assert set(bs.METRIC_OF) == set(bs.ALL)
    r = bs.bench_stream()
    assert r["metric"] == bs.METRIC_OF["stream"]


def test_suite_priority_env_reorders_without_forking_registry(monkeypatch):
    """DMLC_SUITE_PRIORITY puts listed configs first and keeps the rest in
    default order, so the knob can't silently drop configs added to the
    registry later; unknown names fail loudly; explicit argv wins."""
    import benchmarks.bench_suite as bs

    default = [n for n in bs.ALL if n not in bs.DEFAULT_SKIP]
    monkeypatch.delenv("DMLC_SUITE_PRIORITY", raising=False)
    assert bs.resolve_picks([]) == default
    monkeypatch.setenv("DMLC_SUITE_PRIORITY", "allreduce,ingest_scale")
    got = bs.resolve_picks([])
    assert got[:2] == ["allreduce", "ingest_scale"]
    assert sorted(got) == sorted(default)          # nothing dropped/added
    assert [p for p in got[2:]] == [p for p in default
                                    if p not in got[:2]]  # rest keep order
    assert bs.resolve_picks(["csv"]) == ["csv"]    # argv wins verbatim
    monkeypatch.setenv("DMLC_SUITE_PRIORITY", "nonesuch")
    import pytest as _pytest
    with _pytest.raises(SystemExit):
        bs.resolve_picks([])


def test_suite_hang_isolation(tmp_path):
    """A wedged config child (simulated 1h sleep) is killed by the
    per-config timeout and the NEXT config still runs and lands in the
    artifact."""
    import json
    import subprocess

    out = tmp_path / "suite.json"
    env = {**os.environ, "DMLC_SUITE_TEST_HANG": "1",
           "DMLC_SUITE_CONFIG_TIMEOUT": "10",
           "DMLC_BENCH_SUITE_OUT": str(out),
           "DMLC_BENCH_MB": "2", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_suite.py"),
         "_hang", "stream"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    data = json.loads(out.read_text())
    assert len(data["results"]) == 2
    hang, stream = data["results"]
    assert hang["metric"] == "_hang" and "timeout" in hang["error"]
    assert "error" not in stream and stream.get("unit") == "MB/s"


def test_consume_batch_completion_accumulator(bench_mod):
    """The timed-ingest completion proof: every batch folds one element
    into an on-device accumulator, and prove_consumed forces a VALUE read
    — a sync no runtime can resolve early."""
    import jax.numpy as jnp

    acc = None
    total = 0.0
    for i in range(5):
        batch = {"vals": jnp.full((3, 4), float(i + 1))}
        acc = bench_mod.consume_batch(acc, batch)
        total += float(i + 1)
    assert float(acc) == total          # first element of each batch
    bench_mod.prove_consumed(acc)       # must not raise
    bench_mod.prove_consumed(None)      # empty stream: no-op


def test_measure_link_verified_cpu(bench_mod):
    """The link probe must survive any backend (it is optional context in
    the bench JSON): on CPU it measures host 'puts' and returns > 0; it
    must never raise."""
    mbps = bench_mod.measure_link_verified(mb=1, reps=2)
    assert mbps > 0


def test_train_configs_registered_with_metric_keys():
    """deepfm_train/ffm_train are in the registry: their error rows must
    pair with measured rows across runs by metric key."""
    import benchmarks.bench_suite as bs

    assert bs.METRIC_OF["deepfm_train"] == "deepfm_train_stream"
    assert bs.METRIC_OF["ffm_train"] == "ffm_train_stream"
    # never accidentally host-only or cpu-mesh: these need the chip
    assert "deepfm_train" not in bs.HOST_ONLY | bs.CPU_MESH
    assert "ffm_train" not in bs.HOST_ONLY | bs.CPU_MESH


def test_cache_config_registered_host_only():
    """cache_build_replay reproduces the reference's disk_row_iter
    self-report (BASELINE.md instrumentation table); it is pure host/disk
    and must never need the chip."""
    import benchmarks.bench_suite as bs

    assert bs.METRIC_OF["cache"] == "cache_build_replay"
    assert "cache" in bs.HOST_ONLY


def test_probe_deadline_truncates_screen(bench_mod, capfd, monkeypatch):
    """DMLC_BENCH_DEADLINE_S bounds the config screen: the driver runs
    bench.py under a finite timeout, and a truncated probe that proceeds
    with best-so-far beats a killed process with no JSON at all.
    With an already-expired deadline the probe screens nothing, falls to
    the default config, and the timed runs still complete."""
    monkeypatch.setenv("DMLC_BENCH_DEADLINE_S", "0")
    mean, runs, (pt, cm, rows), platform = bench_mod.measure_ours(
        platform_override="tpu")
    err = capfd.readouterr().err
    assert "probe deadline hit" in err
    assert "no combos screened" in err
    # past-deadline runs degrade from 5 timed pairs to 3: measured pairs
    # inside the driver's budget beat a killed process with no JSON
    assert "3 pairs instead of 5" in err
    assert mean > 0 and len(runs) == 3
    # fallback = best-guess-first combo (pt=4, compact first on "tpu"),
    # not a hardcoded worst guess
    assert (pt, cm) == (4, True)


def test_integrity_config_bit_exact_on_cpu():
    """The integrity config's checksum compare must pass on the local
    backend (whose futures are truthful): a failure here means the
    checksum plumbing itself is wrong, not the transport."""
    import benchmarks.bench_suite as bs

    assert bs.METRIC_OF["integrity"] == "ingest_integrity"
    r = bs.bench_integrity()
    assert r["value"] == 1.0, r.get("paths")
    for name in ("libsvm_compact", "libfm_fields"):
        sub = r["paths"][name]
        assert sub["ok"], (name, sub.get("mismatch"))
        # a degenerate zero-feature corpus would make the checksums vacuous
        assert sub["rows"] > 0 and sub["nnz"] > 0


def test_allreduce_multidevice_branch_on_virtual_mesh():
    """bench_allreduce's n>1 branch (feedback-chained, RTT-corrected bus
    bandwidth) executes on the 8-device virtual host mesh — the branch
    only real multi-chip runs would otherwise reach, rewritten in r4 and
    unexercised until this test."""
    import json
    import subprocess

    code = (
        "import os, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "os.environ['DMLC_BENCH_MB'] = '2'\n"
        "import benchmarks.bench_suite as bs\n"
        "print(json.dumps(bs.bench_allreduce()))\n")
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": REPO}, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["metric"] == "allreduce_bus_bw"
    assert r["devices"] == 8
    assert r["value"] > 0 and r["rtt_ms"] >= 0


def test_tpu_micro_wire_builder_roundtrips_decoder():
    """The wire-decode fusion bench's v3 buffer builder must round-trip
    through the REAL decoder and drive the fused consume jit on CPU — a
    builder bug must surface here, not during a chip run."""
    import jax
    import numpy as np

    from benchmarks.tpu_micro import build_v3_buffer
    from dmlc_core_tpu.ops.csr import fm_pairwise
    from dmlc_core_tpu.pipeline.device_loader import make_decoder

    rows, nnz, w = 64, 2048, 20
    buf, meta, ids, vals = build_v3_buffer(rows, nnz, w, seed=3)
    decode = make_decoder(rows, meta)
    d = jax.jit(decode)(buf)
    np.testing.assert_array_equal(np.asarray(d["ids"]), ids.astype(np.int64))
    np.testing.assert_array_equal(np.asarray(d["vals"]), vals)
    # the fused decode+consume program lowers and runs
    table = jax.random.normal(jax.random.PRNGKey(0), (1 << w, 16))

    @jax.jit
    def fused(b):
        d2 = decode(b)
        return fm_pairwise(d2["ids"], d2["vals"], d2["segments"], table,
                           rows)

    out = fused(buf)
    assert out.shape == (rows,)
    assert bool(np.isfinite(np.asarray(out)).all())


@pytest.mark.parametrize("script", ["bench.py", "benchmarks/tpu_micro.py"])
def test_device_bench_exits_nonzero_without_chip(script, tmp_path):
    """No probe, no CPU re-run, no platform-stamped CPU number: a bench of
    the device path that finds no accelerator fails, and prints no JSON."""
    import subprocess

    p = subprocess.run(
        [sys.executable, os.path.join(REPO, script),
         str(tmp_path / "out.json")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no accelerator" in p.stderr
    assert "{" not in p.stdout and not (tmp_path / "out.json").exists()


def test_suite_device_config_fails_without_chip(tmp_path):
    """The orchestrating parent stays off JAX and hands nothing down: a
    device config's ``--one`` child exits non-zero on a chipless host, its
    row says why, host-only configs still run pinned to the CPU, and the
    suite's own exit code is non-zero."""
    import json
    import subprocess

    import benchmarks.bench_suite as bs

    out = tmp_path / "suite.json"
    env = {**os.environ, "DMLC_BENCH_SUITE_OUT": str(out),
           "DMLC_BENCH_MB": "2", "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_suite.py"),
         "libsvm", "fm_train", "stream"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert p.returncode == bs.NO_CHIP_RC, p.stderr[-2000:]
    libsvm, fm, stream = json.loads(out.read_text())["results"]
    assert "no accelerator" in libsvm["error"] and "platform" not in libsvm
    assert fm["error"] == "skipped: no accelerator"
    assert stream["platform"] == "host" and stream.get("unit") == "MB/s"


def test_peaks_keyed_by_device_kind_with_source():
    import benchmarks.bench_suite as bs

    v5e = bs.device_peaks("TPU v5 lite")
    assert (v5e["bf16_tflops"], v5e["hbm_gb_s"]) == (197.0, 819.0)
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        bs.device_peaks("cpu")
