"""CPU rehearsal of ``chip_smoke.py`` (on-chip-measurement guide §2.1–2.2):
the script refuses to run without a TPU, and its phases — the same
functions the chip runs — pass at a tiny size on the CPU backend,
including the ``--chips 4`` phase on virtual devices.  None of this needs
the TPU compiler."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import chip_smoke  # noqa: E402  (repo root is on sys.path under pytest)

# 16 steps, not 245: the loss has to fall in them, hence the larger lr
TINY = chip_smoke.Sizes(rows=2000, features=1 << 12, batch_rows=128,
                        nnz_cap=4096, lr=0.01, min_steps=12, requests=40,
                        kernel_features=512, mesh_steps=6)


@pytest.mark.parametrize("where", ["cpu_backend", "outside_checkout"])
def test_refuses_without_chip_or_program(where, tmp_path):
    """Non-zero exit and no result line when JAX finds no accelerator, and
    in a directory that holds the script and nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "outside_checkout":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=120, env=env, cwd=tmp_path)
    assert p.returncode != 0, p.stdout[-2000:]
    assert '"ok": true' not in p.stdout
    expect = ("refusing to run" if where == "cpu_backend"
              else "No module named 'dmlc_core_tpu'")
    assert expect in p.stderr, p.stderr[-2000:]


@pytest.mark.parametrize("env_value", ["/x", None])
def test_compile_cache_placed_from_outside(env_value, monkeypatch):
    import jax

    from dmlc_core_tpu.utils.compile_cache import (compile_cache_dir,
                                                   enable_compile_cache)
    if env_value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")
        return
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_value)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == env_value
    # with the variable set no code path names another directory
    assert jax.config.jax_compilation_cache_dir == before


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """Work dir, corpus and the phases' hand-offs, shared in file order."""
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    corpus = os.path.join(work, "train.libsvm")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DMLC_TUNED_CONFIG", os.path.join(work, "tuned.json"))
        yield {"work": work, "corpus": corpus,
               "truth": chip_smoke.gen_corpus(corpus, TINY.rows,
                                              TINY.features, seed=0)}


def test_native_builds_from_source():
    from dmlc_core_tpu import native
    chip_smoke.phase_native()
    assert native.available()
    assert callable(native.build)      # not shadowed by the submodule


def test_corpus_is_the_bench_shape(smoke):
    truth = smoke["truth"]
    assert truth["rows"] == TINY.rows
    with open(smoke["corpus"]) as f:
        rows = [line.split() for line in f]
    assert len(rows) == TINY.rows
    counts = np.array([len(r) - 1 for r in rows])
    assert counts.sum() == truth["nnz"] and 4 <= counts.min() <= 39
    for r in rows[:50]:
        ids = [int(t.split(":")[0]) for t in r[1:]]
        assert ids == sorted(set(ids)) and max(ids) < TINY.features
    assert 0.1 < np.mean([r[0] == "1" for r in rows]) < 0.4


def test_ingest_phase(smoke):
    chip_smoke.phase_ingest(smoke["corpus"], smoke["truth"], TINY)
    with pytest.raises(AssertionError, match="host parse"):
        chip_smoke.phase_ingest(
            smoke["corpus"], {**smoke["truth"], "ids": 1}, TINY)


def test_train_phase_fused_follows_per_step(smoke):
    k1 = chip_smoke.phase_train(smoke["corpus"], smoke["work"], TINY, kstep=1)
    k8 = chip_smoke.phase_train(smoke["corpus"], smoke["work"], TINY, kstep=8)
    chip_smoke.check_trajectories(k1, k8)
    assert os.listdir(k1["ckpt_dir"]) and os.listdir(k8["ckpt_dir"])
    assert "tpu_custom_call" not in chip_smoke.step_program_text(TINY)
    smoke["ckpt"] = k1["ckpt_dir"]
    drifted = {**k8, "losses": {s: v + 1e-3 for s, v in k8["losses"].items()}}
    with pytest.raises(AssertionError, match="diverged"):
        chip_smoke.check_trajectories(k1, drifted)


def test_predict_and_serve_phases(smoke):
    preds = chip_smoke.phase_predict(smoke["corpus"], smoke["work"], TINY,
                                     smoke["ckpt"])
    chip_smoke.phase_serve(smoke["corpus"], TINY, smoke["ckpt"], preds)
    with pytest.raises(AssertionError, match="predict mode vs jnp"):
        chip_smoke.phase_serve(smoke["corpus"], TINY, smoke["ckpt"],
                               preds + 1e-3)


def test_kernel_phase_follows_engine_rule():
    chip_smoke.phase_kernels(TINY, seed=0)


def test_four_chip_phase_on_virtual_devices(smoke):
    import jax
    assert len(jax.devices()) >= 4      # conftest's 8 virtual CPU devices
    chip_smoke.run_four_chips(smoke["work"], TINY, seed=0)
