"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's test strategy (SURVEY §4): everything runs single-host
CPU; distributed behavior is validated on simulated devices
(``xla_force_host_platform_device_count``) the way the reference validates
partitioning single-process and the tracker with ``--cluster local``.
``JAX_PLATFORMS=cpu`` keeps every test off the chip; ``chip_smoke.py``,
run through the chip tool, is what exercises it.
"""

import faulthandler
import functools
import importlib.util
import os
import re
import signal

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
# two files compile for a described TPU, each in its own xdist worker: the
# TPU compiler takes a lock file at its load unless told that several
# processes may load it, and these compiles hold no device to share
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Opt-in runtime lock-order checking (the dmlclint lock-discipline rule's
# dynamic companion): DMLC_LOCKCHECK=1 shims package lock creation so the
# whole suite doubles as ordering coverage.  Installed before any package
# import so every lock the modules create at import time is wrapped too.
if os.environ.get("DMLC_LOCKCHECK") == "1":
    from dmlc_core_tpu.utils import lockcheck as _lockcheck

    _lockcheck.install()

    def pytest_terminal_summary(terminalreporter, exitstatus, config):
        _lockcheck.flush()          # land queued metric/flight emission
        rep = _lockcheck.report()
        terminalreporter.write_line(
            "lockcheck: %d lock(s), %d edge(s), %d inversion(s), "
            "%d long hold(s)" % (rep["locks"], rep["edges"],
                                 len(rep["inversions"]),
                                 len(rep["long_holds"])))
        for inv in rep["inversions"]:
            terminalreporter.write_line(
                "lockcheck INVERSION: held %(held)s while acquiring "
                "%(acquiring)s at %(site)s [%(thread)s]" % inv)


# A test that runs this long is hung: the longest, the first scorer compile
# of ``test_tpu_compile_lm.py``, which waits while the file's five compiles
# run side by side, takes 53 s alone and up to 102 s in a whole run of the
# suite on 8 cores, so a host 2.4 times slower still leaves it a margin of
# 1.7.  Its own run ends there as a failure, with every thread's
# stack on the run's stderr, and the worker goes on with the next test:
# the suite does not spend its whole clock on one test.  A hang in native
# code the signal cannot break ends the worker a minute later instead.
# Only a failure in the worker will do under ``--dist loadfile``: xdist
# hands a crashed worker's file back whole, the test that crashed it too.
HANG_S = 420
_STDERR = pytest.StashKey[int]()


def pytest_configure(config):
    # capture is suspended here: this is the run's own stderr, which a
    # test's captured fd 2 is not
    config.stash[_STDERR] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_STDERR])


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    err = item.config.stash[_STDERR]

    def hung(signum, frame):
        faulthandler.dump_traceback(err)
        pytest.fail(f"ran past {HANG_S} s")

    was = signal.signal(signal.SIGALRM, hung)
    signal.setitimer(signal.ITIMER_REAL, HANG_S)
    faulthandler.dump_traceback_later(HANG_S + 60, exit=True, file=err)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


# -- shared test helpers (imported by test modules via conftest) ----------

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "benchmarks", "chip")


@functools.lru_cache(maxsize=None)
def reference(name: str):
    """The benchmark's plain reference ``benchmarks/chip/reference_<name>.py``,
    imported by its path: the one text that both the chip's comparison and
    these tests hold a model family's program to.  It stays plain: nothing
    of the package under test, every product at full precision."""
    path = os.path.join(BENCH, f"reference_{name}.py")
    with open(path) as f:
        text = f.read()
    assert "dmlc_core_tpu" not in text, path
    assert 'default_matmul_precision("highest")' in text, path
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- ahead-of-time compiles for a described TPU v5e -------------------------

HBM_BYTES = 16 * 10 ** 9           # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def on(sharding, tree):
    """``tree``'s arrays as shapes placed by ``sharding``."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def hbm(compiled) -> int:
    """The bytes a compiled program holds in HBM at its peak."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def names_scopes(compiled, scopes):
    """The TPU compiler keeps every ``jax.named_scope`` of the program in
    some instruction's ``op_name`` (a fusion reads its root's), so a device
    trace is read by these names and not by ``fusion.N``."""
    op_names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in scopes:
        at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
        assert any(at.search(n) for n in op_names), (scope, sorted(op_names))


def batch_shapes(rows, nnz):
    """The shapes of a loader batch of ``rows`` rows and ``nnz`` ids."""
    import jax
    import jax.numpy as jnp
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return {"ids": S((nnz,), i32), "vals": S((nnz,), f32),
            "segments": S((nnz,), i32), "labels": S((rows,), f32),
            "weights": S((rows,), f32)}


def free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def start_ingest_worker(uri: str, part: int, nparts: int,
                        fmt: str = "libsvm", *, port: int = 0,
                        batch_rows: int = 64, nnz_cap: int = 1024,
                        max_epochs: int = 1, **kw) -> int:
    """Spawn one serve_ingest daemon thread; block until it listens and
    return its port.  One home for the port-probe + ready-event dance
    (used by test_ingest_service and the CLI workers= tests)."""
    import threading

    from dmlc_core_tpu.pipeline import serve_ingest
    port = port or free_port()
    ev = threading.Event()
    threading.Thread(
        target=serve_ingest,
        args=(uri, part, nparts, fmt),
        kwargs=dict(batch_rows=batch_rows, nnz_cap=nnz_cap, port=port,
                    host="127.0.0.1", max_epochs=max_epochs,
                    ready_event=ev, **kw),
        daemon=True).start()
    assert ev.wait(timeout=30), "ingest worker never became ready"
    return port
