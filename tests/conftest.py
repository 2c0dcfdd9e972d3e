"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's test strategy (SURVEY §4): everything runs single-host
CPU; distributed behavior is validated on simulated devices
(``xla_force_host_platform_device_count``) the way the reference validates
partitioning single-process and the tracker with ``--cluster local``.
``JAX_PLATFORMS=cpu`` keeps every test off the chip; ``chip_smoke.py``,
run through the chip tool, is what exercises it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
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


# -- shared test helpers (imported by test modules via conftest) ----------

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
