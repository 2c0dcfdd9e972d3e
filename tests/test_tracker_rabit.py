"""Tracker + rabit client tests: topology properties, full local rendezvous
with tree collectives over real sockets, recover re-registration, and the
local launcher end-to-end (the reference validates distributed behavior with
--cluster local the same way, SURVEY §4)."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from dmlc_core_tpu.parallel import (RabitContext, RabitTracker, compute_ring,
                                    compute_tree)


def _jax_cpu_multiprocess() -> bool:
    """jax < 0.5 CPU backends refuse multi-process computations outright
    ("Multiprocess computations aren't implemented on the CPU backend") —
    the elastic-rejoin tests need them to run their 3-process cohorts."""
    import jax
    try:
        major, minor = (int(x) for x in jax.__version__.split(".")[:2])
    except ValueError:
        return True
    return (major, minor) >= (0, 5)


needs_multiprocess_cpu = pytest.mark.skipif(
    not _jax_cpu_multiprocess(),
    reason="this jax's CPU backend lacks multi-process collectives")


@pytest.mark.parametrize("world", [1, 2, 3, 5, 8, 16])
def test_tree_and_ring_properties(world):
    tree = compute_tree(world)
    # connected binary tree: world-1 edges, each node ≤3 neighbors
    edges = sum(len(v) for v in tree.values())
    assert edges == 2 * (world - 1)
    assert all(len(v) <= 3 for v in tree.values())
    ring = compute_ring(world)
    assert sorted(ring) == list(range(world))
    # DFS pre-order: every rank appears after its tree parent (recovery data
    # flows with tree locality; ring links are brokered as extra connections,
    # like the reference's assign_rank sends both tree and ring neighbors)
    pos = {r: i for i, r in enumerate(ring)}
    for r in range(1, world):
        assert pos[r] > pos[(r - 1) // 2]


def _run_cohort(world, fn):
    """Spin a tracker + world thread-workers; fn(ctx, results, rank)."""
    tracker = RabitTracker(num_workers=world, host_ip="127.0.0.1")
    tracker.start()
    env = tracker.worker_envs()
    results = [None] * world
    errors = []

    def worker(i):
        try:
            ctx = RabitContext(env["DMLC_TRACKER_URI"],
                               int(env["DMLC_TRACKER_PORT"]),
                               jobid=f"w{i}")
            fn(ctx, results, i)
            ctx.shutdown()
        except Exception as e:  # noqa: BLE001
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    tracker.join(timeout=30)
    assert not errors, errors
    return results


@pytest.mark.parametrize("world", [1, 2, 4, 7])
def test_allreduce_sum_and_max(world):
    def fn(ctx, results, i):
        contrib = np.arange(4, dtype=np.float64) + ctx.rank
        s = ctx.allreduce(contrib, "sum")
        m = ctx.allreduce(contrib, "max")
        results[i] = (ctx.rank, s, m)

    results = _run_cohort(world, fn)
    expect_sum = sum(np.arange(4) + r for r in range(world))
    expect_max = np.arange(4) + (world - 1)
    for rank, s, m in results:
        np.testing.assert_allclose(s, expect_sum)
        np.testing.assert_allclose(m, expect_max)


@pytest.mark.parametrize("root", [0, 1, 3])
def test_broadcast_any_root(root):
    world = 4

    def fn(ctx, results, i):
        payload = {"cfg": "v1", "root": ctx.rank} if ctx.rank == root else None
        out = ctx.broadcast(payload, root=root)
        results[i] = out

    results = _run_cohort(world, fn)
    for out in results:
        assert out == {"cfg": "v1", "root": root}


def test_allgather():
    world = 4

    def fn(ctx, results, i):
        out = ctx.allgather(np.array([ctx.rank * 10.0]))
        results[i] = out

    results = _run_cohort(world, fn)
    for out in results:
        np.testing.assert_allclose(out.ravel(), [0, 10, 20, 30])


def test_recover_keeps_rank():
    world = 3
    tracker = RabitTracker(num_workers=world, host_ip="127.0.0.1")
    tracker.start()
    env = tracker.worker_envs()
    ranks = {}
    ready = threading.Barrier(world)

    def worker(i):
        ctx = RabitContext(env["DMLC_TRACKER_URI"],
                           int(env["DMLC_TRACKER_PORT"]), jobid=f"w{i}")
        ranks[i] = ctx.rank
        ready.wait()
        ctx.shutdown()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    # simulate restart of worker 1: recover must return the same rank
    # (links are not dialed — the old cohort is gone; a real elastic rejoin
    # would find live peers at the refreshed addresses)
    ctx = RabitContext(env["DMLC_TRACKER_URI"],
                       int(env["DMLC_TRACKER_PORT"]), jobid="w1",
                       recover=True, connect_links=False)
    assert ctx.rank == ranks[1]
    ctx.shutdown()
    tracker.stop()


WORKER_SCRIPT = r"""
import numpy as np
from dmlc_core_tpu.parallel import RabitContext
with RabitContext.from_env() as rc:
    out = rc.allreduce(np.array([float(rc.rank + 1)]))
    assert out[0] == sum(range(1, rc.world_size + 1)), out
    rc.tracker_print(f"rank {rc.rank} ok")
"""


def test_local_launcher_end_to_end(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    env = os.environ.copy()
    # the package is run from the repo, not installed: workers need it on path
    env["PYTHONPATH"] = "/root/repo" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    rc = subprocess.run(
        [sys.executable, "-m", "dmlc_core_tpu.parallel.launcher.submit",
         "--cluster", "local", "-n", "3", "--host-ip", "127.0.0.1",
         sys.executable, str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=120,
        env=env)
    assert rc.returncode == 0, rc.stderr


def test_pstracker_env_and_scheduler_spawn():
    """PSTracker parity (reference tracker.py:336-386): scheduler process
    gets DMLC_ROLE=scheduler + PS root env; workers get the same env."""
    import subprocess
    import sys
    from dmlc_core_tpu.parallel.tracker import PSTracker
    t = PSTracker(host_ip="127.0.0.1",
                  pscmd=[sys.executable, "-c",
                         "import os; "
                         "assert os.environ['DMLC_ROLE']=='scheduler'; "
                         "assert os.environ['DMLC_PS_ROOT_URI']=='127.0.0.1'; "
                         "assert int(os.environ['DMLC_PS_ROOT_PORT'])>0"])
    env = t.worker_envs()
    assert env["DMLC_PS_ROOT_URI"] == "127.0.0.1"
    assert int(env["DMLC_PS_ROOT_PORT"]) >= 9100
    t.start()
    assert t.join() == 0
    t.stop()


ELASTIC_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
# OVERRIDE (not append): under pytest the parent env carries conftest's
# device_count=8 flag; 8 virtual devices per process would make a
# 24-device mesh whose dp axis cannot divide this worker's tiny arrays
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
import numpy as np
from dmlc_core_tpu.parallel import ElasticJaxMesh, RabitContext

attempt = int(os.environ.get("DMLC_NUM_ATTEMPT", "0"))
ctx = RabitContext.from_env()
if attempt > 0:
    # reference LoadCheckPoint contract: restoring fast-forwards the rabit
    # seq so the reborn worker's control-plane frames align with survivors
    state = ctx.load_checkpoint()
    assert state == {"phase": 1}, state
mesh = ElasticJaxMesh(ctx)          # base port from DMLC_ELASTIC_BASE_PORT
mesh.initialize()
from jax.experimental import multihost_utils
if attempt == 0:
    assert mesh.generation == 0
    g = multihost_utils.process_allgather(
        np.array([float(ctx.rank + 1)], np.float32))
    assert float(g.sum()) == 6.0, g
    # one control-plane collective so seq alignment is actually exercised
    rows = ctx.allreduce(np.array([100.0], np.float32))
    assert float(rows[0]) == 300.0
    ctx.checkpoint({"phase": 1})
    if ctx.rank == 2:
        print("DYING", ctx.rank, flush=True)
        os._exit(7)                      # crash: no shutdown, no goodbye
    changed = mesh.resync()              # sync point: survivors rebuild
    assert changed, "survivors must observe the bumped generation"
assert mesh.generation == 1, mesh.generation
# post-rejoin reduction over the REBUILT jax mesh: value read-back proves
# the generation-1 collective is correct on every process
g2 = multihost_utils.process_allgather(
    np.array([10.0 * (ctx.rank + 1)], np.float32))
assert float(g2.sum()) == 60.0, g2
import jax.numpy as jnp
total = float(jax.jit(jnp.sum)(
    multihost_utils.host_local_array_to_global_array(
        np.full((2, 2), float(ctx.rank + 1), np.float32),
        jax.sharding.Mesh(np.array(jax.devices()), ("dp",)),
        jax.sharding.PartitionSpec("dp"))))
assert total == 2 * 2 * 6.0, total
# one write: the ranks share a pipe, and print() hands an unbuffered stdout
# (PYTHONUNBUFFERED) each argument separately — the lines interleaved
sys.stdout.write(f"ELASTIC-OK {ctx.rank} {mesh.generation}\n")
sys.stdout.flush()
mesh.close()
ctx.shutdown()
'''


@needs_multiprocess_cpu
def test_elastic_jax_mesh_rejoin_after_kill(tmp_path):
    """VERDICT r4 #9 (SURVEY §7 hard part (c)): kill one jax.distributed
    process mid-job; the launcher respawns it (DMLC_NUM_ATTEMPT=1), the
    cohort agrees a new mesh generation over the rabit control plane, every
    process re-initializes, and a post-rejoin psum/allgather is correct."""
    import socket as _socket
    import subprocess
    import sys

    # two consecutive free ports: generation 0 and the post-rejoin gen 1
    for _ in range(20):
        s0, s1 = _socket.socket(), _socket.socket()
        try:
            s0.bind(("127.0.0.1", 0))
            p = s0.getsockname()[1]
            s1.bind(("127.0.0.1", p + 1))
            break
        except OSError:
            continue
        finally:
            s0.close()
            s1.close()
    script = tmp_path / "elastic_worker.py"
    script.write_text(ELASTIC_WORKER)
    tracker = RabitTracker(num_workers=3, host_ip="127.0.0.1")
    tracker.start()
    # generous timeouts: this 1-core host time-slices these 3 jax
    # processes against whatever else runs (harvest probes, CI); the
    # budgets only bound the failure case — a healthy run takes ~2 min
    base_env = {**os.environ, **tracker.worker_envs(),
                "PYTHONPATH": "/root/repo",
                "DMLC_ELASTIC_BASE_PORT": str(p),
                "DMLC_CHECKPOINT_DIR": str(tmp_path),
                "DMLC_CONNECT_TIMEOUT": "120",
                "DMLC_RECOVER_TIMEOUT": "300"}

    def spawn(rank, att):
        env = dict(base_env, DMLC_TASK_ID=str(rank),
                   DMLC_NUM_ATTEMPT=str(att))
        return subprocess.Popen([sys.executable, str(script)], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
    procs = {i: spawn(i, 0) for i in range(3)}
    try:
        assert procs[2].wait(timeout=300) == 7      # crashed as scripted
        procs[2] = spawn(2, 1)                      # launcher-style retry
        outs = {}
        for i, pr in procs.items():
            out, _ = pr.communicate(timeout=480)
            outs[i] = out
            assert pr.returncode == 0, (i, out[-2000:])
        for i in range(3):
            assert f"ELASTIC-OK {i} 1" in outs[i], outs[i][-1500:]
    finally:
        for pr in procs.values():
            if pr.poll() is None:
                pr.kill()
        tracker.stop()


@needs_multiprocess_cpu
def test_elastic_rejoin_through_tpu_launcher(tmp_path):
    """The launcher half of elastic rejoin: `--cluster tpu --max-attempts 2`
    respawns the crashed rank with DMLC_NUM_ATTEMPT=1 itself (no manual
    respawn), the cohort resyncs to generation 1, and the job exits 0."""
    import subprocess
    import sys

    script = tmp_path / "elastic_worker.py"
    script.write_text(ELASTIC_WORKER)
    env = {**os.environ, "PYTHONPATH": "/root/repo",
           "DMLC_CHECKPOINT_DIR": str(tmp_path),
           "DMLC_CONNECT_TIMEOUT": "120", "DMLC_RECOVER_TIMEOUT": "300"}
    out = subprocess.run(
        [sys.executable, "-m", "dmlc_core_tpu.parallel.launcher.submit",
         "--cluster", "tpu", "-n", "3", "--max-attempts", "2",
         "--elastic", "--host-ip", "127.0.0.1",
         "--env", "PYTHONPATH=/root/repo",
         "--", sys.executable, str(script)],
        capture_output=True, text=True, timeout=600, env=env,
        cwd="/root/repo")
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-2500:])
    for i in range(3):
        assert f"ELASTIC-OK {i} 1" in out.stdout, out.stdout[-2000:]


def test_tpu_launcher_without_elastic_fails_fast(tmp_path):
    """Without --elastic a crashed tpu worker is NOT respawned: plain
    jax.distributed cannot admit a reborn process, so retry would hang —
    the launcher must surface the failure immediately instead."""
    import subprocess
    import sys
    import time as _t

    script = tmp_path / "crash.py"
    script.write_text(
        "import os, sys\n"
        "assert os.environ.get('DMLC_NUM_ATTEMPT', '0') == '0', "
        "'non-elastic job must never see a retry attempt'\n"
        "sys.exit(3 if os.environ['DMLC_TASK_ID'] == '1' else 0)\n")
    t0 = _t.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "dmlc_core_tpu.parallel.launcher.submit",
         "--cluster", "tpu", "-n", "2", "--max-attempts", "3",
         "--host-ip", "127.0.0.1", "--env", "PYTHONPATH=/root/repo",
         "--", sys.executable, str(script)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "PYTHONPATH": "/root/repo"}, cwd="/root/repo")
    assert out.returncode == 3, (out.stdout[-800:], out.stderr[-1500:])
    assert _t.monotonic() - t0 < 120


# ---------------------------------------------------------------------------
# resilience knobs: peer recv timeout + heartbeat liveness
# ---------------------------------------------------------------------------

def _solo_ctx(**kw):
    """1-worker cohort: tracker + registered context (caller tears down)."""
    tracker = RabitTracker(num_workers=1, host_ip="127.0.0.1")
    tracker.start()
    env = tracker.worker_envs()
    ctx = RabitContext(env["DMLC_TRACKER_URI"],
                       int(env["DMLC_TRACKER_PORT"]), jobid="w0",
                       heartbeat_interval=0, **kw)
    return tracker, ctx


def test_peer_recv_timeout_defaults_to_twice_recover_timeout(monkeypatch):
    monkeypatch.delenv("DMLC_PEER_RECV_TIMEOUT", raising=False)
    tracker, ctx = _solo_ctx(recover_timeout=45.0)
    try:
        assert ctx.peer_recv_timeout == 90.0
    finally:
        ctx.shutdown()
        tracker.stop()


@pytest.mark.parametrize("raw", ["0", "-3"])
def test_peer_recv_timeout_nonpositive_means_unbounded(monkeypatch, raw):
    monkeypatch.setenv("DMLC_PEER_RECV_TIMEOUT", raw)
    tracker, ctx = _solo_ctx()
    try:
        assert ctx.peer_recv_timeout is None
    finally:
        ctx.shutdown()
        tracker.stop()


def test_peer_recv_timeout_malformed_falls_back_to_default(monkeypatch):
    """An env typo must not crash worker boot — it logs and uses the
    default."""
    monkeypatch.setenv("DMLC_PEER_RECV_TIMEOUT", "garbage")
    tracker, ctx = _solo_ctx(recover_timeout=30.0)
    try:
        assert ctx.peer_recv_timeout == 60.0
    finally:
        ctx.shutdown()
        tracker.stop()


def test_tracker_declares_silent_worker_dead_and_resets_survivors():
    """Liveness: a worker that stops beating past DMLC_HEARTBEAT_TIMEOUT
    is declared dead exactly once, the dead-worker counter ticks, and the
    survivors get a reset_links push (generation bump) so their next
    collective re-rendezvouses instead of hanging on the corpse."""
    import time as _t

    from dmlc_core_tpu.utils.metrics import metrics

    dead0 = metrics.counter("tracker.dead_workers").value
    tracker = RabitTracker(num_workers=2, host_ip="127.0.0.1",
                           heartbeat_timeout_s=0.6)
    tracker.start()
    env = tracker.worker_envs()
    ctxs = {}
    errors = []

    def worker(i):
        try:
            ctxs[i] = RabitContext(env["DMLC_TRACKER_URI"],
                                   int(env["DMLC_TRACKER_PORT"]),
                                   jobid=f"w{i}", heartbeat_interval=0.1)
        except Exception as e:  # noqa: BLE001
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    survivor = ctxs[0] if ctxs[1].rank != 0 else ctxs[1]
    silent = ctxs[1] if survivor is ctxs[0] else ctxs[0]
    try:
        silent._hb_stop.set()           # worker falls silent, stays alive
        give_up = _t.monotonic() + 10
        while _t.monotonic() < give_up:
            if (metrics.counter("tracker.dead_workers").value > dead0
                    and survivor._target_gen >= 1):
                break
            _t.sleep(0.05)
        assert metrics.counter("tracker.dead_workers").value == dead0 + 1
        assert survivor._target_gen >= 1, \
            "survivor never saw the tracker's reset_links push"
    finally:
        for c in ctxs.values():
            c.shutdown()
        tracker.stop()
