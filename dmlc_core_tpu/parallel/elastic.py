"""Elastic rejoin for the JAX process mesh (SURVEY §7 hard part (c)).

``jax.distributed`` has no native elasticity: one dead process wedges every
collective in its generation, and the coordination service cannot admit a
late joiner into a running cohort.  The reference faces the same problem
for rabit and solves it through the always-up tracker: a reborn worker
registers ``recover``, the tracker bumps the link generation, survivors
re-link (`/root/reference/tracker/dmlc_tracker/tracker.py:279-291`).

This module re-expresses that protocol for the JAX mesh, with a clean
split of planes:

* **control plane** — the rabit host collectives (brokered TCP via our
  tracker) already survive process death: the reborn process re-registers
  with ``recover`` and survivors re-link transparently inside
  ``RabitContext._with_recovery``.  Generation AGREEMENT therefore rides a
  rabit ``allreduce(max)``, which is exactly the piece of state that must
  outlive the broken data plane.
* **data plane** — generation ``g`` of the JAX mesh lives at coordinator
  address ``host:base_port+g``.  Re-initialization is a full teardown:
  ``jax.distributed.shutdown()`` + ``jax.extend.backend.clear_backends()``
  + ``initialize()`` at the new generation's port with the SAME
  process_id/world size.  (Donated/live device arrays die with the old
  backend — callers restore state from their checkpoint, the same
  contract as a reference worker reborn from ``LoadCheckPoint``.)

Protocol (:meth:`ElasticJaxMesh.resync`): every process proposes a
generation — survivors their current one, a reborn process (detected via
``DMLC_NUM_ATTEMPT`` > 0, or any process whose last collective raised)
current+1 — the rabit ``allreduce(max)`` agrees, and everyone at a lower
generation tears down and re-initializes.  Calling ``resync`` between
training phases is the sync-point pattern: cheap (one tiny host
allreduce), and a death anywhere surfaces at the next sync point instead
of wedging a device collective forever.

Proven end-to-end in
``tests/test_tracker_rabit.py::test_elastic_jax_mesh_rejoin_after_kill``:
rank 2 of 3 is killed mid-job, relaunched with a bumped attempt, and the
post-rejoin global-mesh reduction is bit-correct on every process.

**Checkpoint-free recovery** (:mod:`.reshard`): registering a
:class:`~.reshard.StateHandle` via :meth:`ElasticJaxMesh.register_state`
upgrades the rebuild from "teardown + callers reload from checkpoint" to
live redistribution — survivors snapshot their pytree shards to host
memory before teardown, the new cohort agrees a shard-ownership map over
the control plane, and missing shards move point-to-point to
reborn/remapped ranks, with leaf-granular checkpoint reads only for
shards no survivor holds.  ``resync()`` then returns the restored state
(:class:`ResyncResult`), not just "rebuilt".
"""

from __future__ import annotations

import os
from typing import Any, Optional, Tuple

import numpy as np

from ..utils import check, get_env, log_info, log_warning
from ..utils.metrics import metrics
from ..utils.parameter import env_int, parse_lenient_bool
from . import reshard as _reshard
from .rabit import RabitContext

__all__ = ["ElasticJaxMesh", "ResyncResult"]

# deliberately leaked coordination handles from torn-down generations
# whose shutdown raised — see _teardown's clear_state
_ZOMBIE_HANDLES: list = []


def _reshard_enabled() -> bool:
    """``DMLC_RESHARD=0`` kill switch: fall back to the pre-reshard
    behavior (rebuild only; callers restore from checkpoint)."""
    v = parse_lenient_bool("DMLC_RESHARD")
    return True if v is None else v


def _data_plane_enabled() -> bool:
    """``DMLC_ELASTIC_DATA_PLANE=0`` runs the elastic protocol —
    generation agreement, ordered barriers, live resharding — WITHOUT
    ``jax.distributed`` teardown/init.  For cohorts whose collectives all
    ride the control plane (single-device CPU dev runs, jaxes without
    multi-process CPU support) the data-plane rebuild is pure overhead;
    everything else in the rejoin protocol is identical."""
    v = parse_lenient_bool("DMLC_ELASTIC_DATA_PLANE")
    return True if v is None else v


class ResyncResult:
    """Outcome of a sync point — truthy iff the mesh was rebuilt, so
    existing ``if mesh.resync():`` call sites keep working.  On a rebuild
    with a registered :class:`~.reshard.StateHandle`, ``state`` is the
    redistributed pytree (None when nothing was restored) and ``stats``
    the :class:`~.reshard.ReshardStats` for the round."""

    __slots__ = ("rebuilt", "generation", "state", "stats")

    def __init__(self, rebuilt: bool, generation: int,
                 state: Any = None, stats: Any = None) -> None:
        self.rebuilt = rebuilt
        self.generation = generation
        self.state = state
        self.stats = stats

    def __bool__(self) -> bool:
        return self.rebuilt

    def __repr__(self) -> str:
        return (f"ResyncResult(rebuilt={self.rebuilt}, "
                f"generation={self.generation}, "
                f"state={'<restored>' if self.state is not None else None}, "
                f"stats={self.stats})")


class ElasticJaxMesh:
    """Generation-addressed ``jax.distributed`` membership with rejoin.

    Parameters
    ----------
    ctx:        the process's :class:`RabitContext` (control plane).
    base_port:  coordinator port of generation 0; generation ``g`` binds
                ``base_port + g`` (a dead generation's socket may linger in
                TIME_WAIT, so each generation gets a fresh port).
    host:       coordinator host (process 0's address, default from
                ``DMLC_ELASTIC_HOST`` or 127.0.0.1).
    num_processes/process_id: mesh shape; default from the rabit context.
    """

    def __init__(self, ctx: RabitContext, base_port: int = 0,
                 host: str = "", num_processes: int = 0,
                 process_id: Optional[int] = None) -> None:
        self.ctx = ctx
        if not base_port:
            # the tpu launcher exports one base for the whole cohort so
            # every process derives identical generation addresses
            base_port = get_env("DMLC_ELASTIC_BASE_PORT", 0)
            check(base_port > 0, "ElasticJaxMesh needs base_port (or the "
                                 "launcher's DMLC_ELASTIC_BASE_PORT env)")
        self.base_port = int(base_port)
        self.host = host or get_env("DMLC_ELASTIC_HOST", "127.0.0.1")
        self.num_processes = num_processes or ctx.world_size
        self.process_id = ctx.rank if process_id is None else process_id
        self.generation = -1            # not initialized yet
        # a reborn process must drag the cohort forward: its previous
        # incarnation died inside some generation g, so it proposes g+1.
        # DMLC_NUM_ATTEMPT is the launcher's rebirth marker (every backend
        # sets it on retry) — the same signal that flips rabit to recover.
        self._dirty = get_env("DMLC_NUM_ATTEMPT", 0) > 0
        self._state_handle: Optional[_reshard.StateHandle] = None
        self._last_reshard: Tuple[Any, Any] = (None, None)

    def register_state(self, handle: "_reshard.StateHandle") -> None:
        """Register the live state to preserve across generation bumps.

        With a handle registered, ``ensure()`` snapshots
        ``handle.get_state()`` to host memory BEFORE tearing the data
        plane down and redistributes it across the new cohort afterwards
        (:func:`~.reshard.redistribute`), so :meth:`resync` returns the
        restored state instead of just "rebuilt".  COLLECTIVE: register
        at the same point relative to control-plane collectives on every
        rank — the redistribute rounds run inside ``ensure()`` cohort-wide
        (register on all ranks or none; ``DMLC_RESHARD=0`` disables
        uniformly via the env)."""
        self._state_handle = handle

    # -- data-plane lifecycle --------------------------------------------
    def _coordinator(self, gen: int) -> str:
        return f"{self.host}:{self.base_port + gen}"

    def _teardown(self, final: bool = False) -> None:
        import jax
        import jax.extend as jex

        def clear_state() -> None:
            # clear the client/service references so exit hooks / the
            # re-init don't trip over what a skipped or failed shutdown
            # left behind.  The old handles are stashed IMMORTAL, never
            # released: the client's C++ destructor issues a Disconnect,
            # which blocks on the shutdown barrier (dead peers never
            # arrive) and then LOG(FATAL)s the whole process — observed
            # live ~90s after dropping the last reference.  An extra
            # uncounted incref keeps the destructor from running even at
            # interpreter teardown.  jax._src is private and moves across
            # JAX releases: degrade to a warning rather than masking the
            # real failure above
            try:
                import ctypes

                from jax._src import distributed as _dist
                state = getattr(_dist, "global_state", None)
                for attr in ("preemption_sync_manager", "client", "service"):
                    obj = getattr(state, attr, None) if state else None
                    if obj is not None:
                        ctypes.pythonapi.Py_IncRef(ctypes.py_object(obj))
                        _ZOMBIE_HANDLES.append(obj)
                        setattr(state, attr, None)
            except Exception as e2:  # noqa: BLE001 — private-API drift
                log_warning("elastic: could not clear jax distributed "
                            "state (%s) — private API moved?", e2)

        # the shutdown barrier is bounded by the budget ensure() passed to
        # initialize(), so a dead peer costs seconds, not the process
        try:
            jax.distributed.shutdown()
        except Exception as e:  # noqa: BLE001 — half-dead service
            log_warning("elastic: shutdown of generation %d raised "
                        "(%s) — proceeding", self.generation, e)
            clear_state()
        if not final:
            # the old backend holds client handles into the dead
            # coordination service; initialize() refuses to run while any
            # backend lives
            jex.backend.clear_backends()

    def _barrier(self, tag: str) -> None:
        """Control-plane rendezvous (cheap host allreduce; the rabit layer
        re-links around dead/reborn peers on its own).  A failed barrier
        means the teardown ordering it was pacing is NOT guaranteed —
        count it and mark the mesh dirty so the next sync point forces a
        generation bump instead of silently desyncing the cohort."""
        try:
            self.ctx.allreduce(np.array([0], np.int64), "max")
        except Exception as e:  # noqa: BLE001
            metrics.counter("elastic.barrier_failures").add(1)
            self._dirty = True
            log_warning("elastic: %s barrier failed (%s) — mesh marked "
                        "dirty, next sync point will bump", tag, e)

    def ensure(self, gen: int) -> None:
        """Make this process a member of mesh generation ``gen``.

        COLLECTIVE: every cohort member must call this with the same
        target generation (``resync`` guarantees it) — the teardown of
        the previous generation is ORDERED over the control plane.
        Follower clients must disconnect while the leader's coordination
        service still lives: a heartbeat or ShutdownTask RPC that lands
        on a torn-down service kills the whole process with an
        uncatchable C++ ``LOG(FATAL)`` (client.h "Terminating process…"),
        observed live when the leader rebuilt first.  The barriers are
        cohort-wide, so a reborn member (nothing to tear down) still
        paces the rendezvous and the rabit seq counters stay aligned.
        """
        check(gen >= 0, "generation must be >= 0")
        if gen == self.generation:
            return
        handle = self._state_handle
        reshard_on = handle is not None and _reshard_enabled()
        snap = None
        if reshard_on:
            # snapshot live shards to HOST memory before anything is torn
            # down: device arrays (donated or not) die with the backend,
            # host copies do not.  A failed snapshot degrades this rank to
            # a non-holder (peers/checkpoint cover it), never blocks the
            # rebuild.
            try:
                if getattr(handle, "snapshot", None) is not None:
                    # row-sharded owners (embed tables) hand back a ready
                    # HostSnapshot with ranged + replica blocks that the
                    # whole-leaf snapshot_tree path cannot express
                    snap = handle.snapshot()
                else:
                    state = handle.get_state()
                    if state is not None:
                        snap = _reshard.snapshot_tree(state)
            except Exception as e:  # noqa: BLE001 — degrade, don't wedge
                log_warning("elastic: state snapshot failed (%s) — this "
                            "rank recovers from peers/checkpoint", e)
                snap = None
        data_plane = _data_plane_enabled()
        if data_plane:
            import jax
            # without this, the coordination client's error-polling thread
            # LOG(FATAL)s the WHOLE process the moment any peer dies
            # ("client.h Terminating process because the JAX distributed
            # service detected fatal errors") — survivors must outlive a
            # peer death to rejoin.  the flag is version-dependent: degrade
            # to a warning on JAX builds that dropped/renamed it instead of
            # refusing to start
            try:
                jax.config.update("jax_enable_recoverability", True)
            except Exception as e:  # noqa: BLE001 — flag absent in this JAX
                log_warning("elastic: jax_enable_recoverability unavailable "
                            "(%s) — peer-death survival depends on this JAX "
                            "build's defaults", e)
        self._barrier("pre-rebuild")
        if self.process_id != 0:
            if self.generation >= 0 and data_plane:
                self._teardown()
            self._barrier("followers-down")
        else:
            self._barrier("followers-down")
            if self.generation >= 0 and data_plane:
                self._teardown()
        if self.generation < 0 and data_plane:
            # a process that COMPUTED before joining (a reborn rank redoes
            # its epoch from checkpoint first — see initialize()'s rebirth
            # caveat) has an initialized backend, and
            # jax.distributed.initialize refuses to run after any jax
            # call; clear it (live device arrays die — callers restore
            # from their host-side checkpoint, the documented contract)
            import jax.extend as jex
            jex.backend.clear_backends()
        log_info("elastic: joining mesh generation %d at %s "
                 "(process %d/%d%s)", gen, self._coordinator(gen),
                 self.process_id, self.num_processes,
                 "" if data_plane else ", control plane only")
        overlap = (reshard_on and data_plane and
                   parse_lenient_bool("DMLC_RESHARD_OVERLAP") is not False)
        reshard_box: dict = {}
        reshard_thread = None
        if overlap:
            # redistribute rides the rabit control plane ONLY (brokered
            # TCP through the tracker — never the jax backend), so its
            # fetch rounds can run concurrently with
            # jax.distributed.initialize and the coordination-service
            # rendezvous hides behind the bulk transfers.  The cohort is
            # already agreed (barriers above), so reborn/remapped ranks
            # participate exactly as in the sequential path.  Only this
            # thread touches ctx collectives until the join below.
            import threading

            def _run_redistribute() -> None:
                try:
                    reshard_box["out"] = _reshard.redistribute(
                        self.ctx, snap, plan=handle.plan,
                        checkpoint=handle.resolve_checkpoint(),
                        checkpoint_step=handle.checkpoint_step,
                        template=handle.resolve_template(),
                        generation=gen)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    reshard_box["err"] = e

            reshard_thread = threading.Thread(
                target=_run_redistribute, name="reshard-overlap",
                daemon=True)
            reshard_thread.start()
            metrics.counter("elastic.reshard_overlaps").add(1)
        if data_plane:
            # short heartbeat/shutdown budgets (env-tunable): a dead peer
            # must be detected in seconds, and teardown of a broken
            # generation must be BOUNDED — the default 300 s shutdown
            # timeout lets the gen-g service (process 0) and a surviving
            # client block each other long enough that the gen-g+1
            # rendezvous misses ITS window.  The next generation is a
            # fresh service on a fresh port; nothing of the old one is
            # worth waiting minutes for.
            jax.distributed.initialize(
                coordinator_address=self._coordinator(gen),
                num_processes=self.num_processes,
                process_id=self.process_id,
                heartbeat_timeout_seconds=env_int(
                    "DMLC_ELASTIC_HEARTBEAT_S", 10, minimum=1),
                shutdown_timeout_seconds=env_int(
                    "DMLC_ELASTIC_SHUTDOWN_S", 10, minimum=1))
        self.generation = gen
        self._dirty = False
        if reshard_on:
            if reshard_thread is not None:
                reshard_thread.join()
                if "err" in reshard_box:
                    raise reshard_box["err"]
                restored, stats = reshard_box["out"]
            else:
                # sequential path (DMLC_RESHARD_OVERLAP=0, or control
                # plane only): redistribute after the new generation is
                # up; peers → leaf-granular checkpoint → cohort-wide
                # error (see reshard.redistribute)
                restored, stats = _reshard.redistribute(
                    self.ctx, snap, plan=handle.plan,
                    checkpoint=handle.resolve_checkpoint(),
                    checkpoint_step=handle.checkpoint_step,
                    template=handle.resolve_template(), generation=gen)
            self._last_reshard = (restored, stats)
            if restored is not None and handle.set_state is not None:
                handle.set_state(restored)
        else:
            self._last_reshard = (None, None)

    # -- failure handling -------------------------------------------------
    def mark_failed(self) -> None:
        """Record that a data-plane collective failed (caller caught the
        exception); the next :meth:`resync` proposes a bump."""
        self._dirty = True

    def resync(self) -> "ResyncResult":
        """Sync point: agree on the cohort's generation over the control
        plane and re-initialize if it moved.  Returns a
        :class:`ResyncResult` — truthy iff the mesh was rebuilt (drop-in
        for the old bool).  With a :meth:`register_state` handle, a
        rebuild carries the redistributed state in ``.state`` (survivor
        shards reassembled over the control plane; checkpoint only for
        shards no survivor held), so callers re-place it with the new
        mesh's sharding instead of reloading from checkpoint.

        Two host ``allreduce(max)`` rounds — the rabit layer re-links
        around dead/reborn peers on its own (tracker ``recover``), so this
        works exactly when the data plane is broken:

        1. *learn*: max over every process's current generation — a reborn
           process arrives at generation -1 and must not guess the
           cohort's position;
        2. *agree*: dirty processes (reborn, or survivors whose last
           device collective raised) propose cohort+1, the rest cohort;
           the max wins and everyone below it rebuilds.
        """
        cohort = int(self.ctx.allreduce(
            np.array([self.generation], np.int64), "max")[0])
        propose = cohort + 1 if self._dirty else cohort
        agreed = int(self.ctx.allreduce(
            np.array([propose], np.int64), "max")[0])
        agreed = max(agreed, 0)   # first-ever sync point: start at gen 0
        if agreed == self.generation:
            return ResyncResult(False, self.generation)
        self.ensure(agreed)
        restored, stats = self._last_reshard
        return ResyncResult(True, self.generation, restored, stats)

    def initialize(self) -> None:
        """First join: generation 0, or — when reborn — whatever the
        surviving cohort agrees at the sync point.

        REBIRTH CAVEAT: on rebirth this resyncs immediately, which is
        only frame-aligned when the survivors' next control-plane
        collective is ALSO resync (they crashed past their last sync
        point).  If survivors run other collectives first (e.g. an
        epoch-loss allreduce before their resync, as
        ``examples/elastic_train.py`` does), a reborn process must SKIP
        initialize(), redo its work from the checkpoint, run the same
        collectives the survivors are blocked in, and let the shared
        sync point's :meth:`resync` perform the join — mixing resync's
        allreduce with a different collective at the same frame corrupts
        both."""
        if self._dirty:
            # don't guess the cohort's current generation; ask it
            self.resync()
        else:
            self.ensure(0)

    def close(self) -> None:
        """Graceful ORDERED cohort exit.

        Recoverable-task mode skips the coordination service's
        synchronized Shutdown barrier by design (the service says so in
        its log), so an unordered exit races: the leader (process 0, who
        HOSTS the service) can finish its own shutdown and exit while a
        follower's ShutdownTask RPC is in flight — and the follower side
        fails with an uncatchable C++ ``LOG(FATAL)`` (client.h
        "Terminating process…"), killing the process after all its work
        succeeded.  The control plane sequences the teardown instead:

        1. barrier: everyone has finished computing;
        2. followers disconnect (their ShutdownTask lands on a live
           service);
        3. barrier: followers confirm they are out;
        4. the leader tears down client + service last.
        """
        if self.generation < 0:
            return
        self._barrier("pre-close")
        data_plane = _data_plane_enabled()
        if self.process_id != 0:
            if data_plane:
                self._teardown(final=True)
            self._barrier("followers-out")
        else:
            self._barrier("followers-out")
            if data_plane:
                self._teardown(final=True)
        self.generation = -1
