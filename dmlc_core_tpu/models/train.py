"""Training loops and mesh-sharded train steps.

TPU-first design (SURVEY §7 phase 5): parallelism is expressed as shardings
over a named :class:`jax.sharding.Mesh`, and XLA GSPMD inserts the
collectives — no hand-written allreduce:

* **dp** axis: batches are sharded on their leading axis (data parallelism;
  the mesh generalization of the reference's ``ResetPartition(rank, n)``
  input sharding); gradient reduction becomes an ICI all-reduce emitted by
  XLA.
* **mp** axis: the FM factor table ``v [F, dim]`` shards its factor dim
  (model parallelism): embedding gathers stay chip-local, only the per-row
  scalar reduction of the pairwise term crosses the mesh.

``make_train_step`` returns a jitted ``step(params, opt_state, batch) ->
(params, opt_state, loss)``.  With ``mesh``, ``in_shardings`` pin batch and
params; without, it runs single-chip.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..pipeline.device_loader import DeviceLoader
from ..utils import log_info
from ..utils.metrics import metrics
from ..utils.timer import Timer

__all__ = ["make_train_step", "make_eval_step", "batch_sharding",
           "param_shardings", "shard_params", "fit_stream", "TrainState",
           "streaming_auc", "auc_from_histograms", "evaluate_stream",
           "make_train_step_fused", "FusedTrainer",
           "make_train_step_kbatch", "stack_batches"]

TrainState = Tuple[Dict[str, jax.Array], Any]


def batch_sharding(mesh: Optional[Mesh]) -> Optional[NamedSharding]:
    """Batch arrays shard their leading (row / nnz) axis over 'dp'."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P("dp"))


def param_shardings(model, params: Dict[str, jax.Array],
                    mesh: Optional[Mesh],
                    table_shard: str = "dim",
                    ) -> Optional[Dict[str, NamedSharding]]:
    """Sharding recipe for the sparse-model family.

    ``table_shard="dim"`` (default, model parallelism): factor tables shard
    their trailing factor dim over 'mp' (FM ``v[F, d]`` and FFM
    ``v[F, nf, d]`` alike — gathers stay local, only the final per-row
    reduction crosses chips); everything else replicates.

    ``table_shard="rows"`` (embedding/parameter-server parallelism — the
    TPU expression of the reference ecosystem's ps-lite sharded state,
    SURVEY §5.8, and the DLRM-style 'ep' axis): ``v`` AND the linear ``w``
    shard their FEATURE axis over 'mp', so each chip owns a slice of the
    parameter state; XLA turns the batch's gathers into cross-chip
    collectives and keeps the optimizer update local to each shard.
    Memory per chip drops by the mesh factor — the point of ps sharding —
    at the price of gather traffic on ICI.  Feature counts must divide by
    the 'mp' axis size in rows mode (pad ``num_features`` up — padding
    rows are never gathered).
    """
    if table_shard not in ("dim", "rows"):
        raise ValueError(f"table_shard must be 'dim' or 'rows', "
                         f"got {table_shard!r}")
    if mesh is None:
        return None
    if "mp" not in mesh.axis_names:
        return {k: NamedSharding(mesh, P()) for k in params}
    out: Dict[str, NamedSharding] = {}
    for k, v in params.items():
        if k == "v" and v.ndim in (2, 3):
            spec = (P("mp", *([None] * (v.ndim - 1)))
                    if table_shard == "rows"
                    else P(*([None] * (v.ndim - 1) + ["mp"])))
            out[k] = NamedSharding(mesh, spec)
        elif k == "w" and v.ndim == 1 and table_shard == "rows":
            out[k] = NamedSharding(mesh, P("mp"))
        else:
            out[k] = NamedSharding(mesh, P())
    return out


def shard_params(params: Dict[str, jax.Array],
                 shardings: Optional[Dict[str, NamedSharding]]) -> Dict[str, jax.Array]:
    if shardings is None:
        return params
    return {k: jax.device_put(v, shardings[k]) for k, v in params.items()}


def _update(optimizer):
    """What a step does to parameters and state given the gradients, under
    the scopes that name the step's parts in every HLO ``op_name``."""
    def update(grads, opt_state, params):
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        with jax.named_scope("apply_updates"):
            params = optax.apply_updates(params, updates)
        return params, opt_state
    return update


def _sgd_step(model, optimizer, fused: bool = False):
    """The ONE SGD update recipe every step builder closes over
    (per-step, wire-fused scan, and kbatch scan must never drift).

    With ``fused`` (the step's arguments live on one TPU) the tables the
    model declares read only at ``batch["ids"]`` (``model.row_tables``)
    take the exact dense update as one streaming kernel
    (:mod:`dmlc_core_tpu.ops.table_update`) where the optimizer's update of
    them is elementwise: ``model.loss`` runs on the batch's unique rows,
    and no gradient as large as a table exists.  Otherwise, and for every
    other leaf, the update is optax's over dense gradients."""
    update = _update(optimizer)
    tables = tuple(getattr(model, "row_tables", ()))

    def dense(params, opt_state, batch):
        with jax.named_scope("loss_and_grad"):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state = update(grads, opt_state, params)
        return params, opt_state, loss

    def step(params, opt_state, batch):
        plan, why = None, "the step is not on one TPU"
        if fused:
            # imported here: Pallas takes about a second to import, which
            # only a step on a TPU needs to pay
            from ..ops import table_update
            plan, why = table_update.plan(update, params, opt_state, tables,
                                          batch["ids"].shape[0])
        _built(tables, plan, why)
        if plan is None:
            return dense(params, opt_state, batch)
        return _table_step(model, plan, params, opt_state, batch)
    return step


def _built(tables, plan, why: str) -> None:
    """The ``train.fused_tables`` gauge: table leaves that took the kernel
    in the step last built (traced)."""
    n = 0 if plan is None else len(plan.tables)
    metrics.gauge("train.fused_tables").set(n)
    if tables:
        log_info("train step: %d of tables %s take the fused update%s", n,
                 ",".join(tables), f" ({why})" if why else "")


def _table_step(model, plan, params, opt_state, batch):
    """The step with ``plan.tables`` updated by the kernel: the loss and its
    gradient over the batch's unique rows of each table, then one pass over
    every table and its optimizer state."""
    from ..ops import table_update
    tables = plan.tables
    with jax.named_scope("loss_and_grad"):
        with jax.named_scope("table_rows"):
            uid, inv, count = table_update.batch_rows(batch["ids"])
            rows = {k: table_update.gather_rows(params[k], uid, count)
                    for k in tables}
        rest = {k: v for k, v in params.items() if k not in tables}

        def loss_fn(rows, rest):
            return model.loss({**rest, **rows}, dict(batch, ids=inv))
        loss, (rows_grads, grads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(rows, rest)
    # the tables' dense gradients are never read: their place holds the
    # tables themselves
    flat = jax.tree.leaves(({**grads, **{k: params[k] for k in tables}},
                            opt_state, params))
    params, opt_state = plan.apply(flat, rows_grads, uid)
    return params, opt_state, loss


class _StepOnDevice:
    """A jitted step built once per placement of its arguments: on one TPU
    with the tables' kernel, elsewhere without (``build(fused)`` makes the
    jitted function).  Called and lowered as the jitted function is."""

    def __init__(self, build):
        self._build = build
        self._fns: Dict[Any, Any] = {}

    def _fn(self, params):
        sharding = getattr(jax.tree.leaves(params)[0], "sharding", None)
        fn = self._fns.get(sharding)
        if fn is None:
            devices = (sharding.device_set if sharding is not None
                       else {jax.devices()[0]})
            fn = self._fns[sharding] = self._build(
                len(devices) == 1
                and next(iter(devices)).platform == "tpu")
        return fn

    def __call__(self, params, *args):
        return self._fn(params)(params, *args)

    def lower(self, params, *args):
        return self._fn(params).lower(params, *args)


def _by_device(model, build):
    """``build(fused)`` on one TPU when the model declares tables, else the
    one dense jitted step (the program every other model and mesh runs)."""
    if not getattr(model, "row_tables", ()):
        return build(False)
    return _StepOnDevice(build)


def make_train_step(model, optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None, donate: bool = True):
    """Build the jitted SGD step; with a mesh, inputs/outputs carry
    NamedShardings and XLA inserts the dp gradient all-reduce."""
    donated = (0, 1) if donate else ()
    if mesh is None:
        return _by_device(model, lambda fused: jax.jit(
            _sgd_step(model, optimizer, fused), donate_argnums=donated))

    bs = batch_sharding(mesh)
    # params/opt_state shardings are inferred from the input arrays
    # themselves (shard_params places them); the batch is pinned as a
    # pytree PREFIX so every array of it shards its leading batch/nnz
    # axis over 'dp' without key-set coupling
    return jax.jit(
        _sgd_step(model, optimizer),
        in_shardings=(None, None, bs),
        donate_argnums=donated,
    )


def make_train_step_fused(model, optimizer: optax.GradientTransformation,
                          *, rows: int, meta: int, k: int,
                          with_segments: bool = False, donate: bool = True):
    """k train steps in ONE jitted dispatch: ``lax.scan`` over a stack of k
    fused wire buffers, decoding each inside the scan body.

    The per-step dispatch loop the reference's consumer runs host-side
    (``/root/reference/src/data/basic_row_iter.h:61-82``: pull block, call
    consumer, repeat) pays one host→device round trip per step.  Scanning
    k steps per dispatch amortizes it ×k and ships the k buffers as one
    ``[k, words]`` transfer — the TPU-native answer is batching dispatches,
    not a faster host loop.  (What the round trip costs on a
    direct-attached chip is ROADMAP S1's question; the PR 22 smoke ran this
    path on a v5e and found it bit-identical to the per-step loop.)

    Returns ``kstep(params, opt_state, bufs[, segs]) -> (params, opt_state,
    losses[k])``.  ``bufs`` is int32 ``[k, words]``; ``segs`` (CPU backend:
    host-precomputed per-value row ids) is ``[k, nnz]``.  params/opt_state
    are donated (``donate=True``) so the carried state updates in place.
    """
    from ..pipeline.device_loader import make_decoder
    decode = make_decoder(rows, meta)

    def build(fused):
        step = _sgd_step(model, optimizer, fused)

        def body(carry, x):
            p, o = carry
            batch = decode(*x) if with_segments else decode(x)
            p, o, loss = step(p, o, batch)
            return (p, o), loss

        if with_segments:
            def kstep(params, opt_state, bufs, segs):
                (params, opt_state), losses = jax.lax.scan(
                    body, (params, opt_state), (bufs, segs))
                return params, opt_state, losses
        else:
            def kstep(params, opt_state, bufs):
                (params, opt_state), losses = jax.lax.scan(
                    body, (params, opt_state), bufs)
                return params, opt_state, losses
        return jax.jit(kstep, donate_argnums=(0, 1) if donate else ())
    return _by_device(model, build)


class FusedTrainer:
    """Stream-order k-step training over a host-emitting DeviceLoader.

    Consumes ``("fused", buf, meta, rows)`` items from a loader built with
    ``emit="host"``, groups CONSECUTIVE same-meta buffers up to ``k``, and
    dispatches each group as one stacked transfer + one scanned step
    (:func:`make_train_step_fused`).  A meta change flushes the open group
    (partial groups scan with their own length), so steps execute in exact
    stream order — bitwise the same SGD trajectory as the per-step loop,
    just fewer dispatches (tests/test_models.py pins the equivalence).

    Per distinct ``(meta, group_len)`` one jit specialisation is compiled;
    metas quantize to ≤8 nnz buckets (packer quantum) × the few stable
    id_width/dict_bits values of a dataset, and group lengths other than
    ``k`` occur only at meta boundaries and the stream tail.
    """

    def __init__(self, model, optimizer: optax.GradientTransformation,
                 loader, *, k: int = 16, params=None, opt_state=None,
                 seed: int = 0):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.model = model
        self.optimizer = optimizer
        self.loader = loader
        self.k = int(k)
        self.rows = loader.batch_rows
        self.params = (model.init(jax.random.PRNGKey(seed))
                       if params is None else params)
        self.opt_state = (optimizer.init(self.params)
                          if opt_state is None else opt_state)
        self.losses: Optional[jax.Array] = None  # last dispatch's [kk]
        self.steps = 0
        self.rows_dispatched = 0
        self._cpu = jax.default_backend() == "cpu"
        self._kstep_cache: Dict[tuple, Any] = {}
        self._group: list = []          # [(buf, rows_real), ...]
        self._group_meta: Optional[int] = None

    def _kstep(self, meta: int, kk: int):
        key = (meta, kk)
        fn = self._kstep_cache.get(key)
        if fn is None:
            fn = make_train_step_fused(
                self.model, self.optimizer, rows=self.rows, meta=meta,
                k=kk, with_segments=self._cpu)
            self._kstep_cache[key] = fn
        return fn

    def _flush_group(self) -> None:
        if not self._group:
            return
        from ..pipeline.device_loader import (_fused_words_meta,
                                              _host_segments)
        meta = self._group_meta
        kk = len(self._group)
        words = _fused_words_meta(self.rows, meta)
        stacked = np.stack([b[:words] for b, _ in self._group])
        if self._cpu:
            from ..pipeline.device_loader import _decode_meta
            nnz = _decode_meta(meta)[0]
            segs = np.stack([_host_segments(b[:words], self.rows, nnz, words)
                             for b, _ in self._group])
        for b, _ in self._group:
            self.loader.recycle(b)
        dev = jax.device_put(stacked)
        if self._cpu:
            self.params, self.opt_state, self.losses = self._kstep(meta, kk)(
                self.params, self.opt_state, dev, jax.device_put(segs))
        else:
            self.params, self.opt_state, self.losses = self._kstep(meta, kk)(
                self.params, self.opt_state, dev)
        self.steps += kk
        self.rows_dispatched += sum(
            r if r is not None else self.rows for _, r in self._group)
        self._group = []
        self._group_meta = None

    def feed(self, item) -> None:
        """Add one host-emitted loader item; dispatches when a group fills
        or the wire meta changes (stream order is preserved either way)."""
        kind, buf, meta, rows_real = item
        if kind != "fused":
            raise ValueError(f"FusedTrainer needs fused host items, "
                             f"got {kind!r}")
        if self._group and (meta != self._group_meta
                            or len(self._group) >= self.k):
            self._flush_group()
        self._group_meta = meta
        self._group.append((buf, rows_real))
        if len(self._group) >= self.k:
            self._flush_group()

    def flush(self) -> None:
        """Submit any open partial group (end of stream / epoch)."""
        self._flush_group()

    def finish(self) -> float:
        """Flush the tail group and read back the last loss (a value read
        is completion proof on any runtime)."""
        self._flush_group()
        return float(self.losses[-1]) if self.losses is not None else 0.0

    def run_epoch(self) -> float:
        """One pass over the loader; returns the final loss (read back)."""
        for item in self.loader:
            self.feed(item)
        return self.finish()


def make_train_step_kbatch(model, optimizer: optax.GradientTransformation,
                           mesh: Optional[Mesh] = None, donate: bool = True):
    """k steps per dispatch over STACKED DEVICE BATCHES (leading axis k).

    The mesh-composable sibling of :func:`make_train_step_fused`: instead
    of scanning wire buffers (single-chip decode), it scans ordinary
    batch dicts stacked leaf-wise — ``batches[leaf].shape == (k, ...)`` —
    so the dp sharding applies to each leaf's SECOND axis
    (``P(None, 'dp')``) and XLA inserts the per-step gradient all-reduce
    inside the scan.  One dispatch runs k data-parallel SGD steps: the
    per-dispatch round trip amortizes ×k on every chip of the mesh.

    Returns ``kstep(params, opt_state, batches) -> (params, opt_state,
    losses[k])``.  Stack host batches with :func:`stack_batches`.
    """
    def kstep_of(step):
        def kstep(params, opt_state, batches):
            def body(carry, batch):
                p, o, loss = step(*carry, batch)
                return (p, o), loss
            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), batches)
            return params, opt_state, losses
        return kstep

    donated = (0, 1) if donate else ()
    if mesh is None:
        return _by_device(model, lambda fused: jax.jit(
            kstep_of(_sgd_step(model, optimizer, fused)),
            donate_argnums=donated))
    bs = NamedSharding(mesh, P(None, "dp"))    # (k, batch/nnz, ...)
    return jax.jit(kstep_of(_sgd_step(model, optimizer)),
                   in_shardings=(None, None, bs), donate_argnums=donated)


def stack_batches(batches, sharding: Optional[NamedSharding] = None):
    """Stack a list of same-shaped batch dicts leaf-wise along a new
    leading k axis, for :func:`make_train_step_kbatch`.

    Host (numpy) leaves stack on the HOST and ship as one ``device_put``
    (optionally straight into ``sharding`` — ``jnp.stack`` would first
    replicate the full stack on device 0 only for the meshed kstep to
    reshard it); device leaves stack with ``jnp.stack``."""
    keys = batches[0].keys()
    out = {}
    for k in keys:
        leaves = [b[k] for b in batches]
        if isinstance(leaves[0], np.ndarray):
            stacked = np.stack(leaves)
            out[k] = (jax.device_put(stacked, sharding)
                      if sharding is not None else jax.device_put(stacked))
        else:
            out[k] = jnp.stack(leaves)
    return out


def make_eval_step(model, mesh: Optional[Mesh] = None):
    """Jitted ``evaluate(params, batch) -> (correct, total)``; with a mesh
    the batch pins to the dp sharding like the train step."""
    def evaluate(params, batch):
        out = model.forward(params, batch)
        w = batch["weights"]
        pred = (out > 0).astype(jnp.float32)
        y = jnp.where(batch["labels"] > 0, 1.0, 0.0)
        correct = (w * (pred == y)).sum()
        return correct, w.sum()
    if mesh is None:
        return jax.jit(evaluate)
    return jax.jit(evaluate, in_shardings=(None, batch_sharding(mesh)))


def streaming_auc(scores: jax.Array, labels: jax.Array,
                  weights: jax.Array, num_bins: int = 1024):
    """One batch's contribution to a binned ROC-AUC: weighted positive /
    negative score histograms (fixed [0,1] bins over sigmoid(score), so
    accumulation across batches and ``lax.psum`` across dp ranks are both
    plain additions).  Combine with :func:`auc_from_histograms`."""
    p = jax.nn.sigmoid(scores)
    idx = jnp.clip((p * num_bins).astype(jnp.int32), 0, num_bins - 1)
    y = jnp.where(labels > 0, 1.0, 0.0)
    pos = jax.ops.segment_sum(weights * y, idx, num_segments=num_bins)
    neg = jax.ops.segment_sum(weights * (1.0 - y), idx,
                              num_segments=num_bins)
    return pos, neg


def auc_from_histograms(pos: jax.Array, neg: jax.Array) -> jax.Array:
    """Exact AUC of the binned distributions (trapezoid over the ROC steps;
    ties within a bin count half, the standard Mann-Whitney convention)."""
    total_pos = jnp.maximum(pos.sum(), 1e-12)
    total_neg = jnp.maximum(neg.sum(), 1e-12)
    # P(score_pos > score_neg) + 0.5 P(equal), walking bins ascending
    neg_below = jnp.concatenate(
        [jnp.zeros((1,), pos.dtype), jnp.cumsum(neg)[:-1]])
    wins = (pos * (neg_below + 0.5 * neg)).sum()
    return wins / (total_pos * total_neg)


def evaluate_stream(model, params, loader, *, mesh: Optional[Mesh] = None,
                    auc: bool = True):
    """One pass over ``loader``: weighted accuracy and (optionally) the
    streaming binned ROC-AUC.  Works with any loader exposing the batch
    dict contract (DeviceLoader, RemoteIngestLoader)."""
    ev = make_eval_step(model, mesh)
    fwd = jax.jit(model.forward)
    correct = total = 0.0
    pos = neg = 0.0
    for batch in loader:
        c, t = ev(params, batch)
        correct += float(c)
        total += float(t)
        if auc:
            a, b = streaming_auc(fwd(params, batch), batch["labels"],
                                 batch["weights"])
            pos, neg = pos + a, neg + b
    out = {"accuracy": correct / max(total, 1e-9), "weight": total}
    if auc:
        out["auc"] = float(auc_from_histograms(pos, neg))
    return out


def fit_stream(model, loader: DeviceLoader, *, epochs: int = 1,
               optimizer: Optional[optax.GradientTransformation] = None,
               mesh: Optional[Mesh] = None, seed: int = 0,
               log_every: int = 100, kstep: Optional[int] = None):
    """Streaming training: one pass of the ingest pipeline per epoch
    (bounded memory — the in-memory analog is BasicRowIter + full-batch).

    A loader built with ``emit="host"`` routes through the k-step fused
    dispatch (:class:`FusedTrainer`, ``kstep`` steps — default 16 — per
    device round trip; same SGD trajectory).  On that path ``history``
    holds one end-of-epoch loss per epoch when ``log_every`` is nonzero
    (per-step sampling cannot exist inside a fused dispatch), and
    ``mesh`` is unsupported (single-chip optimization).  A
    device-emitting loader runs the classic per-step loop; passing
    ``kstep`` there raises rather than silently ignoring the requested
    fusion."""
    optimizer = optimizer or optax.adam(1e-2)
    if getattr(loader, "emit", "device") == "host":
        if mesh is not None:
            raise ValueError("fused k-step training is single-chip; use a "
                             "device-emitting loader with mesh")
        trainer = FusedTrainer(model, optimizer, loader,
                               k=16 if kstep is None else kstep, seed=seed)
        history = []
        for epoch in range(epochs):
            with Timer() as t:
                loss = trainer.run_epoch()
            loader.before_first()
            if log_every:
                history.append(loss)
            log_info("epoch %d done in %.2fs (%d steps, loss %.5f)",
                     epoch, t.elapsed, trainer.steps, loss)
        return trainer.params, history
    if kstep is not None:
        raise ValueError(
            "kstep requires a loader built with emit='host' (the fused "
            "wire path); this loader emits device batches, so the k-step "
            "dispatch cannot engage — dropping the request silently "
            "would run one round trip per step")
    params = model.init(jax.random.PRNGKey(seed))
    shardings = param_shardings(model, params, mesh)
    params = shard_params(params, shardings)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(model, optimizer, mesh)

    step = 0
    history = []
    for epoch in range(epochs):
        with Timer() as t:
            for batch in loader:
                params, opt_state, loss = step_fn(params, opt_state, batch)
                step += 1
                if log_every and step % log_every == 0:
                    history.append(float(loss))
                    log_info("epoch %d step %d loss %.5f", epoch, step, float(loss))
        loader.before_first()
        log_info("epoch %d done in %.2fs (%d steps)", epoch, t.elapsed, step)
    return params, history
