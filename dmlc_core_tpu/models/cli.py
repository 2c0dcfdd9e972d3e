"""``dmlc-train``: config-file-driven training CLI.

The reference ecosystem's primary UX is an xgboost-style CLI trainer fed
by a ``key=value`` config file plus command-line overrides — the exact
use-case its `config.h` exists for (`/root/reference/include/dmlc/config.h:40`)
with hyper-parameters validated by the Parameter system
(`parameter.h:122`) and implementations picked by name through the
registry (`registry.h:27`).  This module composes our three counterparts
the same way:

    dmlc-train train.conf model=deepfm data=s3://bucket/train.libsvm

Config-file keys and CLI ``key=value`` pairs share one namespace; CLI
wins (reference convention).  Unknown keys fail loudly with the
Parameter system's candidate listing; bad enum/range values raise
``ParamError`` before any data is touched.
"""

from __future__ import annotations

import sys

from ..utils import Config, ParamError
from ..utils.parameter import Parameter, field
from ..utils.registry import Registry

MODEL_REGISTRY = Registry.get("model")


@MODEL_REGISTRY.register("logreg", "sparse logistic regression")
def _logreg(p: "TrainParams"):
    from .sparse import SparseLogReg
    return SparseLogReg(num_features=p.features, l2=p.l2)


@MODEL_REGISTRY.register("fm", "factorization machine")
def _fm(p: "TrainParams"):
    from .sparse import FactorizationMachine
    return FactorizationMachine(num_features=p.features, dim=p.dim,
                                l2=p.l2, task=p.task)


@MODEL_REGISTRY.register("ffm", "field-aware FM (libfm fields)")
def _ffm(p: "TrainParams"):
    from .ffm import FieldAwareFM
    return FieldAwareFM(num_features=p.features, num_fields=p.fields,
                        dim=p.dim, l2=p.l2, task=p.task)


@MODEL_REGISTRY.register("deepfm", "FM + deep tower")
def _deepfm(p: "TrainParams"):
    from .deep import DeepFM
    return DeepFM(num_features=p.features, dim=p.dim,
                  layers=p.layers, l2=p.l2, task=p.task)


@MODEL_REGISTRY.register("dcn", "deep & cross network v2")
def _dcn(p: "TrainParams"):
    from .dcn import DCNv2
    return DCNv2(num_features=p.features, dim=p.dim,
                 layers=p.layers, l2=p.l2, task=p.task)


def _document_scorer(p: "TrainParams", name: str, cls):
    """A language-model scorer ``cls`` built from ``arch=``, held to
    ``task=score`` over its own vocabulary rows."""
    from .hybrid_lm import load_arch
    if not p.arch:
        raise ParamError(f"model={name} needs arch=<json file of the "
                         f"architecture's published keys>")
    model = cls(load_arch(p.arch))
    if p.task != "score" or p.features != model.vocab:
        raise ParamError(
            f"model={name} scores documents by mean log-probability "
            f"over {model.vocab} vocabulary rows: set task=score and "
            f"features={model.vocab} (got task={p.task}, "
            f"features={p.features})")
    return model


@MODEL_REGISTRY.register("hybrid_moe_lm",
                         "linear + latent attention, routed experts: "
                         "scores packed token documents (forward only)")
def _hybrid_moe_lm(p: "TrainParams"):
    from .hybrid_lm import HybridMoELM
    return _document_scorer(p, "hybrid_moe_lm", HybridMoELM)


@MODEL_REGISTRY.register("sambay_lm",
                         "Mamba + differential attention + a YOCO cross-"
                         "decoder: scores packed token documents (forward "
                         "only)")
def _sambay_lm(p: "TrainParams"):
    from .sambay_lm import SambaYLM
    return _document_scorer(p, "sambay_lm", SambaYLM)


class TrainParams(Parameter):
    """All knobs of a training run (printable via ``--help``/doc_string)."""

    data = field(str, help="training data URI")   # no default → required
    mode = field(str, default="train", enum=["train", "predict"],
                 help="predict: restore ckpt_dir's latest and write "
                      "scores for `data` to `output` (xgboost task=pred)")
    output = field(str, default="",
                   help="predictions URI (predict mode; any scheme)")
    workers = field(str, default="",
                    help="comma-separated host:port ingest workers "
                         "(disaggregated ingest; train mode, fused "
                         "formats only — see docs/data.md)")
    valid = field(str, default="",
                  help="validation data URI: accuracy/AUC printed per "
                       "epoch (the reference ecosystem's watchlist)")
    format = field(str, default="auto",
                   enum=["auto", "libsvm", "libfm", "csv"],
                   help="input format ('auto': ?format= URI arg, then file "
                        "suffix .libsvm/.libfm/.csv, then libsvm; ffm "
                        "implies libfm)")
    # LAZY enum (callable, re-read per check): a hardcoded list silently
    # orphaned 'dcn' once (r4 review), and a list snapshotted at class-body
    # time would still reject models registered after this module imports
    # (user plugins — ADVICE r4); deriving from the registry at check time
    # makes registering a model the ONLY step to join the CLI
    model = field(str, default="fm",
                  enum=lambda: sorted(MODEL_REGISTRY.list_names()),
                  help="registered model name")
    features = field(int, default=1 << 20, lower_bound=1,
                     help="feature-space size (ids hashed into it)")
    fields = field(int, default=40, lower_bound=1,
                   help="field count (ffm)")
    dim = field(int, default=16, lower_bound=1, help="factor dimension")
    layers = field(int, default=2, lower_bound=1,
                   help="depth: deepfm tower / dcn cross layers")
    arch = field(str, default="",
                 help="JSON file of a published architecture's config keys "
                      "(+ held_experts, vocab_rows), for models described "
                      "by one (hybrid_moe_lm, sambay_lm)")
    task = field(str, default="binary",
                 enum=["binary", "regression", "score"],
                 help="score: the model's output is the row's score as it "
                      "stands (a document's mean log-probability)")
    epochs = field(int, default=1, lower_bound=1)
    batch_rows = field(int, default=4096, lower_bound=1)
    nnz_cap = field(int, default=131072, lower_bound=1)
    lr = field(float, default=1e-3, lower_bound=0.0)
    l2 = field(float, default=0.0, lower_bound=0.0)
    seed = field(int, default=0)
    ckpt_dir = field(str, default="", help="checkpoint dir URI ('' = off)")
    ckpt_every = field(int, default=0, lower_bound=0,
                       help="async-checkpoint every N steps (0 = only at "
                            "the end); saves overlap training and are "
                            "awaited before exit")
    resume = field(bool, default=False,
                   help="continue from the latest checkpoint in ckpt_dir "
                        "(the reference ecosystem's model_in/model_out "
                        "continuation)")
    eval_auc = field(bool, default=True,
                     help="streaming AUC over the train stream at the end")
    kstep = field(int, default=1, lower_bound=1,
                  help="train steps fused per device dispatch (lax.scan "
                       "over stacked wire buffers). 1 = classic per-step "
                       "loop; 8-16 recommended on TPU where per-dispatch "
                       "latency dominates small steps. Same SGD "
                       "trajectory either way. Ignored for ffm (fields "
                       "ride outside the fused wire); composes with "
                       "workers= ingest")
    log_every = field(int, default=100)


def _make_loader(p: "TrainParams", uri: str, fmt: str, needs_fields: bool,
                 emit: str = "device"):
    """The one place a run's ingest loader is configured: every surface
    (train, validation watchlist, end-of-run AUC, predict) must see the
    same batch shape / fields / hashing, or metrics silently disagree."""
    from ..data import create_parser
    from ..pipeline import DeviceLoader
    return DeviceLoader(
        create_parser(uri, 0, 1, fmt),
        batch_rows=p.batch_rows, nnz_cap=p.nnz_cap,
        fields=needs_fields, id_mod=p.features, emit=emit)


def _parse_argv(argv):
    """[conf-file] [key=value ...] → merged dict (CLI overrides file)."""
    conf: dict = {}
    args = list(argv)
    if args and "=" not in args[0]:
        cfg = Config()
        with open(args[0]) as f:
            cfg.load(f)
        conf.update(cfg.to_dict())
        args = args[1:]
    for a in args:
        if "=" not in a:
            raise ParamError(f"expected key=value, got {a!r}")
        k, v = a.split("=", 1)
        conf[k] = v
    return conf


def _scorer(model):
    """(jitted ``(params, batch) -> (scores, counters)``, ``note``) — the
    scoring loop's two halves.  A model that counts on the device
    (``forward_counted``) hands its counters back beside the scores;
    ``note(counters)``, called once the scores have been read (the same
    dispatch: no extra sync), adds them to the span ring as one
    ``lm.batch`` event.  Every other model has none to note."""
    import jax

    counted = getattr(model, "forward_counted", None)
    if counted is None:
        fwd = jax.jit(model.forward)
        return (lambda params, batch: (fwd(params, batch), None),
                lambda counters: None)
    from ..telemetry import trace
    return (jax.jit(counted),
            lambda counters: trace.add_event(
                "lm.batch", **model.counter_record(counters)))


def _predict(p: TrainParams, model, template_params, fmt: str,
             needs_fields: bool) -> int:
    """Restore the latest checkpoint and write one score per input row to
    ``p.output`` (text, '%.6f\\n'; sigmoid for binary task) through the io
    layer, so any registered scheme works as the sink."""
    import sys

    import jax
    import numpy as np

    from ..io import open_stream
    from ..utils import CheckpointManager, DMLCError

    if not p.ckpt_dir or not p.output:
        print("dmlc-train: predict mode needs ckpt_dir and output",
              file=sys.stderr)
        return 2
    try:
        step_no, state = CheckpointManager(p.ckpt_dir).restore(
            template={"params": template_params})
    except DMLCError as e:
        print(f"dmlc-train: {e}", file=sys.stderr)
        return 2
    meta_model = CheckpointManager(p.ckpt_dir).meta(step_no).get("model")
    if meta_model and meta_model != p.model:
        print(f"dmlc-train: checkpoint was trained as '{meta_model}' but "
              f"model={p.model} requested", file=sys.stderr)
        return 2
    params = state["params"]
    fwd, note = _scorer(model)
    n = 0
    with open_stream(p.output, "w") as out:
        loader = _make_loader(p, p.data, fmt, needs_fields)
        try:
            # one-score-per-input-row alignment: padding rows exist only at
            # the TAIL of the FINAL batch (batch_slices yields full batches;
            # only the flush pads), and loader.stats.rows is the exact real
            # row total once iteration ends — so write with a one-batch lag
            # and trim the held-back last batch.  Weights are NOT a padding
            # signal: a real row may carry an explicit weight of 0 and must
            # still get its score (ADVICE r3).
            held = None
            for batch in loader:
                scores, counters = fwd(params, batch)
                if p.task == "binary":
                    scores = jax.nn.sigmoid(scores)
                if held is not None:
                    for v in held:
                        out.write(b"%.6f\n" % float(v))
                    n += len(held)
                held = np.asarray(scores)
                note(counters)
            if held is not None:
                total = int(loader.stats.rows)
                for v in held[:max(0, total - n)]:
                    out.write(b"%.6f\n" % float(v))
                    n += 1
        finally:
            loader.close()
    print(f"wrote {n} predictions from step {step_no} -> {p.output}",
          flush=True)
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print(TrainParams.doc_string())
        return 0
    from ..utils import DMLCError
    p = TrainParams()
    try:
        p.init(_parse_argv(argv))
    except (DMLCError, OSError) as e:   # ParamError is a DMLCError; a
        # malformed config file raises DMLCError directly
        print(f"dmlc-train: {e}", file=sys.stderr)
        return 2

    import jax
    import optax

    from ..utils.compile_cache import enable_compile_cache
    from .train import (auc_from_histograms, make_train_step, streaming_auc)

    enable_compile_cache()

    try:
        model = MODEL_REGISTRY[p.model](p)
    except (DMLCError, ValueError, OSError) as e:   # the model's own file
        print(f"dmlc-train: {e}", file=sys.stderr)
        return 2
    if p.mode == "train" and not hasattr(model, "loss"):
        print(f"dmlc-train: model={p.model} is forward only (it has no "
              f"loss): use mode=predict", file=sys.stderr)
        return 2
    needs_fields = p.model == "ffm"
    fmt = p.format
    if fmt == "auto":
        if needs_fields:
            fmt = "libfm"
        elif "format=" not in p.data:
            # suffix resolution — but an explicit ?format= URI arg keeps
            # priority (fmt stays 'auto' so create_parser resolves it);
            # plain libsvm is the final default
            base = p.data.split("?")[0].rstrip("/")
            for suf in ("libsvm", "libfm", "csv"):
                if base.endswith("." + suf):
                    fmt = suf
                    break
            else:
                fmt = "auto"

    params = model.init(jax.random.PRNGKey(p.seed))

    if p.mode == "predict":
        return _predict(p, model, params, fmt, needs_fields)

    opt = optax.adam(p.lr)
    opt_state = opt.init(params)
    step = make_train_step(model, opt)

    start_n = 0
    if p.resume:
        if not p.ckpt_dir:
            print("dmlc-train: resume=true needs ckpt_dir", file=sys.stderr)
            return 2
        from ..utils import CheckpointManager, DMLCError as _DE
        try:
            # opt_state rides the checkpoint (ADVICE r3: params-only resume
            # silently reset Adam moments); older params-only checkpoints
            # restore without the key — warn, don't fail
            start_n, state = CheckpointManager(p.ckpt_dir).restore(
                template={"params": params, "opt_state": opt_state})
            params = state["params"]
            if "opt_state" in state:
                opt_state = state["opt_state"]
                print(f"resumed from step {start_n} in {p.ckpt_dir}",
                      flush=True)
            else:
                print(f"resumed params from step {start_n} in {p.ckpt_dir} "
                      "(old checkpoint without opt_state — optimizer "
                      "moments reset)", flush=True)
        except _DE:
            print(f"no checkpoint in {p.ckpt_dir} — starting fresh",
                  flush=True)

    # ONE loader, rewound between epochs (the fit_stream pattern): the
    # parser/transfer threads and pinned buffers are reused, not rebuilt
    use_fused = p.kstep > 1 and not needs_fields
    if p.workers:
        if needs_fields:
            print("dmlc-train: workers= (fused wire) does not carry "
                  "libfm fields — use local ingest for ffm",
                  file=sys.stderr)
            return 2
        from ..pipeline import RemoteIngestLoader
        addrs = []
        for tok in p.workers.split(","):
            host, _, port = tok.strip().rpartition(":")
            addrs.append((host, int(port)))
        loader = RemoteIngestLoader(addrs, batch_rows=p.batch_rows,
                                    emit="host" if use_fused else "device")
    else:
        loader = _make_loader(p, p.data, fmt, needs_fields,
                              emit="host" if use_fused else "device")
    def eval_valid(epoch: int) -> None:
        if not p.valid:
            return
        from .train import evaluate_stream
        vl = _make_loader(p, p.valid, fmt, needs_fields)
        try:
            r = evaluate_stream(model, params, vl,
                                auc=p.task == "binary")
        finally:
            vl.close()
        auc = f" auc {r['auc']:.4f}" if "auc" in r else ""
        print(f"epoch {epoch} valid acc {r['accuracy']:.4f}{auc}",
              flush=True)

    mgr = None
    if p.ckpt_dir:
        from ..utils import CheckpointManager
        mgr = CheckpointManager(p.ckpt_dir)
    elif p.ckpt_every:
        # same loud-misconfig contract as resume-without-ckpt_dir: a long
        # job silently writing zero checkpoints is unrecoverable
        print("dmlc-train: ckpt_every needs ckpt_dir", file=sys.stderr)
        return 2

    n = start_n
    loss = None
    last_async_step = -1
    trainer = None
    if use_fused:
        from .train import FusedTrainer
        trainer = FusedTrainer(model, opt, loader, k=p.kstep,
                               params=params, opt_state=opt_state)

    def after_steps(epoch: int, new_n: int, get_loss) -> None:
        """Shared logging/checkpoint cadence for both loops; in fused mode
        ``new_n`` jumps a group at a time and boundaries fire once per
        crossed multiple (at group granularity, the documented trade)."""
        nonlocal n, last_async_step
        old_n, n = n, new_n
        if p.log_every and old_n // p.log_every != n // p.log_every:
            print(f"epoch {epoch} step {n} loss {float(get_loss()):.5f}",
                  flush=True)
        if mgr is not None and p.ckpt_every \
                and old_n // p.ckpt_every != n // p.ckpt_every:
            # overlaps the next train steps (device leaves get an
            # async on-device copy — they survive donation)
            mgr.save_async(n, {"params": params,
                               "opt_state": opt_state},
                           meta={"model": p.model, "steps": int(n)})
            last_async_step = n

    try:
        for epoch in range(p.epochs):
            if trainer is not None:
                def sync(epoch=epoch):
                    nonlocal params, opt_state
                    if start_n + trainer.steps != n:
                        params, opt_state = trainer.params, trainer.opt_state
                        after_steps(epoch, start_n + trainer.steps,
                                    lambda: trainer.losses[-1])
                for item in loader:
                    trainer.feed(item)
                    sync()
                trainer.flush()
                sync()
                loss = trainer.losses[-1] if trainer.losses is not None \
                    else loss
            else:
                for batch in loader:
                    params, opt_state, loss = step(params, opt_state, batch)
                    after_steps(epoch, n + 1, lambda: loss)
            loader.before_first()
            eval_valid(epoch)
        if loss is None:
            print("dmlc-train: no batches in input", file=sys.stderr)
            return 3
        print(f"trained {p.model}: {n} steps, final loss {float(loss):.5f}",
              flush=True)

        if p.eval_auc and p.task == "binary":
            pos = neg = 0.0
            fwd = jax.jit(model.forward)
            if use_fused:
                # the train loader emits host wire buffers; scoring needs
                # device batches — a fresh device-mode loader over the
                # SAME source: the ingest workers when workers= is set
                # (p.data may only be readable from the worker hosts), the
                # local path otherwise
                if p.workers:
                    from ..pipeline import RemoteIngestLoader
                    auc_loader = RemoteIngestLoader(
                        addrs, batch_rows=p.batch_rows)
                else:
                    auc_loader = _make_loader(p, p.data, fmt, needs_fields)
            else:
                auc_loader = loader
            try:
                for batch in auc_loader:
                    s = fwd(params, batch)
                    a, b = streaming_auc(s, batch["labels"],
                                         batch["weights"])
                    pos, neg = pos + a, neg + b
            finally:
                if auc_loader is not loader:
                    auc_loader.close()
            print(f"train AUC {float(auc_from_histograms(pos, neg)):.4f}",
                  flush=True)
    finally:
        loader.close()
        if mgr is not None:
            # drain the in-flight save even when the loop raised: the last
            # published checkpoint is exactly what a crash needs for resume
            try:
                mgr.wait()
            except Exception as e:  # noqa: BLE001 — secondary failure
                print(f"dmlc-train: background checkpoint failed: {e}",
                      file=sys.stderr)

    if mgr is not None:
        mgr.wait()                     # surface any mid-train async failure
        # dedup only against a save THIS run made: a stale same-numbered
        # checkpoint from an earlier run must be overwritten, not trusted
        if last_async_step != n:
            mgr.save(n, {"params": params, "opt_state": opt_state},
                     meta={"model": p.model, "steps": int(n)})
        print(f"checkpoint step {n} -> {p.ckpt_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
