"""A ratio of the mixture layers' own counters (the ``lm.batch`` events the
scoring loop adds to the span ring when it reads a batch's scores), over
the window's last ``steps`` batches.  ``args``: ``over`` and ``under`` name
two counters (a layer's ``<layer>.<counter>``, or a batch's, as ``tokens``);
``across`` says how a batch's layers become one number (``mean`` of the
layers' ratios, or the ``worst``, the largest); ``scale`` multiplies the
mean over batches.  A program that records no such events or lacks either
counter (a parent commit from before it existed) gives nothing."""


def read(ctx, args):
    from dmlc_core_tpu.telemetry import trace
    steps = int(ctx.values.get("steps") or 0)
    events = [r.get("attrs", {}) for r in trace.recorder.snapshot()
              if r.get("name") == "lm.batch"]
    if not steps or len(events) < steps:
        return None
    over, under = args["over"], args["under"]
    per_batch = []
    for attrs in events[-steps:]:
        layers = sorted(k.rsplit(".", 1)[0] for k in attrs
                        if k.endswith("." + over))
        ratios = []
        for la in layers:
            below = attrs.get(f"{la}.{under}", attrs.get(under))
            if below:
                ratios.append(attrs[f"{la}.{over}"] / below)
        if ratios:
            per_batch.append(max(ratios) if args["across"] == "worst"
                             else sum(ratios) / len(ratios))
    if not per_batch:
        return None
    return float(args.get("scale", 1.0)) * sum(per_batch) / len(per_batch)
