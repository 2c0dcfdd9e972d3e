"""Largest over mean load of a held expert, from the program's own counters
(the ``lm.batch`` events the scoring loop adds to the span ring when it
reads a batch's scores): over the window's last ``steps`` batches, the mean
over batches of the worst mixture layer's ``load_max / load_mean``.  A
program that records no such events gives nothing."""


def read(ctx, args):
    from dmlc_core_tpu.telemetry import trace
    steps = int(ctx.values.get("steps") or 0)
    events = [r.get("attrs", {}) for r in trace.recorder.snapshot()
              if r.get("name") == "lm.batch"]
    if not steps or len(events) < steps:
        return None
    skews = []
    for attrs in events[-steps:]:
        layers = {k.rsplit(".", 1)[0] for k in attrs if k.endswith(".load_max")}
        worst = [attrs[f"{la}.load_max"] / attrs[f"{la}.load_mean"]
                 for la in layers if attrs.get(f"{la}.load_mean")]
        if worst:
            skews.append(max(worst))
    return sum(skews) / len(skews) if skews else None
