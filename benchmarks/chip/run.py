"""One command, one cell, one run:

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Refuses (exit 2, no result line) unless JAX reports a TPU with
as many chips as the cell asks for.  Set-up — native build, corpus, weights,
warm-up of this cell's own shapes, the first checked steps — is timed as
``setup_s``; then the window; then the comparison with the plain reference;
then one JSON line, the last of standard output.  Everything else goes to
standard error.

Which cells, configurations, traffic mixes and per-layer metrics exist is
data: ``BENCHMARK.json`` and the files ``manifest.py`` finds by name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # the process's start, as near as Python gets

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as manifest_mod   # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a traffic kind and a reader may use.  Created by the caller and
    passed down; nothing here is module state."""

    def __init__(self, man, cell_name: str, seed: int, seconds: float,
                 trace: bool):
        self.manifest = man
        self.cell = man.cell(cell_name)
        self.cfg = man.config(cell_name)
        self.traffic = man.traffic(cell_name)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        # a fixed place inside the checkout: scratch files of this cell
        self.work = os.path.join(man.repo_root, ".chipbench", cell_name)
        self.say = say
        self.values: dict = {}      # generic quantities the kind measured
        self.trace_reduced = None   # xplane.reduce() of the traced window
        self.peaks = None

    def fresh_work_dir(self) -> str:
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        return self.work


def device_stamp() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(man, cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float | None = None) -> dict:
    """Everything after the look for a chip: set-up, window, comparison.
    Returns the result object (the last line, not yet printed)."""
    import jax.profiler as prof

    import peaks as peaks_mod
    import xplane
    from compile_meter import CompileMeter

    t_start = time.perf_counter() if t_start is None else t_start
    ctx = Context(man, cell_name, seed, seconds, trace)
    stamp = device_stamp()
    if stamp["platform"] == "tpu":
        ctx.peaks = peaks_mod.device_peaks(stamp["kind"])
    meter = CompileMeter.get()
    kind = man.module("traffic", ctx.traffic["kind"])
    cell = kind.Cell(ctx)
    ctx.fresh_work_dir()
    # the loader's "auto" knobs read a tuned-config file: a fresh path, so
    # no stale untracked .dmlc_tuned.json steers a run
    os.environ["DMLC_TUNED_CONFIG"] = os.path.join(ctx.work, "tuned.json")
    try:
        cell.setup()
        c_s, c_n, c_hits = meter.snapshot()
        ctx.values["compile_s"] = c_s
        say(f"[setup] compile={c_s:.2f}s ({c_n} programs, {c_hits} from the "
            f"persistent cache)")
        trace_dir = os.path.join(ctx.work, "trace")
        window_s = seconds
        if trace:
            window_s = min(seconds, float(ctx.traffic.get("trace_seconds", 3)))
            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            prof.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        try:
            with prof.TraceAnnotation("bench.window"):
                cell.window(window_s)
        finally:
            if trace:
                prof.stop_trace()
        w_s, w_n, _ = meter.snapshot()
        ctx.values["window_compiles"] = w_n - c_n
        if w_n != c_n:
            say(f"[window] {w_n - c_n} programs were compiled or loaded "
                f"INSIDE the window ({w_s - c_s:.2f}s): a shape the warm-up "
                f"did not cover")
        peak = memory_peak_bytes()
        device = dict(stamp, memory_peak_bytes=peak)
        breakdown = None
        if trace:
            t0 = time.perf_counter()
            red = xplane.reduce(xplane.read(xplane.find_xplane(trace_dir)),
                                host_labels=cell.host_labels)
            shutil.rmtree(trace_dir, ignore_errors=True)
            ctx.trace_reduced = red
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            say(f"[trace] reduced in {time.perf_counter() - t0:.1f}s: busy "
                f"{red['busy_s']:.3f}s of {red['window_s']:.3f}s on "
                f"{red['chips']} chip(s)")
        checks = cell.verify()
    finally:
        cell.close()
        shutil.rmtree(ctx.work, ignore_errors=True)

    metrics = {}
    if trace:
        for m in man.metrics_for(cell_name, "per_layer"):
            spec = man.layer_metric(m["name"])
            value = man.module("readers", spec["reader"]).read(
                ctx, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ctx.values["setup_s"] = setup_s
        for m in man.metrics_for(cell_name, "end_to_end"):
            key = ctx.traffic.get("reports", {}).get(m["name"], m["name"])
            metrics[m["name"]] = {"value": ctx.values[key], "unit": m["unit"]}

    correct = all(c["ok"] for c in checks) and bool(checks)
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"],
                            "ok": c["ok"]} for c in checks}
    for c in checks:
        say(f"[correct] {c['name']}: {c['value']:.6g} "
            f"(limit {c['limit']:.6g}) {'ok' if c['ok'] else 'FAILED'}")
    result = {"correct": correct, "attempted": int(ctx.values["attempted"]),
              "failed": int(ctx.values["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest_mod.Manifest(REPO, HERE)
    cell = man.cell(args.workload)
    # the TPU runtime keeps its logs under /tmp/tpu_logs unless told where:
    # inside the checkout, so two checkouts on one machine share nothing
    logs = os.path.join(REPO, ".chipbench", "tpu_logs")
    os.makedirs(logs, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", logs)
    # the program and the harness share one rule for where the persistent
    # compile cache lives: JAX_COMPILATION_CACHE_DIR if set, else a fixed
    # directory in the checkout
    from dmlc_core_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    stamp = device_stamp()
    if stamp["platform"] != "tpu" or stamp["count"] != cell["chips"]:
        say(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
            f"chip(s); JAX reports {stamp} — refusing to run")
        return 2
    say(f"[device] {stamp} jax={jax.__version__} compile_cache={cache}")
    result = run_cell(man, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
