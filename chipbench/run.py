#!/usr/bin/env python3
"""Run one benchmark cell once.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`; its configuration
file, its model family (families/<model_type>.py), its traffic file
(which names the driver under drivers/) and its per-layer readers
(metrics/<name>.py) are found by name.  The run loads,
warms up, measures for --seconds, checks what the timed path produced
against the plain reference, and prints one JSON line last on stdout:
the end-to-end metrics with --trace 0, the per-layer metrics with
--trace 1.  Without the chip the cell asks for it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import os            # noqa: E402
import pathlib       # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# the TPU runtime logs under /tmp unless told otherwise: keep it in here
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "chipbench_out" / "tpu_logs"))

from chipbench import compare, harness          # noqa: E402
from chipbench.harness import BenchError, log    # noqa: E402


def parser():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def _tracer(ctx, enabled):
    """start/stop closures for the measured window's profiler trace."""
    state = {"dir": None}

    def start():
        if not enabled:
            return None
        import jax
        d = ROOT / "chipbench_out" / "trace" / ctx["cell"]["name"]
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(d), profiler_options=opts)
        ctx["spans"].annotate = True
        state["dir"] = str(d)
        return state["dir"]

    def stop():
        if state["dir"] is None:
            return
        import jax
        ctx["spans"].annotate = False
        jax.profiler.stop_trace()

    return start, stop


def main(argv=None, *, require_tpu=True, overrides=None):
    """Exit status; the result line is printed only when it is 0.
    overrides (tests only): replace the cell's configuration or traffic,
    look for families in more directories first, and plant a fault under
    the timed path."""
    args = parser().parse_args(argv)
    overrides = overrides or {}
    try:
        cell, cfg, traffic, bench = harness.load_cell(
            args.workload, overrides.get("bench"))
        cfg = overrides.get("cfg", cfg)
        traffic = overrides.get("traffic", traffic)
        device = harness.device_info(cell["chips"], require_tpu=require_tpu)
        peaks = harness.peaks_for(device["kind"], require=require_tpu)
        harness.use_compile_cache()
        src = ROOT / "src"
        if not (src / "repro").is_dir():
            raise BenchError(f"the program is not in this checkout ({src})")
        sys.path.insert(0, str(src))
        driver = importlib.import_module(
            f"chipbench.drivers.{traffic['driver']}")
        ctx = {"cell": cell, "cfg": cfg, "traffic": traffic,
               "dims": harness.model_dims(cfg,
                                          overrides.get("families", ())),
               "seed": args.seed,
               "pseed": harness.program_seed(args.seed),
               "seconds": args.seconds, "trace": bool(args.trace),
               "spans": harness.Spans(), "fault": overrides.get("fault"),
               "chips": cell["chips"],
               "memory_peak": lambda: harness.memory_peak_bytes(
                   cell["chips"])}
        ctx["start_trace"], ctx["stop_trace"] = _tracer(ctx, args.trace)
        out = driver.run(ctx)
    except (BenchError, ImportError, FileNotFoundError) as e:
        log(f"no result: {type(e).__name__}: {e}")
        return 2

    setup_s = ctx["setup_end"] - T_START
    limits = compare.load_limits(cell["name"])
    correct, rows = compare.judge(out["check"], limits)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        metrics, breakdown = per_layer(cell, bench, ctx, out, peaks)
        tr = out.get("_trace")
        if tr is not None and tr.devices:
            device.update(busy_s=tr.mean_busy_s(), window_s=tr.window_s)
        result["metrics"] = metrics
        result["device"] = device
        if breakdown:
            result["breakdown"] = breakdown
    else:
        vals = dict(out["e2e"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in harness.metrics_for(cell, bench, "end_to_end")}
        result["device"] = device
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in rows}
    log(f"setup_s {setup_s:.3f}; where: "
        f"{json.dumps(out['check'].get('_where', {}))}")
    for name, v, lim in rows:
        log(f"check {name} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


def per_layer(cell, bench, ctx, out, peaks):
    from chipbench import trace as trace_lib
    tr = trace_lib.load(out["trace_dir"], n_devices=cell["chips"])
    out["_trace"] = tr
    rctx = {"trace": tr, "spans": ctx["spans"].records,
            "counters": out["counters"],
            "window_s": out["counters"].get("traced_s") or out["window_s"],
            "dims": ctx["dims"], "peaks": peaks, "chips": cell["chips"],
            "traffic": ctx["traffic"], "cfg": ctx["cfg"]}
    metrics = {}
    if peaks is None or not tr.devices:
        log("no device planes or peaks: no per-layer metric to read")
        return metrics, None
    for m in harness.metrics_for(cell, bench, "per_layer"):
        v = harness.metric_reader(m["name"])(rctx)
        if v is None or not math.isfinite(v):
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    gc.collect()
    return metrics, breakdown


if __name__ == "__main__":
    sys.exit(main())
