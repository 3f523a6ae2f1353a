#!/usr/bin/env python3
"""The readings that the limits of `correct` are set from, on the chip.

  python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
      --seconds 8 --out <file.json>

For each seed, in one process: a run of the cell (a short window at the
cell's own load), whose numbers are the program's sound readings; the
control, which is the reference computed in bfloat16 and put in the
program's place; and each fault the cell can have, planted in the
reference put in the program's place (training: half of the batch left
out; a state left unchanged reads 1 by the measure and needs no run) or
in the program's served tokens (serving: one token altered).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "chipbench_out" / "tpu_logs"))

from chipbench import compare, harness     # noqa: E402


def _ctx(workload, seed, seconds, overrides):
    cell, cfg, traffic, _ = harness.load_cell(workload,
                                              overrides.get("bench"))
    cfg = overrides.get("cfg", cfg)
    traffic = overrides.get("traffic", traffic)
    dims = harness.model_dims(cfg, overrides.get("families", ()))
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "dims": dims,
            "seed": seed, "pseed": harness.program_seed(seed),
            "seconds": seconds, "trace": False, "spans": harness.Spans(),
            "fault": None, "chips": cell["chips"],
            "memory_peak": lambda: harness.memory_peak_bytes(cell["chips"]),
            "start_trace": lambda: None, "stop_trace": lambda: None}


def train_readings(ctx, out):
    import jax.numpy as jnp
    from chipbench.drivers import train
    ci = ctx["check_inputs"]
    beta1 = ctx["cfg"]["optimizer"]["beta1"]
    rows = {"program": {k: v for k, v in out["check"].items()
                        if not k.startswith("_")}}
    runs = [("control_bf16", {"dtype": jnp.bfloat16}),
            ("fault_half_batch", {"fault": "half_batch"})]
    if ctx["chips"] > 1:
        runs.append(("fault_no_exchange", {"fault": "no_exchange"}))
    for name, kw in runs:
        r = train.reference_rounds(ci["base"], ci["start"], ci["inputs"],
                                   ctx["dims"], ctx["cfg"],
                                   chips=ctx["chips"], n_dev=ctx["chips"],
                                   **kw)
        as_prog = {"losses": r["losses"], "start": ci["start"],
                   "after": r["after"],
                   "m1": compare._scale(r["g1"], 1.0 - beta1)}
        nums = compare.train_numbers(as_prog, ci["ref"], beta1)
        rows[name] = {k: v for k, v in nums.items() if not k.startswith("_")}
    rows["fault_state_unchanged"] = {"change_gap": 1.0, "grad_gap": 1.0}
    return rows


def serve_readings(ctx, out):
    import jax.numpy as jnp
    from chipbench.drivers import serve
    ci = ctx["check_inputs"]
    pick, served, ref = ci["pick"], ci["served"], ci["ref"]
    args = (ci["base"], ci["pool"], pick)
    size = (ctx["dims"], ctx["traffic"]["max_len"])
    low = serve.reference_readout(*args, served, *size, dtype=jnp.bfloat16)
    ctrl = serve.reference_readout(
        *args, served, *size, at={r: v["top"] for r, v in low.items()})
    r0 = pick[0].rid
    bad = list(served[r0])
    bad[len(bad) // 2] = (bad[len(bad) // 2] + 1) % ctx["dims"]["vocab"]
    fault = serve.reference_readout(*args, served, *size,
                                    at={**served, r0: bad})
    per_token = {str(r): {"margin": ref[r]["margin"].tolist(),
                          "program": ref[r]["gap"].tolist(),
                          "control": ctrl[r]["gap"].tolist()}
                 for r in served}
    return {"program": {k: v for k, v in out["check"].items()
                        if not k.startswith("_")},
            "control_bf16": compare.serve_numbers(ctrl),
            "fault_token_altered": compare.serve_numbers(fault),
            "_per_token": per_token}


def main(argv=None, *, require_tpu=True, overrides=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    overrides = overrides or {}
    cell, _, traffic, _ = harness.load_cell(args.workload,
                                            overrides.get("bench"))
    harness.device_info(cell["chips"], require_tpu=require_tpu)
    harness.use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    import importlib
    traffic = overrides.get("traffic", traffic)
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    results = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ctx = _ctx(args.workload, seed, args.seconds, overrides)
        out = driver.run(ctx)
        rows = (train_readings if traffic["driver"] == "train"
                else serve_readings)(ctx, out)
        rows["seed"] = seed
        rows["e2e"] = out["e2e"]
        rows["memory_peak_bytes"] = out["memory_peak_bytes"]
        rows["where"] = out["check"].get("_where")
        results.append(rows)
        print(json.dumps({k: v for k, v in rows.items()
                          if not k.startswith("_")}), flush=True)
        harness.log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
        del ctx, out
        gc.collect()
    path = pathlib.Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
