#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep of offered rates.

  python3 chipbench/sweep.py --workload <serve cell> --rates 20,40,80 \
      --seconds 10 --seed 1

One engine, warmed once; each rate runs the cell's own traffic open-loop
for --seconds.  For each rate it prints the end-to-end numbers and the
backlog: time to first token of the requests due in the window's last
quarter against its first quarter.  The knee is the highest rate whose
backlog does not grow; the cell's traffic file takes a fixed rate below
it.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "chipbench_out" / "tpu_logs"))

from chipbench import arrivals, harness    # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell, cfg, tr, _ = harness.load_cell(args.workload)
    harness.device_info(cell["chips"])
    harness.use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench.drivers import serve
    ctx = {"cell": cell, "cfg": cfg, "traffic": tr,
           "dims": harness.model_dims(cfg), "seed": args.seed,
           "pseed": harness.program_seed(args.seed),
           "seconds": args.seconds, "spans": harness.Spans(), "trace": False,
           "start_trace": lambda: None, "stop_trace": lambda: None}
    engine, _, _ = serve.build(ctx)
    serve.warm(engine, tr, args.seconds)
    for rate in [float(r) for r in args.rates.split(",")]:
        t = dict(tr, rate_per_s=rate)
        due = arrivals.schedule(t, args.seed, args.seconds,
                                ctx["dims"]["vocab"])
        w = serve.open_loop(dict(ctx, traffic=t), engine, due)
        e2e, done = serve.summarize(due, w, args.seconds)
        ttft, _, _ = arrivals.latencies(due, w["emitted"])
        ttft = np.asarray(ttft)
        q = len(due) // 4
        row = dict(rate=rate, due=len(due), done=len(done),
                   drain_s=w["end_s"] - args.seconds,
                   ttft_first_q_ms=1e3 * float(np.median(ttft[:q])),
                   ttft_last_q_ms=1e3 * float(np.median(ttft[-q:])),
                   late_p99_ms=1e3 * float(np.percentile(w["late"], 99)),
                   **e2e)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
