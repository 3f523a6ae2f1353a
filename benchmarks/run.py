"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # all (smoke scale)
  PYTHONPATH=src python -m benchmarks.run bench_cutlayer
  BENCH_SCALE=full PYTHONPATH=src python -m benchmarks.run   # paper scale
  PYTHONPATH=src python -m benchmarks.run --dry-run    # CI smoke (minutes)

--dry-run shrinks every bench to collection-test scale (see
benchmarks.common) so CI catches kernel/bench drift on CPU without
hardware; numbers produced under it are meaningless.

Prints ``name,us_per_call,derived`` CSV and writes results/bench.json.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
import traceback

BENCHES = [
    "bench_kernels",        # kernel layer microbenchmarks
    "bench_cutlayer",       # Table I / Fig 2b
    "bench_lora_rank",      # Table II / Fig 2c
    "bench_rank_sides",     # Fig 2a
    "bench_adaptive",       # Fig 3
    "bench_models",         # Fig 4
    "bench_compression",    # beyond paper (adapter channel)
    "bench_smashed",        # beyond paper (smashed f2/f4 channel)
    "bench_scheduler",      # beyond paper (round schedulers, time-to-loss)
    "bench_traces",         # beyond paper (non-stationary heterogeneity)
    "bench_fleet",          # beyond paper (population sweep + two-tier agg)
    "bench_serve",          # beyond paper (multi-adapter serving engine)
    "bench_roofline",       # §Roofline summary
]


def main() -> int:
    argv = sys.argv[1:]
    if "--dry-run" in argv:
        # must land in os.environ before the bench modules (and through
        # them benchmarks.common) are first imported below
        os.environ["BENCH_DRYRUN"] = "1"
        argv = [a for a in argv if a != "--dry-run"]
        print("# dry-run: collection-test scale, numbers not meaningful")
    picked = argv or BENCHES
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for mod_name in picked:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows = mod.run()
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.6g}")
            all_rows.append(r)
        print(f"# {mod_name}: {len(rows)} rows in {time.time()-t0:.1f}s")
    os.makedirs("results", exist_ok=True)
    with open("results/bench.json", "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    if failed:
        print(f"# FAILED: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
