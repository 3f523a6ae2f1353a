"""Serving driver: continuous-batching multi-adapter inference.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small \
      --reduced --adapters 4 --requests 16 --arrival-rate 8 \
      --num-slots 4 --page-size 16

Thin CLI over runtime.serving.ServingEngine: `build(args)` builds (or
loads) a stacked per-client adapter pool and synthesizes a Poisson
request workload; `main` runs the engine and prints latency/throughput
and where the host's time went: the engine's `serve.*` spans
(repro.runtime.spans) summed by name — admission with its prefills,
decode ticks with their wait for the tick's tokens.
With --ckpt the pool is the SplitFT checkpoint's per-client personalized
adapters — gathered from PopulationStore slots in population mode, so
--adapters picks how many fleet members to serve.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--adapters", type=int, default=4,
                    help="number of adapters in the serving pool")
    ap.add_argument("--requests", type=int, default=16,
                    help="number of requests in the workload")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals/sec (0 = all arrive at t=0)")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="concurrent decode slots (continuous batch size)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV cache page size in tokens (0 = contiguous)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot KV capacity (0 = prompt-len + gen)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build(args):
    """(ServingEngine, [Request]) for parsed CLI args."""
    import jax
    from repro.config import reduced as reduced_cfg
    from repro.configs import get_config
    from repro.core.system import SplitFTSystem, SystemConfig
    from repro.models.model import build_model
    from repro.runtime import serving

    arch = get_config(args.arch)
    if args.reduced:
        arch = reduced_cfg(arch)
    model = build_model(arch)
    # independent keys per consumer — reusing one key across init_params,
    # the adapter pool, and the prompt draw correlates "random" streams
    key = jax.random.PRNGKey(args.seed)
    k_params, k_pool, k_prompts = jax.random.split(key, 3)

    if args.ckpt:
        system = SplitFTSystem(
            arch, SystemConfig(num_samples=64, eval_samples=16,
                               checkpoint_dir=args.ckpt), seed=args.seed)
        assert system.restore(), f"no checkpoint under {args.ckpt}"
        params = system.base_params
        if system.store is not None:
            pool = serving.pool_from_population(
                model, system.state, system.store,
                list(range(args.adapters)))
        else:
            pool = serving.pool_from_state(model, system.state)
            n = serving.num_pool_adapters(pool)
            if args.adapters > n:
                raise ValueError(
                    f"--adapters {args.adapters} exceeds the checkpoint's "
                    f"{n} per-client adapters")
            pool = jax.tree.map(lambda v: v[:, :args.adapters], pool)
    else:
        params = model.init_params(k_params)
        pool = serving.build_adapter_pool(model, k_pool, args.adapters)

    max_len = args.max_len or (args.prompt_len + args.gen)
    cfg = serving.ServeConfig(num_slots=args.num_slots, max_len=max_len,
                              page_size=args.page_size)
    engine = serving.ServingEngine(model, params, pool, cfg)

    rng = np.random.default_rng(
        int(jax.random.randint(k_prompts, (), 0, 2**31 - 1)))
    v = arch.model.vocab_size
    arrivals = (np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                          args.requests))
                if args.arrival_rate > 0 else np.zeros(args.requests))
    reqs = [serving.Request(
        rid=i, adapter=i % args.adapters,
        tokens=rng.integers(3, v, size=args.prompt_len),
        max_new=args.gen, arrival=float(arrivals[i]))
        for i in range(args.requests)]
    return engine, reqs


def host_phases(records) -> str:
    """One line: calls and total host ms of each `serve.*` span."""
    calls, total = {}, {}
    for name, start, end, _ in records:
        if name.startswith("serve."):
            calls[name] = calls.get(name, 0) + 1
            total[name] = total.get(name, 0.0) + (end - start)
    return "host time: " + ", ".join(
        f"{n} {total[n] * 1e3:.1f} ms over {calls[n]}"
        for n in sorted(total))


def main(argv=None):
    args = build_parser().parse_args(argv)

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from repro.runtime import spans
    engine, reqs = build(args)
    spans.reset()
    t0 = time.time()
    results = engine.run(reqs)
    wall = time.time() - t0

    lat = np.array([r["t_done"] - r["t_submit"] for r in results])
    ttft = np.array([r["t_first"] - r["t_submit"] for r in results])
    toks = sum(len(r["tokens"]) for r in results)
    print(f"served {len(results)} requests x {args.gen} tokens over "
          f"{args.adapters} adapters in {wall:.3f}s "
          f"({toks / wall:.1f} tok/s, decode traces="
          f"{engine.decode_traces['n']})")
    print(f"latency p50 {np.percentile(lat, 50) * 1e3:.1f} ms   "
          f"p99 {np.percentile(lat, 99) * 1e3:.1f} ms   "
          f"ttft p50 {np.percentile(ttft, 50) * 1e3:.1f} ms")
    print(host_phases(spans.records()))
    print(f"generated ids (rid 0): {results[0]['tokens'][:16]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
