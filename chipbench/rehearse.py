#!/usr/bin/env python3
"""Compile a cell's programs for a described v5e, without the chip.

  JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell>

Builds the cell's round step and C3 evaluation step (training) or its
prefill buckets and decode tick (serving) from shapes alone, compiles
them for one chip of a described `v5e:2x2` topology (the cell's chips
as a (chips, 1) mesh for a sharded cell), and prints each program's
per-device `memory_analysis`.  Nothing runs; no time is measured.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

GIB = 2.0 ** 30


def _mem(ma):
    return (f"arguments {ma.argument_size_in_bytes / GIB:.3f} GiB, "
            f"outputs {ma.output_size_in_bytes / GIB:.3f} GiB, "
            f"temporaries {ma.temp_size_in_bytes / GIB:.3f} GiB")


def _compile(name, fn, *args):
    t0 = time.perf_counter()
    c = fn.lower(*args).compile()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
          f"{_mem(c.memory_analysis())}", flush=True)
    return c


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    jax.config.update("jax_enable_compilation_cache", False)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness
    from repro.configs import get_config
    from repro.core import rounds
    from repro.models.common import ShardingPolicy
    from repro.models.model import build_model

    # the CPU backend would pick the jnp oracles: steer every kernel
    # module's dispatch to its Pallas path, as on the chip
    import importlib
    for mod in ("flash_attention", "decode_attention", "lora_matmul",
                "smashed_quant"):
        importlib.import_module(
            f"repro.kernels.{mod}.ops")._use_pallas = lambda: True
    cell, cfg, tr, _ = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    arch = get_config(cfg["registry"])
    model = build_model(arch)
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    if tr["driver"] == "train":
        import dataclasses
        arch = arch.replace(data=dataclasses.replace(
            arch.data, num_clients=tr["clients"]),
            train=dataclasses.replace(arch.train, batch_size=tr["batch"]))
        model = build_model(arch)
        n, b, s = tr["clients"], tr["batch"], tr["seq_len"]
        k = cell["chips"]
        if k > 1:
            mesh = Mesh(__import__("numpy").asarray(
                topo.devices[:k]).reshape(k, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            policy = ShardingPolicy(mesh=mesh, client_mode=True)
            rep = NamedSharding(mesh, P())
            cli = NamedSharding(mesh, P("data"))
        else:
            policy = ShardingPolicy(mesh=None)
            rep = cli = one
        key = jax.random.PRNGKey(0)
        params = sds(jax.eval_shape(model.init_params, key), rep)
        state = jax.eval_shape(lambda kk: rounds.init_state(
            model, kk, num_clients=n), key)
        state = sds(state, rep)
        batch = {"tokens": jax.ShapeDtypeStruct((n, b, s), jnp.int32,
                                                sharding=cli),
                 "labels": jax.ShapeDtypeStruct((n, b, s), jnp.int32,
                                                sharding=cli),
                 "loss_mask": jax.ShapeDtypeStruct((n, b, s), jnp.float32,
                                                   sharding=cli)}
        vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rep)
        sc = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
        step = rounds.make_train_step(model, policy=policy,
                                      smashed_compress=tr["smashed_compress"])
        c = _compile(f"round step ({n} clients x {b} x {s}, {k} chip(s))",
                     step, params, state, batch, vec, vec, sc, sc)
        text = c.as_text()
        print("  kernels:", sorted({w for w in (
            "flash_attention_pallas", "flash_attention_bwd_pallas",
            "roundtrip_pallas") if w in text}),
              "collectives:", sorted({w for w in (
                  "all-reduce", "all-gather", "reduce-scatter")
                  if w in text}))
        ev = rounds.make_eval_step(model, policy=policy)
        _compile("C3 eval step", ev, params, state, batch, vec)
    else:
        from repro.runtime import serving
        from chipbench import arrivals
        engine_cfg = serving.ServeConfig(num_slots=tr["slots"],
                                         max_len=tr["max_len"],
                                         page_size=tr["page_size"])
        key = jax.random.PRNGKey(0)
        params = sds(jax.eval_shape(model.init_params, key), one)
        from chipbench import weights
        pool = sds(jax.eval_shape(lambda kk: weights.make_pool(
            harness.model_dims(cfg), cfg["lora"], tr["adapters"], kk), key),
            one)
        from repro.runtime import kv_cache
        cache = sds(jax.eval_shape(lambda: kv_cache.init_paged_cache(
            model, tr["slots"], tr["max_len"], tr["page_size"],
            jnp.float32, num_pages=kv_cache.default_num_pages(
                tr["slots"], tr["max_len"], tr["page_size"]))), one)
        i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32,   # noqa: E731
                                               sharding=one)
        bsz = tr["slots"]

        def decode(params_, pool_, ids, toks, cache_, active):
            adapters = serving.attach_ids(pool_, ids)
            logits, cache_ = model.decode_step(params_, adapters, toks,
                                               cache_)
            return jnp.argmax(logits[:, -1, :], -1), cache_

        _compile(f"decode tick ({bsz} slots, max_len {tr['max_len']})",
                 jax.jit(decode), params, pool, i32(bsz), i32(bsz, 1), cache,
                 jax.ShapeDtypeStruct((bsz,), bool, sharding=one))
        for bucket in arrivals.buckets_used(tr, 20, engine_cfg.buckets()):
            def prefill(params_, pool_, ids, toks, bucket=bucket):
                temp = model.init_cache((1,), bucket, jnp.float32)
                x, _, temp = model.forward(
                    params_, serving.attach_ids(pool_, ids),
                    {"tokens": toks}, cache=temp, mode="prefill")
                return model.head(params_, x[:, -1:]), temp
            _compile(f"prefill bucket {bucket}", jax.jit(prefill), params,
                     pool, i32(1), i32(1, bucket))
    return 0


if __name__ == "__main__":
    sys.exit(main())
