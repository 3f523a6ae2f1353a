#!/usr/bin/env python3
"""Bring-up smoke run: both engines on one TPU chip, at gpt2-small's
published widths, through their normal entry points.

  python chip_smoke.py              # one chip: kernel parity, 3 training
                                    # rounds, a serving run
  python chip_smoke.py --chips 4    # four chips: one sharded round and
                                    # its unsharded twin, nothing else

Everything runs in this one process (a chip belongs to one process).
Any failed phase makes the script exit non-zero; only when every phase
passed is the last line of stdout the JSON object

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

Times and memory printed here come from one smoke run, not a benchmark.
Outputs (training checkpoints, history, a summary JSON) go under --out.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# flash forward/backward and the smashed int8 round trip must be Pallas
# custom calls inside the compiled training round
ROUND_KERNELS = ("flash_attention_pallas", "flash_attention_bwd_pallas",
                 "roundtrip_pallas")
# and the indexed LoRA and paged decode attention inside the decode tick
DECODE_KERNELS = ("lora_matmul_indexed_pallas",
                  "decode_attention_paged_pallas")


def _parser():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="output directory (emptied first)")
    return ap


def _grad_tol(dtype):
    """tests/test_grads.py's per-dtype tolerances."""
    import jax.numpy as jnp
    return (dict(rtol=3e-2, atol=1e-1) if dtype == jnp.bfloat16
            else dict(rtol=2e-4, atol=2e-4))


def _close(name, got, want, tol):
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    err = float(np.max(np.abs(g - w))) if g.size else 0.0
    print(f"  {name}: max|err| {err:.3e}", flush=True)
    np.testing.assert_allclose(g, w, err_msg=name, **tol)


def _require_kernels(what: str, hlo_text: str, names):
    """Every name must label a tpu_custom_call in compiled HLO text (its
    op_name metadata carries the jitted kernel wrapper's name)."""
    lines = [line for line in hlo_text.splitlines()
             if "tpu_custom_call" in line]
    missing = [n for n in names if not any(n in line for line in lines)]
    print(f"  {what}: {len(lines)} tpu_custom_call line(s); "
          f"kernels {list(names)} "
          f"{'all present' if not missing else f'MISSING {missing}'}",
          flush=True)
    if missing:
        raise AssertionError(f"{what}: no tpu_custom_call for {missing}")


# ---------------------------------------------------------------------------
# phase 1: kernel parity at gpt2-small widths


def phase_kernels(summary):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.decode_attention import ref as da_ref
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.lora_matmul import ops as lora_ops
    from repro.kernels.lora_matmul import ref as lora_ref
    from repro.kernels.smashed_quant import ops as sq_ops
    from repro.kernels.smashed_quant import ref as sq_ref

    d, h, hd, s, r = 768, 12, 64, 512, 16        # gpt2-small widths
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def rand(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def oracle(fn, *args):
        """ref.py in float32 at full matmul precision, on the same
        (possibly bf16) inputs: a bf16 oracle's own accumulation error
        grows with the reduction length, the kernels accumulate in f32."""
        up = [x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating)
              else x for x in args]
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*up)

    def kernel(names, fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        _require_kernels(names[0], compiled.as_text(), names)
        return compiled(*args)

    for dtype in (jnp.float32, jnp.bfloat16):
        tol = _grad_tol(dtype)
        tag = jnp.dtype(dtype).name
        print(f"-- {tag}", flush=True)

        # flash attention, causal, forward + backward
        q, k, v, g = (rand((2, s, h, hd), dtype) for _ in range(4))

        def fa_vjp(attn):
            def f(q_, k_, v_, g_):
                out, vjp = jax.vjp(lambda *t: attn(*t, causal=True),
                                   q_, k_, v_)
                return (out,) + vjp(g_)
            return f

        got = kernel(("flash_attention_pallas", "flash_attention_bwd_pallas"),
                     fa_vjp(fa_ops.flash_attention), q, k, v, g)
        want = oracle(fa_vjp(fa_ref.attention), q, k, v, g)
        for nm, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            _close(f"flash {nm}", a, b, tol)

        # fused LoRA projection, forward + backward (frozen base)
        x, gy = rand((4 * s, d), dtype), rand((4 * s, d), dtype)
        w = rand((d, d), dtype, 0.05)
        a, b = rand((d, r), dtype, 0.05), rand((r, d), dtype, 0.05)
        sc = jnp.float32(0.7)

        def lora_vjp(fn):
            def f(x_, w_, a_, b_, s_, g_):
                y, vjp = jax.vjp(fn, x_, w_, a_, b_, s_)
                dx, _, da, db, ds = vjp(g_)
                return y, dx, da, db, ds
            return f

        got = kernel(("lora_matmul_pallas", "lora_matmul_bwd_pallas"),
                     lora_vjp(lambda *t: lora_ops.lora_matmul(
                         *t, lora_only=True)), x, w, a, b, sc, gy)
        want = oracle(lora_vjp(lora_ref.lora_matmul), x, w, a, b, sc, gy)
        for nm, ga, wa in zip(("y", "dx", "da", "db", "dscale"), got, want):
            t = (dict(rtol=1.5e-1, atol=5e-1)
                 if nm == "dscale" and dtype == jnp.bfloat16 else tol)
            _close(f"lora {nm}", ga, wa, t)

        # indexed multi-adapter LoRA (one decode token per slot)
        n_slots, pool = 8, 4
        xs = rand((n_slots, 1, d), dtype)
        ap, bp = rand((pool, d, r), dtype, 0.05), rand((pool, r, d), dtype,
                                                        0.05)
        scs = jnp.linspace(0.5, 1.0, pool, dtype=jnp.float32)
        ids = jnp.asarray([3, 0, 1, 2, 2, 1, 0, 3], jnp.int32)
        got = kernel(("lora_matmul_indexed_pallas",),
                     lora_ops.lora_matmul_indexed, xs, w, ap, bp, scs, ids)
        want = oracle(lora_ref.lora_matmul_indexed, xs, w, ap, bp, scs, ids)
        _close("indexed lora y", got, want, tol)

        # decode attention over a dense cache and over a paged one
        bsz, ps = 4, 16
        qd = rand((bsz, h, hd), dtype)
        kc, vc = rand((bsz, s, h, hd), dtype), rand((bsz, s, h, hd), dtype)
        lens = jnp.asarray([1, 100, 511, 512], jnp.int32)
        got = kernel(("decode_attention_pallas",), da_ops.decode_attention,
                     qd, kc, vc, lens)
        want = oracle(da_ref.decode_attention, qd, kc, vc, lens)
        _close("decode dense", got, want, tol)

        p_max = s // ps
        n_pages = bsz * p_max + 1                 # page 0 is the trash page
        kp = rand((n_pages, ps, h, hd), dtype)
        vp = rand((n_pages, ps, h, hd), dtype)
        table = jnp.asarray(1 + np.random.default_rng(0).permutation(
            bsz * p_max).reshape(bsz, p_max), jnp.int32)
        got = kernel(("decode_attention_paged_pallas",),
                     da_ops.decode_attention_paged, qd, kp, vp, table, lens)
        want = oracle(da_ref.decode_attention_paged, qd, kp, vp, table, lens)
        _close("decode paged", got, want, tol)

    # smashed int8 round trip and the quantize/dequantize pair, G = 5
    # messages (clients) x batch 4 x seq 512 x d 768 activations.  A value
    # whose x/scale lands on a rounding tie may round either way on two
    # different division units: there the two may differ by one step.
    xa = rand((5, 4, s, d), jnp.float32) * jnp.exp(
        rand((d,), jnp.float32))
    x3 = np.asarray(xa, np.float64).reshape(5, -1, d)
    _, scale_ref = sq_ref.quantize(jnp.asarray(x3, jnp.float32))
    t = x3 / np.asarray(scale_ref, np.float64)[:, None, :]
    tie = np.abs(np.abs(t - np.floor(t)) - 0.5) < 1e-3
    step = np.broadcast_to(np.asarray(scale_ref)[:, None, :], t.shape)

    def close_but_ties(name, got, want, tol):
        g = np.asarray(got, np.float64).reshape(t.shape)
        w = np.asarray(want, np.float64).reshape(t.shape)
        off = ~np.isclose(g, w, **tol)
        print(f"  {name}: {int(off.sum())} of {off.size} off "
              f"({int(tie.sum())} ties)", flush=True)
        if np.any(off & ~tie) or np.any(np.abs(g - w)[off]
                                        > step[off] * (1 + 1e-5)):
            raise AssertionError(f"{name}: differs away from rounding ties")

    stol = dict(rtol=2e-5, atol=2e-5)
    got = kernel(("roundtrip_pallas",), sq_ops.int8_roundtrip_smashed, xa)
    want = oracle(lambda x: sq_ref.roundtrip(x.reshape(5, -1, d)), xa)
    close_but_ties("smashed roundtrip", got, want, stol)
    q, scale = kernel(("quantize_pallas",), sq_ops.int8_quantize_smashed, xa)
    q_ref, _ = oracle(lambda x: sq_ref.quantize(x.reshape(5, -1, d)), xa)
    _close("smashed scale", scale, scale_ref, dict(rtol=1e-6, atol=0))
    close_but_ties("smashed q", np.asarray(q, np.float64) * step.reshape(
        q.shape), np.asarray(q_ref, np.float64) * step, dict(rtol=0,
                                                            atol=0))
    deq = kernel(("dequantize_pallas",), sq_ops.int8_dequantize_smashed,
                 q, scale)
    _close("smashed dequantize", deq,
           oracle(lambda q_, s_: sq_ref.dequantize(q_.reshape(5, -1, d), s_)
                  .reshape(q_.shape), q, scale), dict(rtol=1e-6, atol=0))
    summary["kernels"] = "pass"


# ---------------------------------------------------------------------------
# phase 2: three gpt2-small training rounds through repro.launch.train


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _round_shapes(system):
    """Argument shapes of one round step besides params and state."""
    import jax.numpy as jnp
    n = system.arch.data.num_clients
    b, s = system.arch.train.batch_size, system.arch.train.seq_len
    batch = {"tokens": _sds((n, b, s), jnp.int32),
             "labels": _sds((n, b, s), jnp.int32),
             "loss_mask": _sds((n, b, s), jnp.float32)}
    vec, sc = _sds((n,), jnp.float32), _sds((), jnp.float32)
    return batch, vec, vec, sc, sc


def _peak_bytes():
    """peak_bytes_in_use of device 0, None where the backend has none."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _memory_line(ma):
    gib = 2.0 ** 30
    return (f"arguments {ma.argument_size_in_bytes / gib:.3f} GiB, "
            f"outputs {ma.output_size_in_bytes / gib:.3f} GiB "
            f"(aliased {ma.alias_size_in_bytes / gib:.3f}), "
            f"temporaries {ma.temp_size_in_bytes / gib:.3f} GiB, "
            f"code {ma.generated_code_size_in_bytes / gib:.3f} GiB")


def _train_system(out, extra, policy=None):
    from repro.launch import train
    shutil.rmtree(out, ignore_errors=True)     # train.py resumes from --out
    args = train.build_parser().parse_args(
        ["--arch", "gpt2-small", "--smashed-compress", "int8",
         "--out", str(out)] + extra)
    system = train.build_system(args, policy=policy)
    if system.restore():
        raise AssertionError(f"{out} was not fresh: a checkpoint resumed")
    return system


def phase_train(summary, out):
    import jax
    import numpy as np

    rounds = 3
    system = _train_system(out / "train", ["--rounds", str(rounds)])
    n = system.arch.data.num_clients
    b, s = system.arch.train.batch_size, system.arch.train.seq_len
    print(f"  config: gpt2-small d_model {system.arch.model.d_model}, "
          f"{system.arch.model.num_layers} layers, {n} clients x batch "
          f"{b} x seq {s}, smashed int8, cut {system.arch.split.cut_layer}",
          flush=True)

    t0 = time.perf_counter()
    compiled = system.train_step.lower(
        system.base_params, system.state, *_round_shapes(system)).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    print(f"  [smoke run, not a benchmark] round compile {compile_s:.3f} s; "
          f"memory_analysis: {_memory_line(ma)}", flush=True)
    _require_kernels("training round", compiled.as_text(), ROUND_KERNELS)
    del compiled

    hist_path = out / "train" / "history.jsonl"
    round_s = []
    with open(hist_path, "w") as hf:
        def record(rec):
            hf.write(json.dumps({k: (v.tolist() if isinstance(v, np.ndarray)
                                     else v) for k, v in rec.items()})
                     + "\n")

        for r in range(rounds):
            t0 = time.perf_counter()
            system.run(1, log_every=0, callback=record)
            jax.block_until_ready(system.state)
            round_s.append(time.perf_counter() - t0)
            loss = system.history[-1]["loss"]
            print(f"  [smoke run, not a benchmark] round {r + 1}: "
                  f"{round_s[-1]:.3f} s (host loop incl. C3 eval and "
                  f"checkpoint{'; first call also compiles' if r == 0 else ''}"
                  f"), loss {loss:.6f}", flush=True)
            if not math.isfinite(loss):
                raise AssertionError(f"round {r + 1} loss {loss}")
    final = system.evaluate()
    print(f"  final eval: {final}", flush=True)
    if not math.isfinite(final["ce"]):
        raise AssertionError(f"final eval CE {final['ce']}")
    peak = _peak_bytes()
    print(f"  [smoke run, not a benchmark] peak_bytes_in_use {peak}; "
          f"memory_stats {jax.devices()[0].memory_stats()}", flush=True)
    summary["train"] = dict(
        clients=n, batch=b, seq=s, compile_s=compile_s, round_s=round_s,
        losses=[h["loss"] for h in system.history], final=final,
        peak_bytes_in_use=peak, temp_bytes=ma.temp_size_in_bytes,
        argument_bytes=ma.argument_size_in_bytes)


# ---------------------------------------------------------------------------
# phase 3: serving through repro.launch.serve's engine


def phase_serve(summary):
    import jax
    import jax.numpy as jnp

    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "gpt2-small", "--adapters", "4", "--requests", "8",
         "--num-slots", "4", "--page-size", "16", "--prompt-len", "32",
         "--gen", "16"])
    engine, reqs = serve.build(args)
    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    vocab = engine.model.arch.model.vocab_size
    for res in results:
        toks = res["tokens"]
        if toks is None or len(toks) != res["max_new"]:
            raise AssertionError(f"request {res['rid']} finished with "
                                 f"{toks and len(toks)} of "
                                 f"{res['max_new']} tokens")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"request {res['rid']}: token outside "
                                 "the vocabulary")
    traces = engine.decode_traces["n"]
    print(f"  [smoke run, not a benchmark] served {len(results)} requests "
          f"x {args.gen} tokens over {args.adapters} adapters, page size "
          f"{args.page_size}, in {wall:.3f} s (includes compiles); "
          f"decode_traces {traces}", flush=True)
    if len(results) != len(reqs) or traces != 1:
        raise AssertionError(f"{len(results)} results, decode_traces "
                             f"{traces}")
    b = engine.cfg.num_slots
    tick = engine._decode.lower(
        engine.params, engine.pool, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b, 1), jnp.int32), engine.cache,
        jnp.zeros((b,), bool)).compile().as_text()
    _require_kernels("decode tick", tick, DECODE_KERNELS)
    peak = _peak_bytes()
    print(f"  [smoke run, not a benchmark] peak_bytes_in_use {peak}",
          flush=True)
    summary["serve"] = dict(requests=len(results), gen=args.gen,
                            decode_traces=traces, wall_s=wall,
                            peak_bytes_in_use=peak)


# ---------------------------------------------------------------------------
# four chips: one sharded round against its unsharded twin


def phase_sharded(summary, out):
    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.models.common import ShardingPolicy
    from repro.runtime import sharding as rules

    # 8 clients so the client axis divides over 4 chips; batch 2 (the
    # paper's is 4) so that the unsharded twin fits one chip
    extra = ["--clients", "8", "--batch-size", "2", "--no-adaptive",
             "--rounds", "1"]
    mesh = make_host_mesh(4)
    sharded = _train_system(out / "sharded", extra, policy=ShardingPolicy(
        mesh=mesh, client_mode=True))
    t0 = time.perf_counter()
    sharded.run(1, log_every=0)
    jax.block_until_ready(sharded.state)
    sharded_s = time.perf_counter() - t0

    flat, _ = jax.tree_util.tree_flatten_with_path(sharded.state)
    split = 0
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", "?")))
                     for p in path)
        ax = rules.state_client_axis(keys, leaf.ndim)
        if ax is None:
            continue
        shard = leaf.sharding.shard_shape(leaf.shape)
        devices = {sh.device for sh in leaf.addressable_shards}
        if shard[ax] * 4 != leaf.shape[ax] or len(devices) != 4:
            raise AssertionError(
                f"{'/'.join(keys)} {leaf.shape}: shard {shard} over "
                f"{len(devices)} devices, not 1/4 of the client axis")
        split += 1
    print(f"  {split} per-client state leaves split 1/4 along the client "
          f"axis over 4 devices", flush=True)

    loss_sh = sharded.history[0]["loss"]
    host = [np.asarray(x) for x in jax.tree.leaves(
        {k: sharded.state[k] for k in ("client_adapters",
                                       "server_adapters")})]
    del sharded
    gc.collect()

    twin = _train_system(out / "twin", extra)
    t0 = time.perf_counter()
    twin.run(1, log_every=0)
    jax.block_until_ready(twin.state)
    twin_s = time.perf_counter() - t0
    loss_tw = twin.history[0]["loss"]
    ref = [np.asarray(x) for x in jax.tree.leaves(
        {k: twin.state[k] for k in ("client_adapters", "server_adapters")})]
    print(f"  [smoke run, not a benchmark] round incl. compile: sharded "
          f"{sharded_s:.3f} s, unsharded {twin_s:.3f} s", flush=True)
    print(f"  loss sharded {loss_sh!r} unsharded {loss_tw!r}", flush=True)
    np.testing.assert_allclose(loss_sh, loss_tw, rtol=1e-4)
    digest = lambda ls: [float(np.linalg.norm(x.astype(np.float64)))
                         for x in ls]
    np.testing.assert_allclose(digest(host), digest(ref), rtol=1e-4)
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(host, ref))
    # Adam's first step moves an element by about lr * sign(grad): where a
    # gradient is within summation-order noise of zero the two runs may
    # step opposite ways: about 0.1% of them at gpt2-small on four v5e
    # chips.  A sharding fault moves about half the elements.
    off = sum(int(np.sum(~np.isclose(a, b, rtol=1e-4, atol=1e-5)))
              for a, b in zip(host, ref))
    total = sum(a.size for a in host)
    print(f"  adapter leaves: max|sharded - unsharded| {worst:.3e}; "
          f"{off} of {total} elements differ", flush=True)
    if off > 1e-2 * total:
        raise AssertionError(f"{off} of {total} adapter elements differ")
    summary["sharded"] = dict(loss_sharded=loss_sh, loss_unsharded=loss_tw,
                              max_abs_adapter_diff=worst,
                              adapter_elements_off=off,
                              client_leaves_split=split,
                              sharded_s=sharded_s, unsharded_s=twin_s)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        print("REPRO_PALLAS_INTERPRET is set: the kernels would run in "
              "interpret mode, not on the chip", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found: this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    out = pathlib.Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    summary = {"device": {"platform": dev.platform,
                          "kind": dev.device_kind, "count": len(devices)}}
    if args.chips == 4:
        phases = [("sharded round vs unsharded twin",
                   lambda: phase_sharded(summary, out))]
    else:
        phases = [("kernel parity", lambda: phase_kernels(summary)),
                  ("training", lambda: phase_train(summary, out)),
                  ("serving", lambda: phase_serve(summary))]
    failed = []
    for name, run in phases:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"FAIL {name}", flush=True)
        else:
            print(f"PASS {name} ({time.perf_counter() - t0:.1f} s)",
                  flush=True)
        gc.collect()
    summary["failed"] = failed
    with open(out / "summary.json", "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
