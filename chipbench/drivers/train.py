"""Training driver: the SplitFT round engine, round after round.

Set-up builds one `SplitFTSystem` through `repro.launch.train`, installs
the harness's weights and adapters (drawn from --seed; the job's batches
are the program's own stream at the traffic file's fixed data seed, so
every seed trains the same rows in the same order), and drives its first rounds through
the system's own `run(1)` (the first one compiles the round and the C3
evaluation step).  The window then calls `run(1)` back to back until
`--seconds` have passed.  No checkpoint is written.  Once the window has
closed the reference follows the first rounds from the same start, with
the same batches, cuts and aggregation weights.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import compare, flops, weights
from chipbench.harness import (BenchError, ROOT, TracedSegment,
                               check_program_arch, log)
from chipbench.reference import static_ranks

FAULTS = ("state_unchanged", "half_batch", "no_exchange")


def _system(ctx):
    from repro.launch import train as ltrain
    cfg, tr = ctx["cfg"], ctx["traffic"]
    out = ROOT / "chipbench_out" / ctx["cell"]["name"]
    argv = ["--arch", cfg["registry"], "--out", str(out),
            "--rounds", "1000000", "--seed", str(tr["data_seed"]),
            "--clients", str(tr["clients"]),
            "--batch-size", str(tr["batch"]),
            "--partition", tr["partition"], "--alpha", str(tr["alpha"]),
            "--samples", str(tr["corpus_samples"]),
            "--smashed-compress", tr["smashed_compress"],
            "--scheduler", tr["scheduler"], "--controller", tr["controller"],
            "--adaptive" if tr["adaptive"] else "--no-adaptive"]
    if cfg.get("program_reduced"):
        argv.append("--reduced")
    args = ltrain.build_parser().parse_args(argv)
    policy = None
    if tr["mesh_data"] > 1:
        from repro.launch.mesh import make_host_mesh
        from repro.models.common import ShardingPolicy
        policy = ShardingPolicy(mesh=make_host_mesh(tr["mesh_data"]),
                                client_mode=True)
    system = ltrain.build_system(args, policy=policy)
    system.ckpt = None                      # no checkpoint, ever
    if system.arch.train.seq_len != tr["seq_len"]:
        raise BenchError(f"traffic seq_len {tr['seq_len']} but the program "
                         f"trains at {system.arch.train.seq_len}")
    return system


def _host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)


def run(ctx):
    import jax
    import jax.numpy as jnp

    cfg, tr, dims, spans = ctx["cfg"], ctx["traffic"], ctx["dims"], \
        ctx["spans"]
    lora = cfg["lora"]
    fault = ctx.get("fault")
    n_ref = tr["reference_rounds"]

    system = _system(ctx)
    check_program_arch(dims, lora, system.arch)
    k_base, k_ad = jax.random.split(jax.random.PRNGKey(ctx["pseed"]))
    base = weights.make_base(dims, k_base)
    cad0, sad0 = weights.make_train_adapters(dims, lora, tr["clients"], k_ad)
    weights.check_layout(base, system.base_params, "base weights")
    weights.check_layout(cad0, system.state["client_adapters"],
                         "client adapters")
    weights.check_layout(sad0, system.state["server_adapters"],
                         "server adapters")
    system.base_params = base
    system.state = dict(system.state, client_adapters=cad0,
                        server_adapters=sad0)
    start = {"cad": _host(cad0), "sad": _host(sad0)}
    del cad0, sad0

    inner_train, inner_eval = system.train_step, system.eval_step
    seen = {"inputs": [], "record": True, "tokens": 0.0, "rows": []}

    def train_step(base_p, state, batch, w, active, lr_c, lr_s):
        if seen["record"]:
            seen["inputs"].append(dict(
                batch={k: np.array(v) for k, v in batch.items()},
                weights=np.asarray(w), active=np.asarray(active),
                cuts=np.asarray(state["cuts"])))
        else:
            m = batch["loss_mask"]
            seen["tokens"] += float(m.sum())
            seen["rows"].append(m.sum(-1).ravel())
        if fault == "half_batch":
            batch = dict(batch)
            m = np.array(batch["loss_mask"])
            m[:, m.shape[1] // 2:] = 0.0
            batch["loss_mask"] = m
        kept = (jax.tree.map(jnp.copy, state)
                if fault in ("state_unchanged", "no_exchange") else None)
        with spans.span("bench.train_step"):
            new_state, metrics = inner_train(base_p, state, batch, w, active,
                                             lr_c, lr_s)
        if fault == "no_exchange":
            # the clients of every chip but the first never get the
            # aggregate: their rows stay as they were
            k = tr["clients"] // ctx["chips"]
            new_state = dict(new_state, client_adapters=jax.tree.map(
                lambda a, b: a.at[:, k:].set(b[:, k:]),
                new_state["client_adapters"], kept["client_adapters"]))
            kept = None
        return (kept if kept is not None else new_state), metrics

    def eval_step(*a):
        with spans.span("bench.eval_step"):
            return inner_eval(*a)

    system.train_step, system.eval_step = train_step, eval_step

    # ---- set-up: the first rounds, which compile, feed the reference ----
    prog = {}
    for r in range(n_ref):
        system.run(1, log_every=0)
        if r == 0:
            prog["m1"] = {"cad": _host(system.state["opt_c"]["m"]),
                          "sad": _host(system.state["opt_s"]["m"])}
    jax.block_until_ready(system.state)
    prog["after"] = {"cad": _host(system.state["client_adapters"]),
                     "sad": _host(system.state["server_adapters"])}
    prog["losses"] = [h["loss"] for h in system.history[:n_ref]]
    seen["record"] = False
    caches = (inner_train._cache_size(), inner_eval._cache_size())
    ctx["setup_end"] = time.perf_counter()

    # ---- the measured window ----
    seg = TracedSegment(ctx)
    per_round = []                          # (start, end, row lengths)
    t0 = time.perf_counter()
    with spans.span("bench.window"):
        while True:
            seg.poll(time.perf_counter() - t0)
            tb = time.perf_counter()
            with spans.span("bench.round"):
                system.run(1, log_every=0)
            te = time.perf_counter()
            per_round.append((tb, te, seen["rows"][-1]))
            if te - t0 >= ctx["seconds"]:
                break
            if seg.on and te - t0 >= seg.start_s + seg.seconds:
                seg.end()
        jax.block_until_ready(system.state)
    window_s = time.perf_counter() - t0
    seg.end()
    compiles = (inner_train._cache_size() - caches[0]
                + inner_eval._cache_size() - caches[1])
    dur = np.asarray([te - tb for tb, te, _ in per_round])
    log(f"window: {len(per_round)} rounds in {window_s:.3f} s, "
        f"{seen['tokens']:.0f} real tokens, compiles in window {compiles}; "
        f"round p50 {1e3 * np.median(dur):.1f} ms, slowest "
        f"{', '.join(f'{1e3 * t:.0f}' for t in np.sort(dur)[-3:][::-1])} ms")
    mem = ctx["memory_peak"]()

    traced = [r for r in per_round if seg.covers(r[0]) and seg.covers(r[1])]
    lens = (np.concatenate([r[2] for r in traced]) if traced
            else np.zeros(0))
    out = {
        "window_s": window_s, "trace_dir": seg.dir,
        "memory_peak_bytes": mem, "compiles_in_window": compiles,
        "attempted": len(per_round), "failed": 0,
        "e2e": {"train_tokens_per_s": seen["tokens"] / window_s},
        "counters": {
            "traced_s": seg.length, "rounds": len(traced),
            "model_flops": flops.train_flops(
                dims, static_ranks(dims, lora), lens),
            "rows": tr["clients"] * tr["batch"], "seq_len": tr["seq_len"],
        },
    }
    system.train_step = system.eval_step = None
    del system, inner_train, inner_eval
    gc.collect()

    # ---- the reference follows the first rounds ----
    t_ref = time.perf_counter()
    ref = reference_rounds(base, start, seen["inputs"], dims, cfg,
                           n_dev=ctx["chips"])
    prog["start"] = start
    ctx["check_inputs"] = {"base": base, "start": start,
                           "inputs": seen["inputs"], "prog": prog,
                           "ref": ref}
    out["check"] = compare.train_numbers(prog, ref)
    log(f"reference: {n_ref} rounds in {time.perf_counter() - t_ref:.1f} s")
    return out


def reference_rounds(base, start, inputs, dims, cfg, dtype=None,
                     fault=None, chips=1, n_dev=1):
    """Run the reference over the recorded rounds.  Returns per-round
    losses, the first round's clipped gradients and the adapters after
    the last round; with dtype bfloat16 this is the control, and `fault`
    plants one of FAULTS in the reference put in the program's place."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference as R

    dtype = dtype or jnp.float32
    lora = dict(cfg["lora"])
    opt = cfg["optimizer"]
    compress = cfg["split"]["smashed_compress"]
    st = {"cad": jax_tree(start["cad"]), "sad": jax_tree(start["sad"])}
    st["opt_c"], st["opt_s"] = R.init_opt(st["cad"]), R.init_opt(st["sad"])
    losses, grads, gnorms = [], None, []
    for k, inp in enumerate(inputs):
        batch = {kk: jnp.asarray(v) for kk, v in inp["batch"].items()}
        if fault == "half_batch":
            m = np.array(inp["batch"]["loss_mask"])
            m[:, m.shape[1] // 2:] = 0.0
            batch["loss_mask"] = jnp.asarray(m)
        new, total, g_c, g_s = R.train_round(
            base, st, batch, inp["cuts"], inp["weights"], inp["active"],
            dims=dims, lora=lora, opt=opt, compress=compress, dtype=dtype,
            groups=chips if fault == "no_exchange" else 1,
            devices=jax.devices()[:n_dev])
        if fault != "state_unchanged":
            st = new
        losses.append(float(total))
        g = {"cad": _host(g_c), "sad": _host(g_s)}
        gnorms.append(compare.leaf_norms(g))
        if k == 0:
            grads = g
    return {"losses": losses, "g1": grads, "grad_norms": gnorms,
            "after": {"cad": _host(st["cad"]), "sad": _host(st["sad"])}}


def jax_tree(t):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.asarray, t)
