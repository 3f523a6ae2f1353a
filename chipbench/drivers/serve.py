"""Serving driver: open-loop traffic into `ServingEngine.submit` / `step`.

Set-up builds the engine through `repro.launch.serve.build`, installs the
harness's weights and adapter pool, and warms exactly the shapes the
traffic uses: one request per prefill bucket its prompts fall into, each
followed by decode ticks.  In the window the generator submits each
request when it is due, and the engine steps while it has work.  Times
are the harness's own: a token's time is the host clock just after the
`step()` that emitted it; a request's latencies run from its scheduled
arrival.  Requests due in the window are followed to completion after
it.  Once they are done the reference checks a seeded sample of them.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import arrivals, compare, flops, weights
from chipbench.harness import TracedSegment, check_program_arch, log
from chipbench.reference import static_ranks

FAULTS = ("token_altered",)
DRAIN_S = 60.0


def _engine(ctx):
    from repro.launch import serve as lserve
    cfg, tr = ctx["cfg"], ctx["traffic"]
    argv = ["--arch", cfg["registry"], "--adapters", str(tr["adapters"]),
            "--requests", "1", "--num-slots", str(tr["slots"]),
            "--page-size", str(tr["page_size"]),
            "--max-len", str(tr["max_len"]), "--prompt-len", "1",
            "--gen", "1", "--seed", str(ctx["pseed"])]
    if cfg.get("program_reduced"):
        argv.append("--reduced")
    engine, _ = lserve.build(lserve.build_parser().parse_args(argv))
    return engine


def build(ctx):
    """The engine, with the harness's weights and pool installed."""
    import jax
    cfg, tr, dims = ctx["cfg"], ctx["traffic"], ctx["dims"]
    engine = _engine(ctx)
    check_program_arch(dims, cfg["lora"], engine.model.arch)
    k_base, k_pool = jax.random.split(jax.random.PRNGKey(ctx["pseed"]))
    base = weights.make_base(dims, k_base)
    pool = weights.make_pool(dims, cfg["lora"], tr["adapters"], k_pool)
    weights.check_layout(base, engine.params, "base weights")
    weights.check_layout(pool, engine.pool, "adapter pool")
    engine.params, engine.pool = base, pool
    return engine, base, pool


def warm(engine, tr, seconds):
    """Every prefill bucket the traffic uses, each followed by ticks."""
    import jax
    from repro.runtime.serving import Request
    for i, b in enumerate(arrivals.buckets_used(tr, seconds,
                                                engine.cfg.buckets())):
        plen = min(b, tr["max_len"] - 2)        # still bucket b
        engine.submit(Request(rid=-1 - i, adapter=0,
                              tokens=np.full((plen,), 3, np.int32),
                              max_new=2))
    while engine.has_work():
        engine.step()
    jax.block_until_ready(engine.cache)


def open_loop(ctx, engine, due):
    """Submit each request when due, step while there is work, follow
    the due requests to their end.  Returns what the window saw."""
    import jax
    from repro.runtime.serving import Request
    dims, spans, seconds = ctx["dims"], ctx["spans"], ctx["seconds"]
    fault = ctx.get("fault")
    traces0 = (engine.decode_traces["n"], engine.prefill_traces["n"])
    emitted = {d.rid: [] for d in due}           # token times
    counts = {}                                  # rid -> tokens so far
    late, steps, tokens = [], [], []
    i, n = 0, len(due)
    seg = TracedSegment(ctx)
    t0 = time.perf_counter()
    with spans.span("bench.window"):
        while True:
            now = time.perf_counter() - t0
            seg.poll(now)
            if now >= seconds:
                seg.end()
            while i < n and due[i].arrival <= now:
                d = due[i]
                with spans.span("bench.submit"):
                    engine.submit(Request(rid=d.rid, adapter=d.adapter,
                                          tokens=d.tokens,
                                          max_new=d.max_new))
                late.append(now - d.arrival)
                counts[d.rid] = 0
                i += 1
            if engine.has_work():
                queued = len(engine.queue)
                ts = time.perf_counter()
                with spans.span("bench.step"):
                    engine.step()
                te = time.perf_counter()
                steps.append((ts, te, queued != len(engine.queue)))
                if fault == "token_altered":
                    _alter_one(engine, dims["vocab"])
                for rid, js in _new_tokens(engine, counts).items():
                    emitted[rid].extend([te - t0] * len(js))
                    tokens.extend((te, rid, j) for j in js)
            elif i < n:
                wait = due[i].arrival - (time.perf_counter() - t0)
                if wait > 0:
                    with spans.span("bench.wait"):
                        time.sleep(wait)
            else:
                break
            if time.perf_counter() - t0 > seconds + DRAIN_S:
                break
    seg.end()
    jax.block_until_ready(engine.cache)

    # what the traced segment saw, for the per-layer readers
    plens = {d.rid: len(d.tokens) for d in due}
    ranks = static_ranks(dims, ctx["cfg"]["lora"])
    attended, fl = [], 0.0
    for t, rid, j in tokens:
        if not seg.covers(t):
            continue
        if j == 0:
            fl += flops.prefill_flops(dims, plens[rid], ranks)
        else:
            attended.append(plens[rid] + j)
            fl += flops.decode_flops(dims, plens[rid] + j, ranks)
    return {"emitted": emitted, "late": np.asarray(late),
            "in_window": sum(1 for t, _, _ in tokens if t - t0 <= seconds),
            "end_s": time.perf_counter() - t0, "trace_dir": seg.dir,
            "compiles": (engine.decode_traces["n"] - traces0[0]
                         + engine.prefill_traces["n"] - traces0[1]),
            "counters": {"traced_s": seg.length, "attended": attended,
                         "model_flops": fl,
                         "idle_steps_s": [te - ts for ts, te, adm in steps
                                          if not adm and seg.covers(ts)
                                          and seg.covers(te)]}}


def summarize(due, w, seconds):
    """End-to-end numbers of one window."""
    ttft, tbt, done = arrivals.latencies(due, w["emitted"])
    return {"ttft_p95_ms": 1e3 * _p95(ttft), "tbt_p95_ms": 1e3 * _p95(tbt),
            "serve_tokens_per_s": w["in_window"] / seconds}, done


def run(ctx):
    tr, dims, seconds = ctx["traffic"], ctx["dims"], ctx["seconds"]
    engine, base, pool = build(ctx)
    due = arrivals.schedule(tr, ctx["seed"], seconds, dims["vocab"])
    warm(engine, tr, seconds)
    ctx["setup_end"] = time.perf_counter()

    w = open_loop(ctx, engine, due)
    mem = ctx["memory_peak"]()
    e2e, done = summarize(due, w, seconds)
    late = w["late"]
    log(f"window: {len(due)} requests due, {len(done)} done "
        f"{w['end_s']:.2f} s after the window opened, {w['in_window']} "
        f"tokens in {seconds:.1f} s; generator late p50 "
        f"{1e3 * np.median(late):.3f} ms p99 "
        f"{1e3 * np.percentile(late, 99):.3f} ms max "
        f"{1e3 * late.max():.3f} ms; compiles in window {w['compiles']}")
    out = {
        "window_s": float(seconds), "trace_dir": w["trace_dir"],
        "memory_peak_bytes": mem, "compiles_in_window": w["compiles"],
        "attempted": len(due), "failed": len(due) - len(done), "e2e": e2e,
        "counters": w["counters"],
    }

    # ---- the reference checks a seeded sample of the finished ----
    pick = _sample(due, done, tr["check_requests"], ctx["seed"])
    served = {d.rid: engine.results[d.rid]["tokens"] for d in pick}
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readout(base, pool, pick, served, dims, tr["max_len"])
    out["check"] = compare.serve_numbers(ref)
    ctx["check_inputs"] = {"base": base, "pool": pool, "pick": pick,
                           "served": served, "ref": ref}
    log(f"reference: {len(pick)} requests, "
        f"{sum(len(v) for v in served.values())} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s")
    return out


def _p95(values):
    if not values:
        return math.nan
    v = float(np.percentile(np.asarray(values, np.float64), 95))
    return math.inf if math.isnan(v) else v


def _new_tokens(engine, counts):
    """{rid: [indices of tokens emitted since the last look]}."""
    now = {}
    for s in engine.slots:
        if s is not None and s["rid"] >= 0:
            now[s["rid"]] = len(s["gen"])
    out = {}
    for rid, c in list(counts.items()):
        k = now.get(rid)
        if k is None:
            res = engine.results[rid]["tokens"]
            if res is None:
                continue                         # still queued
            k = len(res)
            del counts[rid]
        else:
            counts[rid] = k
        if k > c:
            out[rid] = list(range(c, k))
    return out


def _alter_one(engine, vocab):
    """The planted fault: one in-flight token changed where it is made."""
    for s in engine.slots:
        if s is not None and s["rid"] >= 0 and len(s["gen"]) > 1:
            s["gen"][-1] = (s["gen"][-1] + 1) % vocab
            s["last"] = s["gen"][-1]
            return


def _sample(due, done, k, seed):
    """The longest finished request and k - 1 more drawn from the seed."""
    done = set(done)
    fin = [d for d in due if d.rid in done]
    if not fin:
        return []
    longest = max(fin, key=lambda d: (d.max_new, len(d.tokens)))
    rest = [d for d in fin if d.rid != longest.rid]
    rng = np.random.default_rng(seed + 1)
    idx = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(idx)]


def reference_readout(base, pool, pick, served, dims, max_len, dtype=None,
                      at=None, block=4):
    """The reference at each position of each picked request that
    produced a served token, fed the request's prompt and its served
    tokens {rid: [token, ...]}: {rid: {"gap": how far the reference's
    logit of the token at that position (`at[rid]`, by default the served
    one) lies below its best, "top": the token the reference puts first,
    "margin": its best minus its second best}}, arrays of length
    len(served[rid]).  Rows run `block` at a time at length max_len (one
    compiled shape) and are reduced on the device."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference as R

    def readout(params, pool, ids, toks, want):
        logits = R.serve_logits(params, pool, ids, toks, dims=dims,
                                dtype=dtype or jnp.float32)
        top2 = jax.lax.top_k(logits, 2)[0]
        got = jnp.take_along_axis(logits, want[..., None], -1)[..., 0]
        return (top2[..., 0] - got, jnp.argmax(logits, -1),
                top2[..., 0] - top2[..., 1])

    f = jax.jit(readout)
    out = {}
    for b0 in range(0, len(pick), block):
        part = pick[b0:b0 + block]
        toks = np.zeros((block, max_len), np.int32)
        want = np.zeros((block, max_len), np.int32)
        ids = np.zeros((block,), np.int32)
        for r, d in enumerate(part):
            gen = np.asarray(served[d.rid], np.int32)
            seq = np.concatenate([d.tokens, gen[:-1]])
            toks[r, :len(seq)] = seq
            p0 = len(d.tokens) - 1
            want[r, p0:p0 + len(gen)] = (gen if at is None
                                         else np.asarray(at[d.rid], np.int32))
            ids[r] = d.adapter
        gap, top, margin = (np.asarray(a) for a in f(
            base, pool, jnp.asarray(ids), jnp.asarray(toks),
            jnp.asarray(want)))
        for r, d in enumerate(part):
            sl = slice(len(d.tokens) - 1, len(d.tokens) - 1
                       + len(served[d.rid]))
            out[d.rid] = {"gap": gap[r, sl].astype(np.float64),
                          "top": top[r, sl], "margin": margin[r, sl]}
    return out
