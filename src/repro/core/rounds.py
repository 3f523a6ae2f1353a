"""The SplitFT round engine — Algorithm 1 as one jitted SPMD step.

One `train_step` call = one global round (f1-f5 + b1-b4):

  f1/f2  client-side forward to the cut      } a single end-to-end
  f3     server fwd/bwd on smashed data      } jax.value_and_grad over
  f4/f5  gradient return + client backward   } (client_adps, server_adps):
                                               the cut boundary is the
                                               mask switch in the merged
                                               adapter tree, so AD routes
                                               exactly the paper's
                                               gradients to each side
  b1-b3  FedAvg of client adapters (weighted, masked, survivor-aware,
         step-normalized, optionally top-k+EF or int8 compressed)
  b4     dormant rows re-synced to the server adapters

The engine is *policy-free*: which clients participate and how many local
steps each runs per round comes from a RoundScheduler
(repro.core.scheduler) as data — the `active` mask and the
state["step_budgets"] array.  With `max_local_steps > 1` the f/b phases
become a lax.scan over the inner steps with per-client active masks
(client i runs budgets[i] steps; its adapter rows, optimizer slots and EF
residuals freeze for k >= budgets[i]), while FedAvg stays at the round
boundary.  max_local_steps == 1 is exactly the pre-scheduler lockstep
step, bit-for-bit.

`async_buffer=True` selects the FedBuff-style buffered engine: one call =
one *event tick* (the clients finishing a local step at the same
simulated instant, chosen by the host's event queue), not one barrier
round.  Completed updates accumulate in a server-side buffer
(state["buffer_mask"]); when the buffer reaches `buffer_size` the engine
aggregates with staleness-discounted, step-normalized weights and
re-broadcasts to the *buffered* clients only — in-flight clients keep
training on stale adapters (state["adapter_version"] tracks which global
version each row descends from).  Buffer fill, staleness and versions are
all arrays in state, so the tick executable never recompiles as events
fire.

Heterogeneous per-client cuts, rank policy, adaptive movement, elastic
membership and step budgets are all *data* (mask arrays) — one executable
covers every configuration (DESIGN.md §3).

Base parameters stay frozen (LoRA fine-tuning): they are an input, never
an output, so the optimizer holds state only for adapters.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.core import aggregation, lora as lora_lib, smashed as smashed_lib, \
    split
from repro.models.common import NO_SHARDING, ShardingPolicy
from repro.models.model import Model
from repro.optim import ErrorFeedback, int8_dequantize, int8_quantize, \
    make_optimizer
from repro.runtime.sharding import (constrain_client_batch, constrain_state,
                                    under_mesh)

Params = Dict[str, Any]


def init_state(model: Model, key, *, num_clients: int,
               dtype=jnp.float32) -> Params:
    """Round-engine state (everything that changes across rounds)."""
    arch = model.arch
    kc, ks = jax.random.split(key)
    cad = lora_lib.init_adapters(model, kc, num_clients=num_clients,
                                 dtype=dtype)
    sad = lora_lib.init_adapters(model, ks, num_clients=0, dtype=dtype)
    opt = _optimizer_of(arch)
    state: Params = {
        "client_adapters": cad,
        "server_adapters": sad,
        "opt_c": opt.init(cad),
        "opt_s": opt.init(sad),
        "cuts": jnp.full((num_clients,), arch.split.cut_layer, jnp.int32),
        "round": jnp.zeros((), jnp.int32),
    }
    return state


def _optimizer_of(arch: ArchConfig):
    t = arch.train
    return make_optimizer(t.optimizer, weight_decay=t.weight_decay,
                          beta1=t.beta1, beta2=t.beta2, eps=t.eps,
                          grad_clip=t.grad_clip)


def _cut_boundary(smasher, buckets, choice, cuts, residual=None,
                  topk_frac=None):
    """Pick the cut-boundary hook: the per-client bucket selector when the
    co-controller is on (buckets + state["smashed_choice"]), else the
    single configured compressor (optionally with EF residual).
    topk_frac ((N,) float32 from state["topk_frac"], bucket path only)
    makes the topk bucket's keep fraction per-client data."""
    if buckets is not None:
        if choice is None:
            raise ValueError(
                "compressor_buckets needs state['smashed_choice'] "
                "((N,) int32 bucket indices; see prepare_state)")
        if residual is not None:
            raise ValueError("smashed error feedback does not compose "
                             "with per-client compressor buckets")
        return smashed_lib.make_multi_boundary(buckets, cuts, choice,
                                               topk_frac=topk_frac)
    if topk_frac is not None:
        raise ValueError(
            "state['topk_frac'] (the continuous topk knob) needs the "
            "co-controller's compressor buckets; the single-compressor "
            "path keeps its static topk_frac")
    return smashed_lib.make_boundary(smasher, cuts, residual=residual)


def _state_ranks(model: Model, state: Params, cuts):
    """(N, M) effective-rank array when state carries the co-controller's
    per-client "rank_cut"; None otherwise (static LoRAConfig policy)."""
    rank_cut = state.get("rank_cut")
    if rank_cut is None:
        return None
    return lora_lib.effective_ranks(model.num_flat_layers, cuts,
                                    model.arch.lora, r_cut=rank_cut)


def make_train_step(model: Model, *, policy: ShardingPolicy = NO_SHARDING,
                    remat: str = "none", ce_chunk: int = 0,
                    agg_every: int = 1, compress: str = "none",
                    topk_frac: float = 0.05, microbatch: int = 1,
                    smashed_compress: str = "none",
                    smashed_topk_frac: float = 0.1,
                    compressor_buckets=None,
                    max_local_steps: int = 1,
                    async_buffer: bool = False, buffer_size: int = 2,
                    staleness_power: float = 0.5,
                    num_edges: int = 1,
                    server_step_norm: bool = True,
                    jit: bool = True):
    """Build the jitted round step (`round_step`, so its compiled module
    is `jit_round_step`).

    round_step(base_params, state, batch, weights, active, lr_c, lr_s)
      -> (state', metrics)

    weights: (N,) combined FedAvg x C3 weights (w_i * |D_i|/|D|);
    active:  (N,) {0,1} survivor mask (straggler deadline / elastic).

    microbatch=A > 1 accumulates gradients over A slices of the per-client
    batch before the optimizer step — activation memory scales 1/A while
    the gradient buffer stays adapter-sized (LoRA's key memory property).

    smashed_compress selects the cut-boundary activation compressor
    (none | int8 | fp8 | topk, see repro.core.smashed): the f2 uplink is
    compressed in-forward at each client's cut layer and the f4 gradient
    return symmetrically in-backward via the straight-through VJP.  If the
    state carries a "smashed_ef" residual (with_smashed_ef), the topk
    compressor runs with error feedback.

    compressor_buckets (optional, static tuple of compressor names) is
    the co-controller's search space: state must then carry
    "smashed_choice" — (N,) int32 indices into the tuple (see
    prepare_state) — and each client's cut boundary runs its chosen
    bucket.  Per-client compression becomes data (overrides
    smashed_compress); incompatible with smashed error feedback.  If
    state also carries "rank_cut" ((N,) int32), each client's
    rank-at-cut is likewise read from state: merge/serve/aggregate all
    use effective_ranks(..., r_cut=state["rank_cut"]), so the
    co-controller moves cut, rank and compressor without a recompile.

    max_local_steps=K > 1 selects the local-steps engine: batch gains a
    leading (K,) step axis, state must carry "step_budgets" (N,) int32
    (with_step_budgets; written by the local_steps scheduler each round),
    and the step runs a lax.scan over K inner steps.  Client i's adapters,
    optimizer slots and EF residual advance only for inner steps
    k < budgets[i]; the server side advances while any client is active.
    FedAvg happens once, at the round boundary, with weights divided by
    each client's effective step count (aggregation.fedavg `steps`) so
    extra local steps do not bias the global adapter.  K == 1 is exactly
    the pre-scheduler lockstep path.

    async_buffer=True selects the FedBuff event-tick engine (see module
    docstring): `active` becomes the set of clients *finishing* at this
    simulated instant, state must carry the buffer/version arrays
    (with_async_buffer) and per-client optimizer step counts
    (with_per_client_opt_steps), and aggregation fires inside the tick
    only when the buffer reaches `buffer_size`, discounting each buffered
    update by staleness_discount(staleness, power=staleness_power).

    num_edges > 1 selects two-tier (hierarchical) aggregation: state must
    carry "edge_assign" ((N,) int32, see with_edge_assign/prepare_state);
    FedAvg runs clients -> edge groups -> server (aggregation.fedavg
    edge mode).  num_edges == 1 is the flat path verbatim (bitwise pin).

    server_step_norm (default True) down-weights each client's per-inner-
    step gradient into the SHARED server adapters by 1/K_i under the
    local-steps engine (and 1/(steps-in-buffer) under async) so a client
    running K local steps pushes the same total server-side gradient mass
    as a one-step client.  Forward values are unchanged; with K == 1 (or
    an always-flushing buffer) the scale is exactly 1.0 and the step is
    bit-identical to server_step_norm=False — the regression pin in
    tests/test_population.py.

    When policy.mesh is set, the engines also pin the client axis of the
    state and batch to the mesh's data axis (runtime.sharding
    constrain_state / constrain_client_batch): cohort-parallel FSDP where
    each data-axis shard holds a slice of the cohort's adapter rows."""
    arch = model.arch
    opt = _optimizer_of(arch)
    smasher = smashed_lib.make_compressor(smashed_compress,
                                          topk_frac=smashed_topk_frac)
    buckets = None
    if compressor_buckets is not None:
        buckets = tuple(
            smashed_lib.make_compressor(nm, topk_frac=smashed_topk_frac)
            for nm in compressor_buckets)
    if max_local_steps < 1:
        raise ValueError(f"max_local_steps must be >= 1, got "
                         f"{max_local_steps}")
    if max_local_steps > 1 and microbatch > 1:
        raise ValueError("the local-steps engine does not compose with "
                         "microbatch accumulation yet")
    if async_buffer:
        if max_local_steps > 1 or microbatch > 1:
            raise ValueError("the async engine runs one local step per "
                             "event tick; it does not compose with "
                             "max_local_steps or microbatch")
        if compress != "none":
            raise ValueError("adapter-delta compression (topk/int8) is "
                             "not yet composed with async buffering; use "
                             "compress='none'")
        if agg_every != 1:
            raise ValueError("async buffering replaces agg_every: the "
                             "buffer fill decides when to aggregate")
        if buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got "
                             f"{buffer_size}")
        return _make_async_step(
            model, opt, smasher, policy=policy, remat=remat,
            ce_chunk=ce_chunk, buffer_size=buffer_size,
            staleness_power=staleness_power, buckets=buckets,
            num_edges=num_edges, server_step_norm=server_step_norm,
            jit=jit)

    if max_local_steps > 1:
        return _make_local_steps_step(
            model, opt, smasher, policy=policy, remat=remat,
            ce_chunk=ce_chunk, agg_every=agg_every, compress=compress,
            topk_frac=topk_frac, max_local_steps=max_local_steps,
            buckets=buckets, num_edges=num_edges,
            server_step_norm=server_step_norm, jit=jit)

    mesh = policy.mesh

    def round_step(base_params, state, batch, weights, active, lr_c, lr_s):
        state = constrain_state(state, mesh)
        batch = constrain_client_batch(batch, mesh)
        cad, sad = state["client_adapters"], state["server_adapters"]
        cuts = state["cuts"]
        rank_cut = state.get("rank_cut")
        sm_ef = state.get("smashed_ef")
        if sm_ef is not None and microbatch > 1:
            raise ValueError("smashed error feedback does not compose "
                             "with microbatch accumulation")
        wl = weights * active
        wl = wl / jnp.maximum(jnp.sum(wl), 1e-9)
        boundary = _cut_boundary(smasher, buckets,
                                 state.get("smashed_choice"), cuts,
                                 residual=sm_ef,
                                 topk_frac=state.get("topk_frac"))

        def loss_fn(cad_, sad_, mb):
            eff = split.merge_adapters(model, cad_, sad_, cuts,
                                       rank_cut=rank_cut)
            per_loss, metrics = model.loss(
                base_params, eff, mb, policy=policy, remat=remat,
                ce_chunk=ce_chunk, per_client=True, boundary=boundary)
            total = jnp.sum(wl * per_loss)
            return total, metrics

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)

        if microbatch > 1:
            def split_mb(t):
                n, b = t.shape[0], t.shape[1]
                t = t.reshape((n, microbatch, b // microbatch)
                              + t.shape[2:])
                return jnp.moveaxis(t, 1, 0)      # (A, N, B/A, ...)

            mbs = jax.tree.map(split_mb, batch)

            def mb_body(carry, mb):
                g_c, g_s, tot, met = carry
                (t, m), (gc, gs) = grad_fn(cad, sad, mb)
                g_c = jax.tree.map(jnp.add, g_c, gc)
                g_s = jax.tree.map(jnp.add, g_s, gs)
                met = jax.tree.map(jnp.add, met, m)
                return (g_c, g_s, tot + t, met), None

            zeros_like_f32 = lambda tr: jax.tree.map(
                lambda x: jnp.zeros(x.shape, x.dtype), tr)
            met0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, x.dtype),
                jax.eval_shape(lambda: loss_fn(cad, sad, jax.tree.map(
                    lambda t: t[0], mbs))[1]))
            (g_cad, g_sad, total, metrics), _ = jax.lax.scan(
                mb_body,
                (zeros_like_f32(cad), zeros_like_f32(sad),
                 jnp.float32(0.0), met0),
                mbs)
            scale = 1.0 / microbatch
            g_cad = jax.tree.map(lambda g: g * scale, g_cad)
            g_sad = jax.tree.map(lambda g: g * scale, g_sad)
            total = total * scale
            metrics = jax.tree.map(lambda m: m * scale, metrics)
        else:
            (total, metrics), (g_cad, g_sad) = grad_fn(cad, sad, batch)

        metrics = dict(metrics)
        new_sm_ef = metrics.pop("smashed_ef", None)
        if new_sm_ef is not None:
            # inactive (deadline-dropped / elastic) clients transmitted
            # nothing: their accumulated residual must survive the round
            m = active.reshape((-1,) + (1,) * (new_sm_ef.ndim - 1)) > 0
            new_sm_ef = jnp.where(m, new_sm_ef, state["smashed_ef"])

        new_cad, opt_c = opt.update(g_cad, state["opt_c"], cad, lr_c)
        new_sad, opt_s = opt.update(g_sad, state["opt_s"], sad, lr_s)

        new_cad, ef = _round_aggregate(
            model, compress=compress, topk_frac=topk_frac,
            agg_every=agg_every, cad_start=cad, new_cad=new_cad,
            new_sad=new_sad, cuts=cuts, weights=weights, active=active,
            ef=state.get("ef"), round_idx=state["round"],
            ranks=_state_ranks(model, state, cuts),
            edge_assign=state.get("edge_assign"), num_edges=num_edges)

        new_state = dict(state)
        new_state.update(client_adapters=new_cad, server_adapters=new_sad,
                         opt_c=opt_c, opt_s=opt_s,
                         round=state["round"] + 1)
        if ef is not None:
            new_state["ef"] = ef
        if new_sm_ef is not None:
            new_state["smashed_ef"] = new_sm_ef
        metrics["total"] = total
        return constrain_state(new_state, mesh), metrics

    round_step = under_mesh(round_step, mesh)
    if jit:
        return jax.jit(round_step, donate_argnums=(1,))
    return round_step


def _round_aggregate(model: Model, *, compress, topk_frac, agg_every,
                     cad_start, new_cad, new_sad, cuts, weights, active,
                     ef, round_idx, steps=None, ranks=None,
                     edge_assign=None, num_edges: int = 1):
    """b1-b3 at the round boundary, shared by both engines: optional
    adapter-delta compression (top-k+EF / int8), survivor- and
    step-normalized FedAvg, then the b3/b4 broadcast.  ranks: optional
    (N, M) per-client effective ranks for heterogeneous-rank column-wise
    aggregation (aggregation.fedavg).  edge_assign/num_edges: optional
    two-tier clients -> edges -> server mode (aggregation.fedavg).
    Returns (client_adapters', ef')."""

    def do_agg(operand):
        cad_in, ef_in = operand
        cad_for_agg = cad_in
        ef_out = ef_in
        if compress == "topk":
            delta = aggregation.adapter_delta(cad_in, cad_start)
            dense, ef_out, _ = ErrorFeedback.apply(delta, ef_in,
                                                   topk_frac)
            cad_for_agg = aggregation.apply_delta(cad_start, dense)
        elif compress == "int8":
            delta = aggregation.adapter_delta(cad_in, cad_start)
            deq = int8_dequantize(int8_quantize(delta))
            deq = jax.tree.map(lambda d, ref: d.astype(ref.dtype),
                               deq, delta)
            cad_for_agg = aggregation.apply_delta(cad_start, deq)
        agg = aggregation.fedavg(model, cad_for_agg, cuts, weights,
                                 active, steps=steps, ranks=ranks,
                                 edge_assign=edge_assign,
                                 num_edges=num_edges)
        out = aggregation.broadcast_after_agg(model, cad_for_agg, agg,
                                              new_sad, cuts)
        return out, ef_out

    def no_agg(operand):
        return operand

    if agg_every <= 1:
        return do_agg((new_cad, ef))
    return jax.lax.cond((round_idx + 1) % agg_every == 0,
                        do_agg, no_agg, (new_cad, ef))


# ---------------------------------------------------------------------------
# local-steps engine (scheduler == "local_steps")


def _select_clients(step_act, new_tree, old_tree):
    """Per-leaf `where` keeping old values for clients inactive this inner
    step.  Client axis is axis 1 for stacked leaves ((Lg, N, ...)); scalar
    leaves (the optimizer step count) advance while anyone is active."""
    any_act = jnp.any(step_act > 0)

    def sel(n, o):
        if n.ndim == 0:
            return jnp.where(any_act, n, o)
        if n.ndim == 1:
            return jnp.where(step_act > 0, n, o)
        m = step_act.reshape((1, -1) + (1,) * (n.ndim - 2)) > 0
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new_tree, old_tree)


def _select_any(step_act, new_tree, old_tree):
    """Whole-tree `where`: advance only while any client is active."""
    any_act = jnp.any(step_act > 0)
    return jax.tree.map(lambda n, o: jnp.where(any_act, n, o),
                        new_tree, old_tree)


def _make_local_steps_step(model: Model, opt, smasher, *, policy, remat,
                           ce_chunk, agg_every, compress, topk_frac,
                           max_local_steps: int, buckets=None,
                           num_edges: int = 1,
                           server_step_norm: bool = True,
                           jit: bool = True):
    """The K-inner-step engine (see make_train_step docstring).

    batch leaves carry a leading (K,) step axis; state carries
    "step_budgets".  One lax.scan body = one local step on every client
    simultaneously (the SPMD client axis), masked so client i freezes
    after budgets[i] steps.  Reported metrics are the FIRST inner step's
    (the round-start loss), keeping loss curves comparable across
    schedulers."""
    K = max_local_steps
    mesh = policy.mesh

    def round_step(base_params, state, batch, weights, active, lr_c, lr_s):
        state = constrain_state(state, mesh)
        batch = constrain_client_batch(batch, mesh, step_axis=True)
        cad, sad = state["client_adapters"], state["server_adapters"]
        cuts = state["cuts"]
        rank_cut = state.get("rank_cut")
        choice = state.get("smashed_choice")
        tfrac = state.get("topk_frac")
        budgets = state["step_budgets"]
        sm_ef = state.get("smashed_ef")
        has_ef = sm_ef is not None
        # 1/K_i server-gradient normalization (see make_train_step): a
        # client running K_i inner steps contributes 1/K_i of its server
        # gradient per step.  Exactly 1.0 when budgets == 1 (bitwise pin)
        srv_scale = None
        if server_step_norm:
            srv_scale = 1.0 / jnp.clip(budgets.astype(jnp.float32),
                                       1.0, float(K))

        def inner(carry, xs):
            mb, k = xs
            if has_ef:
                cad_c, sad_c, opt_c, opt_s, ef_c = carry
            else:
                cad_c, sad_c, opt_c, opt_s = carry
                ef_c = None
            step_act = active * (k < budgets).astype(active.dtype)
            wl = weights * step_act
            wl = wl / jnp.maximum(jnp.sum(wl), 1e-9)
            boundary = _cut_boundary(smasher, buckets, choice, cuts,
                                     residual=ef_c, topk_frac=tfrac)

            def loss_fn(cad_, sad_):
                eff = split.merge_adapters(model, cad_, sad_, cuts,
                                           rank_cut=rank_cut,
                                           server_scale=srv_scale)
                per_loss, metrics = model.loss(
                    base_params, eff, mb, policy=policy, remat=remat,
                    ce_chunk=ce_chunk, per_client=True, boundary=boundary)
                total = jnp.sum(wl * per_loss)
                return total, metrics

            (total, metrics), (g_cad, g_sad) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(cad_c, sad_c)
            metrics = dict(metrics)
            new_ef = metrics.pop("smashed_ef", None)

            new_cad, new_opt_c = opt.update(g_cad, opt_c, cad_c, lr_c)
            new_cad = _select_clients(step_act, new_cad, cad_c)
            new_opt_c = _select_clients(step_act, new_opt_c, opt_c)
            new_sad, new_opt_s = opt.update(g_sad, opt_s, sad_c, lr_s)
            new_sad = _select_any(step_act, new_sad, sad_c)
            new_opt_s = _select_any(step_act, new_opt_s, opt_s)
            out = (new_cad, new_sad, new_opt_c, new_opt_s)
            if has_ef:
                # residual carries the client axis FIRST ((N, B, S, d))
                m = step_act.reshape((-1,) + (1,) * (new_ef.ndim - 1)) > 0
                new_ef = jnp.where(m, new_ef, ef_c)
                out = out + (new_ef,)
            metrics["total"] = total
            return out, metrics

        carry0 = (cad, sad, state["opt_c"], state["opt_s"])
        if has_ef:
            carry0 = carry0 + (sm_ef,)
        ks = jnp.arange(K)
        carry, stacked = jax.lax.scan(inner, carry0, (batch, ks))
        if has_ef:
            new_cad, new_sad, opt_c, opt_s, new_sm_ef = carry
        else:
            new_cad, new_sad, opt_c, opt_s = carry
            new_sm_ef = None
        # round metrics = first inner step (round-start loss; every active
        # client runs step 0, so it is comparable across schedulers)
        metrics = jax.tree.map(lambda m: m[0], stacked)

        # -- b1-b3: aggregate at the round boundary, step-normalized ------
        eff_steps = jnp.clip(budgets.astype(jnp.float32), 1.0, float(K))
        new_cad, ef = _round_aggregate(
            model, compress=compress, topk_frac=topk_frac,
            agg_every=agg_every, cad_start=cad, new_cad=new_cad,
            new_sad=new_sad, cuts=cuts, weights=weights, active=active,
            ef=state.get("ef"), round_idx=state["round"],
            steps=eff_steps, ranks=_state_ranks(model, state, cuts),
            edge_assign=state.get("edge_assign"), num_edges=num_edges)

        new_state = dict(state)
        new_state.update(client_adapters=new_cad, server_adapters=new_sad,
                         opt_c=opt_c, opt_s=opt_s,
                         round=state["round"] + 1)
        if ef is not None:
            new_state["ef"] = ef
        if new_sm_ef is not None:
            new_state["smashed_ef"] = new_sm_ef
        return constrain_state(new_state, mesh), metrics

    round_step = under_mesh(round_step, mesh)
    if jit:
        return jax.jit(round_step, donate_argnums=(1,))
    return round_step


# ---------------------------------------------------------------------------
# async buffered engine (scheduler == "async", FedBuff-style)


def _make_async_step(model: Model, opt, smasher, *, policy, remat,
                     ce_chunk, buffer_size: int, staleness_power: float,
                     buckets=None, num_edges: int = 1,
                     server_step_norm: bool = True, jit: bool = True):
    """One event tick of the buffered-asynchronous engine.

    step(base_params, state, batch, weights, active, lr_c, lr_s)
      -> (state', metrics)

    active: (N,) {0,1} — the clients whose local step COMPLETES at this
    simulated instant (the host event queue's current tick).  Their
    adapter rows and optimizer slots advance one step; everyone else is
    frozen (unlike the barrier engines there is no end-of-round broadcast
    to squash drift, so freezing is mandatory).  The completions join the
    server buffer; when fill >= buffer_size the buffered rows are FedAvg'd
    with weights w_i * (1+staleness_i)^-p / steps_i and only the buffered
    clients are re-synced to the new global adapters.

    Extra metrics (all pre-aggregation): "buffer_fill", "buffer_mask",
    "staleness", "aggregated" (whether this tick closed a round), and
    "fleet_total" — the weights-averaged loss over the WHOLE fleet (every
    client's current batch against its current, possibly stale, row).
    The tick's training loss ("total") covers only the finishing clients,
    which is the wrong quantity to compare against a barrier scheduler's
    fleet-average round loss; records use fleet_total so loss curves stay
    comparable across schedulers (same contract as the local-steps
    engine's first-inner-step metrics).  state["round"] counts
    aggregations, not ticks."""
    M = buffer_size
    mesh = policy.mesh

    def round_step(base_params, state, batch, weights, active, lr_c, lr_s):
        state = constrain_state(state, mesh)
        batch = constrain_client_batch(batch, mesh)
        cad, sad = state["client_adapters"], state["server_adapters"]
        cuts = state["cuts"]
        n = active.shape[0]
        if M > n:
            raise ValueError(
                f"buffer_size={M} can never fill: only {n} distinct "
                "clients exist; clamp it to the fleet size")
        rank_cut = state.get("rank_cut")
        sm_ef = state.get("smashed_ef")
        wl = weights * active
        wl = wl / jnp.maximum(jnp.sum(wl), 1e-9)
        boundary = _cut_boundary(smasher, buckets,
                                 state.get("smashed_choice"), cuts,
                                 residual=sm_ef,
                                 topk_frac=state.get("topk_frac"))
        # this tick is the finisher's (buffer_steps+1)-th local step since
        # its last flush: 1/K_i server-gradient discount (see
        # make_train_step).  Exactly 1.0 right after a flush, so an
        # always-flushing (const-speed) run is bitwise-unchanged
        srv_scale = None
        if server_step_norm:
            srv_scale = 1.0 / (state["buffer_steps"] + 1.0)

        def loss_fn(cad_, sad_, mb):
            eff = split.merge_adapters(model, cad_, sad_, cuts,
                                       rank_cut=rank_cut,
                                       server_scale=srv_scale)
            per_loss, metrics = model.loss(
                base_params, eff, mb, policy=policy, remat=remat,
                ce_chunk=ce_chunk, per_client=True, boundary=boundary)
            total = jnp.sum(wl * per_loss)
            return total, (per_loss, metrics)

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        (total, (per_loss, metrics)), (g_cad, g_sad) = grad_fn(cad, sad,
                                                               batch)
        wf = weights / jnp.maximum(jnp.sum(weights), 1e-9)
        fleet_total = jnp.sum(wf * per_loss)

        metrics = dict(metrics)
        new_sm_ef = metrics.pop("smashed_ef", None)
        if new_sm_ef is not None:
            m = active.reshape((-1,) + (1,) * (new_sm_ef.ndim - 1)) > 0
            new_sm_ef = jnp.where(m, new_sm_ef, state["smashed_ef"])

        # only the finishing clients' rows/slots advance; the server side
        # advances whenever anyone finishes (it co-trained with them)
        new_cad, opt_c = opt.update(g_cad, state["opt_c"], cad, lr_c)
        new_cad = _select_clients(active, new_cad, cad)
        opt_c = _select_clients(active, opt_c, state["opt_c"])
        new_sad, opt_s = opt.update(g_sad, state["opt_s"], sad, lr_s)
        new_sad = _select_any(active, new_sad, sad)
        opt_s = _select_any(active, opt_s, state["opt_s"])

        # -- buffer bookkeeping (all data; no recompilation per event) ----
        buf = jnp.clip(state["buffer_mask"] + active, 0.0, 1.0)
        bsteps = state["buffer_steps"] + active
        fill = jnp.sum(buf)
        staleness = (state["global_version"]
                     - state["adapter_version"]).astype(jnp.float32)
        aggregate = fill >= M

        def do_agg(operand):
            cad_in, buf_, bsteps_, ver_, gver_ = operand
            agg = aggregation.fedavg(
                model, cad_in, cuts, weights, buf_,
                steps=jnp.maximum(bsteps_, 1.0), staleness=staleness,
                staleness_power=staleness_power,
                ranks=_state_ranks(model, state, cuts),
                edge_assign=state.get("edge_assign"),
                num_edges=num_edges)
            out = aggregation.broadcast_after_agg(
                model, cad_in, agg, new_sad, cuts, recv_mask=buf_)
            new_gver = gver_ + 1
            new_ver = jnp.where(buf_ > 0, new_gver, ver_)
            return (out, jnp.zeros_like(buf_), bsteps_ * (1.0 - buf_),
                    new_ver, new_gver)

        def no_agg(operand):
            return operand

        new_cad, new_buf, new_bsteps, new_ver, new_gver = jax.lax.cond(
            aggregate, do_agg, no_agg,
            (new_cad, buf, bsteps, state["adapter_version"],
             state["global_version"]))

        new_state = dict(state)
        new_state.update(client_adapters=new_cad, server_adapters=new_sad,
                         opt_c=opt_c, opt_s=opt_s,
                         buffer_mask=new_buf, buffer_steps=new_bsteps,
                         adapter_version=new_ver, global_version=new_gver,
                         round=state["round"]
                         + aggregate.astype(jnp.int32))
        if new_sm_ef is not None:
            new_state["smashed_ef"] = new_sm_ef
        metrics["total"] = total
        metrics["fleet_total"] = fleet_total
        metrics["buffer_fill"] = fill
        metrics["buffer_mask"] = buf
        metrics["staleness"] = staleness
        metrics["aggregated"] = aggregate
        return constrain_state(new_state, mesh), metrics

    round_step = under_mesh(round_step, mesh)
    if jit:
        return jax.jit(round_step, donate_argnums=(1,))
    return round_step


def make_eval_step(model: Model, *, policy: ShardingPolicy = NO_SHARDING,
                   ce_chunk: int = 0, jit: bool = True):
    """Evaluate the GLOBAL model (paper b4) on per-client eval batches.

    Returns per-client (loss, accuracy) — the inputs to the C3 rule.
    The function is `c3_eval_step`, so its module is `jit_c3_eval_step`."""

    def c3_eval_step(base_params, state, batch, weights):
        eff = split.serve_adapters(model, state["client_adapters"],
                                   state["server_adapters"], state["cuts"],
                                   weights,
                                   rank_cut=state.get("rank_cut"))
        per_loss, metrics = model.loss(base_params, eff, batch,
                                       policy=policy, ce_chunk=ce_chunk,
                                       per_client=True)
        return per_loss, metrics

    c3_eval_step = under_mesh(c3_eval_step, policy.mesh)
    return jax.jit(c3_eval_step) if jit else c3_eval_step


def with_error_feedback(state: Params) -> Params:
    """Attach zeroed EF residuals (needed before compress='topk')."""
    state = dict(state)
    state["ef"] = ErrorFeedback.init(state["client_adapters"])
    return state


def with_step_budgets(state: Params) -> Params:
    """Attach the per-client local-step budget array (needed before the
    max_local_steps > 1 engine).  The scheduler overwrites it each round;
    it lives in state so checkpoints round-trip it."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["step_budgets"] = jnp.ones((n,), jnp.int32)
    return state


def with_async_buffer(state: Params) -> Params:
    """Attach the FedBuff buffer/version arrays (needed before the
    async_buffer=True engine).  All zeros: empty buffer, every client on
    global version 0.  Lives in state so checkpoints round-trip a
    mid-buffer snapshot bit-exactly."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["buffer_mask"] = jnp.zeros((n,), jnp.float32)
    state["buffer_steps"] = jnp.zeros((n,), jnp.float32)
    state["adapter_version"] = jnp.zeros((n,), jnp.int32)
    state["global_version"] = jnp.zeros((), jnp.int32)
    return state


def with_per_client_opt_steps(state: Params) -> Params:
    """Vectorize the client optimizer's step counter to one count per
    client ((N,), masked increments via _select_clients) so Adam's bias
    correction tracks each client's ACTUAL number of steps.  Required for
    the async engine; fixes the shared-count over-correction for
    small-budget clients under local_steps (ROADMAP)."""
    state = dict(state)
    n = state["cuts"].shape[0]
    opt_c = dict(state["opt_c"])
    cnt = opt_c.get("count")
    if cnt is not None and jnp.ndim(cnt) == 0:
        opt_c["count"] = jnp.full((n,), cnt, jnp.int32)
    state["opt_c"] = opt_c
    return state


def with_rank_cut(state: Params, r_cut: int) -> Params:
    """Attach the co-controller's per-client rank-at-cut array ((N,)
    int32, initialized to the static policy's r_cut).  Once present, the
    engines read rank from state instead of LoRAConfig — rank becomes
    per-client data, moved by C3 without recompiles."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["rank_cut"] = jnp.full((n,), int(r_cut), jnp.int32)
    return state


def with_edge_assign(state: Params, num_edges: int) -> Params:
    """Attach the edge-group assignment ((N,) int32, client i -> edge
    i % num_edges) for two-tier aggregation (make_train_step num_edges).
    Assignment is data — the host (or population gather) may overwrite
    it any round without recompiling."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["edge_assign"] = jnp.arange(n, dtype=jnp.int32) % int(num_edges)
    return state


def with_smashed_choice(state: Params, index: int = 0) -> Params:
    """Attach the co-controller's per-client compressor-bucket index
    ((N,) int32 into make_train_step's compressor_buckets tuple)."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["smashed_choice"] = jnp.full((n,), int(index), jnp.int32)
    return state


def with_topk_frac(state: Params, frac: float) -> Params:
    """Attach the co-controller's per-client continuous topk keep
    fraction ((N,) float32, initialized uniform).  Once present, the
    bucket cut boundary runs its topk bucket at each client's own
    fraction (smashed.make_multi_boundary topk_frac) — the fraction is
    data the controller moves without recompiling."""
    state = dict(state)
    n = state["cuts"].shape[0]
    state["topk_frac"] = jnp.full((n,), float(frac), jnp.float32)
    return state


def prepare_state(state: Params, *, max_local_steps: int = 1,
                  async_buffer: bool = False, rank_cut=None,
                  smashed_choice=None, topk_frac=None,
                  edge_groups: int = 1) -> Params:
    """Attach every scheduler-conditional state leaf in one place —
    the single source of truth for the engine's state template, shared
    by SplitFTSystem and the cell builders so the two paths can never
    drift (a mismatch only surfaces later as a restore()/eval_shape
    template error).

    rank_cut / smashed_choice / topk_frac: initial per-client
    rank-at-cut, compressor-bucket index, and continuous topk keep
    fraction for the adaptive co-controller (None leaves the static
    policy in force — the pre-controller template, bit-exact)."""
    if max_local_steps > 1:
        state = with_step_budgets(state)
    if async_buffer:
        state = with_async_buffer(state)
    if max_local_steps > 1 or async_buffer:
        # clients take unequal step counts inside a round: Adam's bias
        # correction must track each client's own count
        state = with_per_client_opt_steps(state)
    if rank_cut is not None:
        state = with_rank_cut(state, rank_cut)
    if smashed_choice is not None:
        state = with_smashed_choice(state, smashed_choice)
    if topk_frac is not None:
        state = with_topk_frac(state, topk_frac)
    if edge_groups > 1:
        state = with_edge_assign(state, edge_groups)
    return state


def with_smashed_ef(state: Params, model: Model) -> Params:
    """Attach the zeroed smashed-channel EF residual ((N, B, S, d_model),
    needed before smashed_compress='topk' with error feedback)."""
    state = dict(state)
    t = model.arch.train
    n = state["cuts"].shape[0]
    state["smashed_ef"] = jnp.zeros(
        (n, t.batch_size, t.seq_len, model.arch.model.d_model),
        jnp.float32)
    return state
