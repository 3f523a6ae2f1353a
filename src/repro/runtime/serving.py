"""Continuous-batching, multi-adapter serving engine.

Serving a SplitFT deployment means serving *many* fine-tuned variants of
one base model at once: every client's personalized adapter is a separate
"model" that shares all base weights.  The engine holds the stacked
adapter pool (S-LoRA-style) and batches requests across adapters:

  * B fixed *slots*, each holding at most one in-flight request;
  * an admission queue: a request waits until a slot (and, in paged mode,
    enough KV pages) frees up;
  * per-request *prefill* into a small bucketed temp cache, installed
    into the slot (one compiled prefill per bucket size);
  * one *decode tick* advances every occupied slot by one token in a
    single jitted call — the per-slot adapter choice rides an (B,) ids
    array through the indexed LoRA kernel, and the slot -> request
    mapping is data, so admissions and completions never retrace
    (`decode_traces` pins this in tests).

Policy is data, as everywhere in this codebase: heterogeneous adapter
ranks are masked rank slots in the pool, the cut/rank history of each
client is already baked into its pool row by split.merge_adapters, and
the page table (paged mode, runtime.kv_cache) makes cache placement data
too.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lora as lora_lib
from repro.core import split as split_lib
from repro.runtime import kv_cache
from repro.runtime.spans import span

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Adapter pools


def attach_ids(pool: Params, ids) -> Params:
    """Augment the stacked pool {group:{target:{"A":(Lg,P,din,r),...}}}
    with a per-row adapter-id leaf ((Lg, B) so layer scans slice it like
    every other adapter leaf) — the layout lora_apply dispatches on."""
    ids = jnp.asarray(ids, jnp.int32)
    out: Params = {}
    for gname, targets in pool.items():
        out[gname] = {}
        for tname, ad in targets.items():
            lg = ad["A"].shape[0]
            out[gname][tname] = dict(
                ad, ids=jnp.broadcast_to(ids[None], (lg,) + ids.shape))
    return out


def build_adapter_pool(model, key, num_adapters: int, *, ranks=None,
                       dtype=jnp.float32) -> Params:
    """Random stacked pool for benches/tests: P distinct adapters at max
    rank, optionally rank-masked per adapter (ranks: (P,) ints — the
    heterogeneous-rank case, expressed as masked slots)."""
    ad = lora_lib.init_adapters(model, key, num_clients=num_adapters,
                                dtype=dtype)
    # init_adapters starts B at zero (identity adapter); perturb it so the
    # P adapters actually produce distinct outputs
    flat, treedef = jax.tree_util.tree_flatten(ad)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(flat))
    flat = [leaf if leaf.std() > 0 else
            0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
            for leaf, k in zip(flat, keys)]
    ad = jax.tree_util.tree_unflatten(treedef, flat)
    m = model.num_flat_layers
    if ranks is None:
        rank_arr = jnp.full((num_adapters, m), model.arch.lora.r_others,
                            jnp.int32)
    else:
        rank_arr = jnp.broadcast_to(
            jnp.asarray(ranks, jnp.int32)[:, None], (num_adapters, m))
    return lora_lib.mask_adapters(model, ad, rank_arr)


def pool_from_state(model, state: Params) -> Params:
    """The per-client personalized adapters of a SplitFT training state,
    as a serving pool (P = N clients).  merge_adapters already yields the
    apply-ready client-axis tree — the pool IS the training layout."""
    return split_lib.merge_adapters(
        model, state["client_adapters"], state["server_adapters"],
        state["cuts"], rank_cut=state.get("rank_cut"))


def pool_from_population(model, state: Params, store, pids: Sequence[int]
                         ) -> Params:
    """Serve specific population members: gather their persistent adapter
    rows from PopulationStore slots into the engine state's client axis,
    then build the pool for exactly those pids (row i serves pids[i])."""
    pids = [int(p) for p in pids]
    n = len(pids)
    if n > store.cohort:
        raise ValueError(
            f"{n} pids exceed the store's client axis ({store.cohort}); "
            "serve in groups of at most the training cohort size")
    padded = pids + [pids[-1]] * (store.cohort - n)
    gathered = store.gather(state, padded)
    pool = pool_from_state(model, gathered)
    return jax.tree.map(lambda v: v[:, :n], pool)


def num_pool_adapters(pool: Params) -> int:
    leaf = jax.tree_util.tree_leaves(pool)[0]
    return leaf.shape[1]


# ---------------------------------------------------------------------------
# Requests / config


@dataclasses.dataclass
class Request:
    rid: int
    adapter: int                 # pool row
    tokens: np.ndarray           # (prompt_len,) int32
    max_new: int
    arrival: float = 0.0         # seconds from run() start


@dataclasses.dataclass
class ServeConfig:
    num_slots: int = 4
    max_len: int = 128           # per-slot KV capacity (prompt + generated)
    page_size: int = 0           # 0 = contiguous per-slot cache
    prompt_buckets: Tuple[int, ...] = ()   # default: doubling up to max_len

    def buckets(self) -> Tuple[int, ...]:
        if self.prompt_buckets:
            return tuple(sorted(self.prompt_buckets))
        lo = self.page_size if self.page_size else 8
        # paged: buckets are whole pages, so the top one rounds max_len up
        # (prompts are still capacity-checked against max_len itself)
        top = (math.ceil(self.max_len / self.page_size) * self.page_size
               if self.page_size else self.max_len)
        out = []
        b = lo
        while b < top:
            out.append(b)
            b *= 2
        out.append(top)
        return tuple(out)


# ---------------------------------------------------------------------------
# Engine


class ServingEngine:
    """Slot scheduler + jitted prefill/decode over a stacked adapter pool.

    All sampling is greedy (argmax) — the parity contract with the serial
    single-adapter oracle is exact-token equality, so decode is
    deterministic by construction.

    Latency stamps are seconds of `clock` since the engine's origin (its
    construction, reset by `run`): `t_submit` is when the request was
    due, `t_first` when its first token reached the host after the
    prefill, `t_done` when the tick that produced its last token had
    returned it to the host."""

    def __init__(self, model, params: Params, pool: Params,
                 cfg: ServeConfig, dtype=jnp.float32,
                 clock=time.perf_counter):
        self.model = model
        self.clock = clock
        self.t0 = clock()
        self.params = params
        self.pool = pool
        self.cfg = cfg
        self.dtype = dtype
        self.num_adapters = num_pool_adapters(pool)
        if cfg.page_size:
            if any(b % cfg.page_size for b in cfg.buckets()):
                raise ValueError(
                    f"prompt buckets {cfg.buckets()} must be multiples of "
                    f"page_size={cfg.page_size}")
            self._n_pages = kv_cache.default_num_pages(
                cfg.num_slots, cfg.max_len, cfg.page_size)
            self.cache = kv_cache.init_paged_cache(
                model, cfg.num_slots, cfg.max_len, cfg.page_size, dtype,
                num_pages=self._n_pages)
            self.allocator = kv_cache.PageAllocator(self._n_pages)
            self._p_max = kv_cache.pages_per_slot(cfg.max_len,
                                                  cfg.page_size)
        else:
            self.cache = model.init_cache((cfg.num_slots,), cfg.max_len,
                                          dtype)
            self.allocator = None
        self.slots: List[Optional[Dict[str, Any]]] = [None] * cfg.num_slots
        self.queue: deque = deque()
        self.results: Dict[int, Dict[str, Any]] = {}
        self.decode_traces = {"n": 0}
        self.prefill_traces = {"n": 0}

        def _decode_raw(params, pool, ids, toks, cache, active):
            self.decode_traces["n"] += 1
            adapters = attach_ids(pool, ids)
            logits, cache = self.model.decode_step(params, adapters, toks,
                                                   cache)
            nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
            # freed/idle slots must not accumulate length (their writes go
            # to position 0 / the trash page and are never read)
            cache = dict(cache)
            cache["len"] = jnp.where(active, cache["len"], 0)
            return nxt, cache

        def _prefill_raw(params, pool, ids, toks, plen):
            self.prefill_traces["n"] += 1
            bucket = toks.shape[1]
            temp = self.model.init_cache((1,), bucket, self.dtype)
            x, _, temp = self.model.forward(
                params, attach_ids(pool, ids), {"tokens": toks},
                cache=temp, mode="prefill")
            # logits at the true last prompt position, not the bucket pad
            xl = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=1)
            logits = self.model.head(params, xl)
            return jnp.argmax(logits[0, -1], -1).astype(jnp.int32), temp

        self._decode = jax.jit(_decode_raw)
        self._prefill = jax.jit(_prefill_raw)    # retraces per bucket
        self._install_paged = jax.jit(kv_cache.install_slot_paged)
        self._install_contig = jax.jit(kv_cache.install_slot_contiguous)
        self._free = jax.jit(kv_cache.free_slot)

    # -- admission -------------------------------------------------------

    def bucket_for(self, plen: int) -> int:
        for b in self.cfg.buckets():
            if b >= plen:
                return b
        raise ValueError(f"prompt length {plen} exceeds max bucket "
                         f"{self.cfg.buckets()[-1]}")

    def now(self) -> float:
        """Seconds of the engine's clock since its origin."""
        return self.clock() - self.t0

    def submit(self, req: Request, *, now: Optional[float] = None):
        """Enqueue a request due at `now` (default: the engine's clock).
        Raises immediately (loudly) if the request can never fit the
        per-slot cache — truncating silently would corrupt the
        generation."""
        plen = int(np.asarray(req.tokens).shape[-1])
        total = plen + req.max_new
        if plen < 1 or req.max_new < 1:
            raise ValueError(f"request {req.rid}: empty prompt or "
                             "non-positive max_new")
        if total > self.cfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({plen}) + max_new "
                f"({req.max_new}) = {total} exceeds the per-slot KV "
                f"capacity max_len={self.cfg.max_len}; raise --max-len or "
                "shorten the request")
        if not 0 <= req.adapter < self.num_adapters:
            raise ValueError(f"request {req.rid}: adapter {req.adapter} "
                             f"outside pool of {self.num_adapters}")
        self.queue.append(req)
        self.results[req.rid] = {
            "rid": req.rid, "adapter": req.adapter, "prompt_len": plen,
            "max_new": req.max_new,
            "t_submit": self.now() if now is None else now,
            "t_first": None, "t_done": None, "tokens": None}

    def _free_slot_ids(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def _admit(self) -> bool:
        admitted = False
        free = self._free_slot_ids()
        while self.queue and free:
            req = self.queue[0]
            plen = int(np.asarray(req.tokens).shape[-1])
            bucket = self.bucket_for(plen)
            pages: List[int] = []
            if self.allocator is not None:
                ps = self.cfg.page_size
                n_alloc = max(math.ceil((plen + req.max_new) / ps),
                              bucket // ps)
                if n_alloc > self.allocator.available:
                    break      # wait for completions to release pages
                pages = self.allocator.alloc(n_alloc)
            self.queue.popleft()
            slot = free.pop(0)
            with span("serve.prefill", rid=req.rid):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :plen] = np.asarray(req.tokens, np.int32)
                tok0, temp = self._prefill(self.params, self.pool,
                                           jnp.asarray([req.adapter],
                                                       jnp.int32),
                                           jnp.asarray(toks),
                                           jnp.int32(plen))
                if self.allocator is not None:
                    row = jnp.asarray(kv_cache.page_row(pages,
                                                        self._p_max))
                    self.cache = self._install_paged(
                        self.cache, jnp.int32(slot), temp, row,
                        jnp.int32(plen))
                else:
                    self.cache = self._install_contig(
                        self.cache, jnp.int32(slot), temp,
                        jnp.int32(plen))
                tok0 = int(tok0)
            now = self.now()
            res = self.results[req.rid]
            res["t_first"] = now
            state = {"rid": req.rid, "aid": req.adapter, "last": tok0,
                     "gen": [tok0], "remaining": req.max_new - 1,
                     "pages": pages}
            self.slots[slot] = state
            admitted = True
            if state["remaining"] == 0:
                self._finish(slot, now)
        return admitted

    # -- decode ----------------------------------------------------------

    def _finish(self, slot: int, now: float):
        state = self.slots[slot]
        res = self.results[state["rid"]]
        res["tokens"] = list(state["gen"])
        res["t_done"] = now
        self.cache = self._free(self.cache, jnp.int32(slot))
        if self.allocator is not None and state["pages"]:
            self.allocator.free(state["pages"])
        self.slots[slot] = None

    def step(self) -> bool:
        """One engine iteration: admit what fits, then one decode tick
        over all occupied slots.  Returns whether anything ran."""
        with span("serve.admit"):
            admitted = self._admit()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return admitted
        with span("serve.tick"):
            self._tick(occupied)
        return True

    def _tick(self, occupied: List[int]):
        b = self.cfg.num_slots
        toks = np.zeros((b, 1), np.int32)
        ids = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        for i in occupied:
            toks[i, 0] = self.slots[i]["last"]
            ids[i] = self.slots[i]["aid"]
            active[i] = True
        nxt, self.cache = self._decode(self.params, self.pool,
                                       jnp.asarray(ids), jnp.asarray(toks),
                                       self.cache, jnp.asarray(active))
        with span("serve.wait.tick"):
            nxt = np.asarray(nxt)
        now = self.now()
        for i in occupied:
            s = self.slots[i]
            tok = int(nxt[i])
            s["gen"].append(tok)
            s["last"] = tok
            s["remaining"] -= 1
            if s["remaining"] <= 0:
                self._finish(i, now)

    # -- driver ----------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> List[Dict[str, Any]]:
        """Serve a workload honoring per-request arrival offsets; returns
        per-request result dicts (tokens + timing) ordered by rid."""
        reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self.t0 = self.clock()
        i = 0
        while i < len(reqs) or self.has_work():
            now = self.now()
            while i < len(reqs) and reqs[i].arrival <= now:
                self.submit(reqs[i], now=reqs[i].arrival)
                i += 1
            ran = self.step()
            if not ran and not self.has_work() and i < len(reqs):
                wait = reqs[i].arrival - self.now()
                if wait > 0:
                    time.sleep(min(wait, 0.002))
        return [self.results[r.rid]
                for r in sorted(requests, key=lambda r: r.rid)]


# ---------------------------------------------------------------------------
# Serial oracle (the parity contract for tests)


def serial_reference(model, params: Params, pool: Params,
                     requests: Sequence[Request], *, max_len: int,
                     dtype=jnp.float32) -> Dict[int, List[int]]:
    """Greedy per-request generation, one request at a time in its own
    contiguous cache, same indexed pool with B = 1.  The batched engine
    must reproduce these tokens exactly (tests/test_serving.py)."""
    out: Dict[int, List[int]] = {}
    for req in requests:
        cache = model.init_cache((1,), max_len, dtype)
        adapters = attach_ids(pool, jnp.asarray([req.adapter], jnp.int32))
        toks = jnp.asarray(np.asarray(req.tokens, np.int32)[None])
        logits, cache = model.prefill(params, adapters, {"tokens": toks},
                                      cache)
        tok = int(jnp.argmax(logits[0, -1]))
        gen = [tok]
        for _ in range(req.max_new - 1):
            logits, cache = model.decode_step(
                params, adapters, jnp.asarray([[tok]], jnp.int32), cache)
            tok = int(jnp.argmax(logits[0, -1]))
            gen.append(tok)
        out[req.rid] = gen
    return out
