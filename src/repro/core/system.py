"""SplitFTSystem — host-side orchestration of the full paper workflow.

Owns: corpus -> tokenize -> partition (C4) -> per-client loaders ->
round loop -> eval, C3 adjustment, aggregation weights,
checkpoint/resume, elastic membership.

The round loop itself is split engine/policy:

  * the *engine* (rounds.make_train_step) is one jitted executable; which
    clients run and how many local steps each takes per round is data;
  * the *policy* is a RoundScheduler (repro.core.scheduler): sync
    (Algorithm 1 lockstep), deadline (straggler drop), local_steps
    (speed-proportional K_i per client), or async (FedBuff-style
    buffered asynchrony).  The scheduler also owns the simulated
    wall-clock accounting (`sim_time` / cumulative `sim_clock` in the
    round records) that the benchmarks compare.

C3 is likewise split engine/policy.  The round epilogue (`_adjust_c3`)
runs one of two host-side controllers: `accuracy` (the paper's rule —
cuts follow per-client accuracy alone) or `co` (adaptive.co_adjust —
per client, the (cut bucket, rank-at-cut bucket, smashed compressor)
triple minimizing the PREDICTED round makespan, priced through
`predict_round_times`, under an accuracy dead-band).  Whatever the
controller decides is written into round state as plain int32 arrays
("cuts", "rank_cut", "smashed_choice"): policy is data, so a moved
triple re-masks the next engine call instead of recompiling it, and
prediction reuses the exact comm/speed code the simulated clock
charges (jitter aside), keeping predicted == simulated testable.

The host loop has two shapes.  The barrier schedulers run one plan ->
one engine call -> one record per round (`_run_barrier`).  The async
scheduler replaces the barrier with an event-queue loop (`_run_async`):
phase-completion events drawn from the SpeedModel advance a simulated
clock; a step-completion tick is one engine call over the finishing
clients, and a round record is emitted whenever the server buffer
reaches `buffer_size` and flushes (one round == one aggregation, so
histories stay comparable across schedulers).

Time is modeled per phase (client compute / f2 uplink / server compute /
f4 downlink / adapter sync — runtime.straggler.PHASES).  With
`overlap_comm=False` each step is one event charging the serial phase
sum (the legacy clock); with `overlap_comm=True` the async loop runs the
phases as a double-buffered pipeline — compute of step k+1 overlaps the
transfers of step k — and only `adapter_sync` completions reach the
engine.  Elastic membership composes with the event loop: a leaver's
in-flight events are dropped (never relaunched), and a rejoiner enters
at the current clock with its next batch index.

Everything device-side lives in rounds.py; this class only moves numpy
batches in and metrics out, so it works identically on CPU (paper-scale
experiments) and on a mesh (dry-run / production).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import ArchConfig
from repro.core import adaptive, comm, rounds, smashed
from repro.core import scheduler as scheduler_lib
from repro.core.scheduler import RoundPlan
from repro.core.split import serve_adapters
from repro.data import (ClientDataLoader, make_client_loaders,
                        partition_dataset, synthetic_corpus)
from repro.data.pipeline import stack_client_batches
from repro.data.tokenizer import HashTokenizer
from repro.models.common import NO_SHARDING
from repro.models.model import Model, build_model
from repro.runtime import straggler
from repro.runtime import timemodel
from repro.runtime.spans import span
from repro.runtime import traces as traces_lib
from repro.runtime.elastic import ClientPool
from repro.runtime.population import CohortSampler, PopulationStore
from repro.runtime.straggler import SpeedModel


@dataclasses.dataclass
class SystemConfig:
    num_samples: int = 2000
    eval_samples: int = 256
    adjust_every: int = 1          # C3 cadence (rounds)
    agg_every: int = 1             # FedAvg cadence (rounds)
    compress: str = "none"         # adapter channel: none | topk | int8
    topk_frac: float = 0.05
    smashed_compress: Optional[str] = None   # f2/f4 channel: none | int8 |
                                             # fp8 | topk; None -> arch.split
    smashed_topk_frac: Optional[float] = None
    smashed_ef: Optional[bool] = None  # EF residual for smashed topk;
                                       # None -> on iff compressor is topk
    scheduler: Optional[str] = None    # sync | deadline | local_steps |
                                       # async; None -> arch.split.
                                       # scheduler (straggler_sim promotes
                                       # sync -> deadline, the legacy
                                       # spelling)
    max_local_steps: Optional[int] = None    # None -> arch.split
    straggler_sim: bool = False        # attach a SpeedModel
    deadline_frac: Optional[float] = None    # None -> arch.split
    buffer_size: Optional[int] = None  # async: aggregate every M distinct
                                       # client completions; None ->
                                       # arch.split (clamped to N)
    staleness_power: Optional[float] = None  # async: (1+s)^-p discount;
                                             # None -> arch.split
    overlap_comm: Optional[bool] = None  # pipeline the comm phases so
                                         # uplink of step k overlaps
                                         # compute of k+1; None ->
                                         # arch.split.overlap_comm
    speed_sigma: Optional[float] = None      # SpeedModel overrides (None
    bw_sigma: Optional[float] = None         # -> SpeedModel defaults);
    jitter_sigma: Optional[float] = None     # 0s = deterministic fleet
    bw_mean: Optional[float] = None          # mean link bandwidth (B/s);
                                             # inf = zero wire time
    client_flops_per_s: Optional[float] = None  # reference client device
                                                # throughput (FLOP/s) the
                                                # compute phase divides
                                                # by; None -> the
                                                # phase_times default
    server_flops_per_s: Optional[float] = None  # >0 charges the server
                                                # compute phase too
    server_ingest_bw: Optional[float] = None  # >0 charges the server's
                                              # adapter-ingest fan-in
                                              # (the hop hierarchical
                                              # aggregation shortens)
    edge_bw: Optional[float] = None           # edge->server link (B/s)
                                              # under edge_groups > 1
    population: Optional[int] = None   # fleet-scale population; None ->
                                       # arch.data.population; 0 = fleet
                                       # mode (clients ARE the population)
    edge_groups: Optional[int] = None  # two-tier aggregation groups;
                                       # None -> arch.split.edge_groups
                                       # (1 = flat, bitwise)
    server_step_norm: Optional[bool] = None  # 1/K_i server-gradient
                                             # normalization; None ->
                                             # arch.split.server_step_norm
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    keep_checkpoints: int = 3
    adaptive: Optional[bool] = None   # None -> arch.split.adaptive
    controller: Optional[str] = None  # C3 controller: accuracy | co;
                                      # None -> arch.split.controller
    rank_buckets: Optional[tuple] = None        # co: rank-at-cut search
                                                # set; None -> arch.split
                                                # (then (lora.r_cut,))
    compressor_buckets: Optional[tuple] = None  # co: compressor search
                                                # set; None -> arch.split
                                                # (then the configured
                                                # smashed_compress)
    acc_dead_band: Optional[float] = None  # None -> arch.split
    min_gain: Optional[float] = None       # None -> arch.split
    trace: Optional[str] = None        # replay a recorded heterogeneity
                                       # trace file (runtime/traces.py
                                       # JSON format); implies a
                                       # SpeedModel
    trace_gen: Optional[str] = None    # synthetic trace spec, e.g.
                                       # "diurnal:amp=0.8+markov"
                                       # (traces.make_trace_gen);
                                       # mutually exclusive with trace
    time_source: Optional[str] = None  # controller pricing source
                                       # (runtime/timemodel.py): analytic
                                       # | trace | measured; None ->
                                       # trace when a trace is installed,
                                       # else analytic (both bitwise with
                                       # the pre-pricer clock)
    ewma_alpha: float = 0.3            # measured: EWMA smoothing of the
                                       # observed/predicted phase ratios
    model_seed: Optional[int] = None   # price candidates from a
                                       # SpeedModel drawn at this seed
                                       # instead of the clock's (the
                                       # mis-specification testbed);
                                       # None -> the clock itself
    record_trace: Optional[str] = None  # dump the run's observed
                                        # per-phase factors to this path
                                        # as FileTrace JSON when run()
                                        # returns
    continuous_topk: Optional[bool] = None  # co: search the topk keep
                                            # fraction continuously
                                            # (state["topk_frac"]);
                                            # None -> arch.split
                                            # .continuous_topk


class SplitFTSystem:
    def __init__(self, arch: ArchConfig, sys_cfg: SystemConfig = None, *,
                 policy=NO_SHARDING, seed: int = 0, jit: bool = True):
        self.arch = arch
        self.sys = sys_cfg or SystemConfig()
        self.model = build_model(arch)
        self.policy = policy
        self.seed = seed
        n = arch.data.num_clients
        self.pool = ClientPool(n)
        self.population = (arch.data.population
                           if self.sys.population is None
                           else self.sys.population) or 0
        if 0 < self.population < n:
            raise ValueError(
                f"population={self.population} must be >= the cohort "
                f"size (num_clients={n}); the engine's client axis IS "
                "the cohort")

        # ---- data (C4) ----
        with span("splitft.setup.corpus"):
            tok = HashTokenizer(arch.model.vocab_size)
            texts = synthetic_corpus(self.sys.num_samples,
                                     seed=arch.data.seed)
            self.samples = [np.asarray(tok.encode(t), np.int32)
                            for t in texts]
            lengths = [len(s) for s in self.samples]
        # fleet mode partitions over the N clients directly; population
        # mode partitions over a fixed shard pool and maps pid -> shard
        # (pid % shards), so the partition cost is O(shards), not O(P),
        # and pid p sees the same shard at any population size >= shards
        self._n_shards = (n if not self.population
                          else min(self.population, max(n, 256)))
        with span("splitft.setup.partition"):
            parts = partition_dataset(
                lengths, self._n_shards, strategy=arch.data.partition,
                alpha=arch.data.alpha,
                num_classes=arch.data.num_length_classes,
                seed=arch.data.seed)
        self.parts = parts
        with span("splitft.setup.corpus"):
            eval_texts = synthetic_corpus(self.sys.eval_samples,
                                          seed=arch.data.seed + 777)
            eval_tokens = [np.asarray(tok.encode(t), np.int32)
                           for t in eval_texts]
        self._eval_tokens = eval_tokens
        with span("splitft.setup.loaders"):
            if not self.population:
                self.loaders = make_client_loaders(
                    self.samples, parts, batch_size=arch.train.batch_size,
                    seq_len=arch.train.seq_len, seed=seed)
                self.eval_loaders = make_client_loaders(
                    [t for t in eval_tokens],
                    [np.arange(len(eval_tokens))] * n,
                    batch_size=arch.train.batch_size,
                    seq_len=arch.train.seq_len, seed=seed + 999)
            else:
                # loaders are built per-pid on cohort install; seed the
                # slots with pids 0..n-1 (exactly the first P == C
                # cohort, which the sampler returns without consuming RNG)
                self._loader_cache: Dict[int, ClientDataLoader] = {}
                self._eval_loader_cache: Dict[int, ClientDataLoader] = {}
                pids0 = np.arange(n, dtype=np.int64)
                self.loaders = [self._loader_for(int(p)) for p in pids0]
                self.eval_loaders = [self._eval_loader_for(int(p))
                                     for p in pids0]

        # ---- round scheduler (policy) + straggler simulation ----
        sched_name = self.sys.scheduler
        if sched_name is None:
            sched_name = arch.split.scheduler
            if sched_name == "sync" and self.sys.straggler_sim:
                sched_name = "deadline"   # legacy: straggler_sim == drop
        dl_frac = (arch.split.deadline_frac
                   if self.sys.deadline_frac is None
                   else self.sys.deadline_frac)
        k_cap = (arch.split.max_local_steps
                 if self.sys.max_local_steps is None
                 else self.sys.max_local_steps)
        buf = (arch.split.async_buffer_size
               if self.sys.buffer_size is None else self.sys.buffer_size)
        buf = max(1, min(buf, n))      # can never exceed distinct clients
        spow = (arch.split.staleness_power
                if self.sys.staleness_power is None
                else self.sys.staleness_power)
        self.overlap_comm = (arch.split.overlap_comm
                             if self.sys.overlap_comm is None
                             else self.sys.overlap_comm)
        self.controller = (arch.split.controller
                           if self.sys.controller is None
                           else self.sys.controller)
        if self.controller not in ("accuracy", "co"):
            raise ValueError(f"unknown C3 controller "
                             f"{self.controller!r}; known: accuracy, co")
        self.scheduler = scheduler_lib.make_scheduler(
            sched_name, deadline_frac=dl_frac, max_local_steps=k_cap,
            buffer_size=buf, staleness_power=spow,
            overlap_comm=self.overlap_comm)
        speed_kw = {k: getattr(self.sys, k)
                    for k in ("speed_sigma", "bw_sigma", "jitter_sigma",
                              "bw_mean", "server_flops_per_s",
                              "server_ingest_bw", "edge_bw")
                    if getattr(self.sys, k) is not None}
        # the co-controller prices candidates with SpeedModel.phase_times,
        # so it always carries a speed model
        if self.sys.trace and self.sys.trace_gen:
            raise ValueError("set --trace (replay a recorded file) or "
                             "--trace-gen (synthetic generator), not "
                             "both")
        self.speed = (SpeedModel(n, seed=seed, **speed_kw)
                      if (self.sys.straggler_sim
                          or self.scheduler.needs_speed
                          or self.controller == "co"
                          or self.sys.trace or self.sys.trace_gen)
                      else None)
        if self.sys.trace:
            self.speed.trace = traces_lib.load_trace(self.sys.trace)
        elif self.sys.trace_gen:
            self.speed.trace = traces_lib.make_trace_gen(
                self.sys.trace_gen, seed=seed)

        # ---- time-model layer (runtime/timemodel.py) ----
        # charge vs predict split: the clock always charges the jittered
        # SpeedModel; time_source selects what the controller's
        # predictions are built from
        src = self.sys.time_source
        if src is not None and src not in timemodel.TIME_SOURCES:
            raise ValueError(f"unknown time_source {src!r}; known: "
                             f"{timemodel.TIME_SOURCES}")
        if self.speed is None:
            if src not in (None, "analytic"):
                raise ValueError(
                    f"time_source={src!r} needs the simulated clock's "
                    "timing hooks, but no SpeedModel is attached — "
                    "there are no observed phase times to learn from; "
                    "set straggler_sim=True, a speed-model scheduler, "
                    "or a trace")
            if self.sys.record_trace:
                raise ValueError(
                    "record_trace needs the simulated clock's timing "
                    "hooks, but no SpeedModel is attached — there are "
                    "no phase times to record; set straggler_sim=True, "
                    "a speed-model scheduler, or a trace")
            if self.sys.model_seed is not None:
                raise ValueError(
                    "model_seed mis-specifies the pricing SpeedModel, "
                    "but no SpeedModel is attached; set "
                    "straggler_sim=True first")
        if src is None:
            src = ("trace" if (self.speed is not None
                               and self.speed.trace is not None)
                   else "analytic")
        if src == "trace" and (self.speed is None
                               or self.speed.trace is None):
            raise ValueError(
                "time_source='trace' prices candidates at the trace "
                "window, but no trace is installed; set trace/trace_gen "
                "(or use analytic/measured)")
        self.time_source = src
        model_sm = None
        if self.sys.model_seed is not None \
                and int(self.sys.model_seed) != seed:
            model_sm = SpeedModel(n, seed=int(self.sys.model_seed),
                                  **speed_kw)
            model_sm.trace = self.speed.trace
        self.pricer = (timemodel.make_pricer(
            src, self.speed, model_sm, ewma_alpha=self.sys.ewma_alpha)
            if self.speed is not None else None)
        self.recorder = (timemodel.TraceRecorder(self.speed)
                         if self.sys.record_trace else None)
        self._observing = (src == "measured"
                           or self.recorder is not None)
        self.sim_clock = 0.0           # cumulative simulated seconds

        # ---- model/state (engine) ----
        with span("splitft.setup.init"):
            key = jax.random.PRNGKey(seed)
            k_base, k_state = jax.random.split(key)
            self.base_params = self.model.init_params(k_base)
            self.state = rounds.init_state(self.model, k_state,
                                           num_clients=n)
            if self.sys.compress == "topk":
                self.state = rounds.with_error_feedback(self.state)
        self.smashed_compress = (arch.split.smashed_compress
                                 if self.sys.smashed_compress is None
                                 else self.sys.smashed_compress)
        self.smashed_topk_frac = (arch.split.smashed_topk_frac
                                  if self.sys.smashed_topk_frac is None
                                  else self.sys.smashed_topk_frac)
        use_smashed_ef = (self.smashed_compress == "topk"
                          if self.sys.smashed_ef is None
                          else self.sys.smashed_ef)
        if use_smashed_ef and self.smashed_compress != "topk":
            raise ValueError(
                "smashed_ef=True requires smashed_compress='topk' "
                f"(got {self.smashed_compress!r}); int8/fp8 are "
                "memoryless round-trips with no residual to feed back")
        if use_smashed_ef:
            with span("splitft.setup.init"):
                self.state = rounds.with_smashed_ef(self.state, self.model)

        # ---- co-controller search space (cut x rank x compressor) ----
        self.acc_dead_band = (arch.split.acc_dead_band
                              if self.sys.acc_dead_band is None
                              else self.sys.acc_dead_band)
        self.min_gain = (arch.split.min_gain if self.sys.min_gain is None
                         else self.sys.min_gain)
        rb = (self.sys.rank_buckets if self.sys.rank_buckets is not None
              else arch.split.rank_buckets) or (arch.lora.r_cut,)
        self.rank_buckets = tuple(sorted({int(r) for r in rb}))
        if any(r < 1 or r > arch.lora.r_others for r in self.rank_buckets):
            raise ValueError(
                f"rank_buckets {self.rank_buckets} must lie in "
                f"[1, r_others={arch.lora.r_others}] (adapters are "
                "allocated at r_others; ranks are masks, not shapes)")
        cbk = (self.sys.compressor_buckets
               if self.sys.compressor_buckets is not None
               else arch.split.compressor_buckets) \
            or (self.smashed_compress,)
        # bucket index order == aggressiveness order: weakest compression
        # (most wire bytes) first, so "one step weaker" is index - 1
        self.comp_buckets = tuple(sorted(
            dict.fromkeys(cbk),
            key=lambda nm: -smashed.wire_bytes(
                nm, batch=arch.train.batch_size, seq=arch.train.seq_len,
                d_model=arch.model.d_model,
                topk_frac=self.smashed_topk_frac)))
        self.continuous_topk = (arch.split.continuous_topk
                                if self.sys.continuous_topk is None
                                else self.sys.continuous_topk)
        if self.continuous_topk:
            if self.controller != "co":
                raise ValueError(
                    "continuous_topk is a co-controller search knob; "
                    f"set controller='co' (got {self.controller!r})")
            if "topk" not in self.comp_buckets:
                raise ValueError(
                    "continuous_topk tunes the topk compressor's keep "
                    "fraction, but 'topk' is not in the compressor "
                    f"buckets {self.comp_buckets}")

        # ---- hierarchical aggregation + server-step normalization ----
        self.num_edges = max(1, (arch.split.edge_groups
                                 if self.sys.edge_groups is None
                                 else self.sys.edge_groups) or 1)
        self.server_step_norm = (arch.split.server_step_norm
                                 if self.sys.server_step_norm is None
                                 else self.sys.server_step_norm)

        is_async = self.scheduler.name == "async"
        co = self.controller == "co"
        if co and use_smashed_ef:
            raise ValueError(
                "the co-controller's per-client compressor choice does "
                "not compose with smashed error feedback (the EF "
                "residual is sized for one compressor's remainder "
                "semantics); set smashed_ef=False")
        init_rank = int(self.rank_buckets[int(np.argmin(np.abs(
            np.asarray(self.rank_buckets) - arch.lora.r_cut)))])
        init_choice = (self.comp_buckets.index(self.smashed_compress)
                       if self.smashed_compress in self.comp_buckets
                       else 0)
        with span("splitft.setup.init"):
            self.state = rounds.prepare_state(
                self.state, max_local_steps=self.scheduler.max_steps,
                async_buffer=is_async,
                rank_cut=init_rank if co else None,
                smashed_choice=init_choice if co else None,
                topk_frac=(self.smashed_topk_frac
                           if (co and self.continuous_topk) else None),
                edge_groups=self.num_edges)
        with span("splitft.setup.engine"):
            self.train_step = rounds.make_train_step(
                self.model, policy=policy, remat=arch.train.remat,
                agg_every=self.sys.agg_every, compress=self.sys.compress,
                topk_frac=self.sys.topk_frac,
                smashed_compress=self.smashed_compress,
                smashed_topk_frac=self.smashed_topk_frac,
                compressor_buckets=self.comp_buckets if co else None,
                max_local_steps=self.scheduler.max_steps,
                async_buffer=is_async, buffer_size=buf,
                staleness_power=spow, num_edges=self.num_edges,
                server_step_norm=self.server_step_norm, jit=jit)
            self.eval_step = rounds.make_eval_step(self.model,
                                                   policy=policy, jit=jit)

        # ---- C3 state ----
        self.c3_weights = np.ones(n)
        self.sample_counts = np.array([l.num_samples()
                                       for l in self.loaders], float)
        self._comm_cache = None        # (cuts bytes, comm dict) memo
        self._times_cache: Dict[Any, np.ndarray] = {}
        self.ckpt = (CheckpointManager(self.sys.checkpoint_dir,
                                       keep=self.sys.keep_checkpoints)
                     if self.sys.checkpoint_dir else None)
        self.history: List[Dict[str, Any]] = []
        self._adaptive = (arch.split.adaptive if self.sys.adaptive is None
                          else self.sys.adaptive)

        # ---- fleet-scale population (cohort engine) ----
        if self.population:
            sp_kw = (dict(speed_sigma=self.speed.speed_sigma,
                          bw_mean=self.speed.bw_mean,
                          bw_sigma=self.speed.bw_sigma)
                     if self.speed is not None else {})
            self.store = PopulationStore(self.population, self.state,
                                         seed=seed, **sp_kw)
            self.sampler = CohortSampler(self.population, n, seed=seed)
        else:
            self.store = None
            self.sampler = None
        self._cohort_pids: Optional[np.ndarray] = None
        self._cohort_cursors: Optional[np.ndarray] = None
        self._cohort_scattered = True

    # ------------------------------------------------------------------
    # fleet-scale population: cohort install / gather / scatter

    def _loader_for(self, pid: int) -> ClientDataLoader:
        """Per-pid train loader (population mode): pid p streams shard
        p % shards with a pid-keyed seed, so its batch sequence is a
        stable attribute surviving cohort churn.  With P == C this is
        exactly make_client_loaders' seed + i convention."""
        ld = self._loader_cache.get(pid)
        if ld is None:
            arch = self.arch
            part = self.parts[pid % self._n_shards]
            ld = ClientDataLoader([self.samples[j] for j in part],
                                  batch_size=arch.train.batch_size,
                                  seq_len=arch.train.seq_len,
                                  seed=self.seed + pid)
            if len(self._loader_cache) > 4 * len(self.pool.active):
                self._loader_cache.clear()   # bound memory under churn
            self._loader_cache[pid] = ld
        return ld

    def _eval_loader_for(self, pid: int) -> ClientDataLoader:
        ld = self._eval_loader_cache.get(pid)
        if ld is None:
            arch = self.arch
            ld = ClientDataLoader(self._eval_tokens,
                                  batch_size=arch.train.batch_size,
                                  seq_len=arch.train.seq_len,
                                  seed=self.seed + 999 + pid)
            if len(self._eval_loader_cache) > 4 * len(self.pool.active):
                self._eval_loader_cache.clear()
            self._eval_loader_cache[pid] = ld
        return ld

    def _install_cohort(self, pids: np.ndarray):
        """Point the whole host side at a new cohort: gather the pids'
        slots into engine state, recompute derived per-client arrays
        (edge assignment, C3 weights, loaders, speed draws), and drop
        the per-cohort memo caches."""
        pids = np.asarray(pids, np.int64)
        self._cohort_pids = pids
        self.state = jax.tree.map(jnp.asarray,
                                  self.store.gather(self.state, pids))
        if "edge_assign" in self.state:
            self.state["edge_assign"] = jnp.asarray(
                pids % self.num_edges, jnp.int32)
        self._cohort_cursors = self.store.cursors(pids)
        self.c3_weights = self.store.c3_weights(pids)
        self.loaders = [self._loader_for(int(p)) for p in pids]
        self.eval_loaders = [self._eval_loader_for(int(p)) for p in pids]
        self.sample_counts = np.array([l.num_samples()
                                       for l in self.loaders], float)
        if self.speed is not None:
            sp, bw, js = self.store.speed_draws(pids)
            self.speed.speed = np.asarray(sp)
            self.speed.bandwidth = np.asarray(bw)
            # pid-keyed jitter + trace series: both are attributes of
            # the CLIENT, so they must follow the pid into its slot
            self.speed.jitter_seeds = np.asarray(js, np.int64)
            self.speed.trace_pids = pids.copy()
            # the pricer's model draws (and measured state keying)
            # follow the cohort too — a no-op when model is the clock
            self.pricer.install_cohort(pids)
        self._comm_cache = None
        self._times_cache.clear()
        self._cohort_scattered = False

    def _pop_gather(self):
        """Draw and install the next cohort (no-op in fleet mode)."""
        if self.store is None:
            return
        if self._cohort_pids is not None and not self._cohort_scattered:
            self._pop_scatter()        # safety: never drop a live cohort
        self._install_cohort(self.sampler.sample())

    def _pop_scatter(self):
        """Write the live cohort's state back into the store
        (idempotent: a second call before the next gather is a no-op, so
        the checkpoint path inside _finish_round composes with the round
        loop's own scatter)."""
        if self.store is None or self._cohort_pids is None \
                or self._cohort_scattered:
            return
        sched = self.scheduler
        if sched.name == "async" and sched.started:
            cursors = sched.launches.copy()
        else:
            # every cohort member consumed batch index cursor_i this
            # round (barrier semantics: inactive/dropped clients still
            # advance, matching the fleet path's batch(r) stream)
            cursors = np.asarray(self._cohort_cursors) + 1
        self.store.scatter(self.state, self._cohort_pids,
                           cursors=cursors, c3_weights=self.c3_weights)
        self._cohort_scattered = True

    def _batch_index(self, i: int, r: int) -> int:
        """Client slot i's batch index for barrier round r: the fleet
        path streams by round; population mode streams by the pid's own
        persistent cursor."""
        if self._cohort_cursors is not None:
            return int(self._cohort_cursors[i])
        return r

    # ------------------------------------------------------------------
    def combined_weights(self) -> np.ndarray:
        """FedAvg weight |D_i|/|D| x C3 weight w_i (paper formula 2)."""
        p = self.pool.weights(self.sample_counts)
        w = p * self.c3_weights
        s = w.sum()
        return w / s if s > 0 else w

    def _train_batch(self, r: int):
        return stack_client_batches(
            [l.batch(self._batch_index(i, r))
             for i, l in enumerate(self.loaders)])

    def _train_batches(self, r: int, k: int):
        """(K, N, B, S) batch stack for the local-steps engine; inner step
        j of round r draws from the deterministic stream at r * K + j."""
        steps = [stack_client_batches(
                    [l.batch(self._batch_index(i, r) * k + j)
                     for i, l in enumerate(self.loaders)])
                 for j in range(k)]
        return {key: np.stack([s[key] for s in steps])
                for key in steps[0]}

    def _eval_batch(self, r: int):
        return stack_client_batches([l.batch(r) for l in self.eval_loaders])

    # ------------------------------------------------------------------
    # round-loop pieces (one jitted step + host-side policy around it)

    def _state_policy(self):
        """The co-controller's per-client (rank_cut, smashed_choice)
        arrays from round state, (None, None) under the static policy."""
        rank = self.state.get("rank_cut")
        choice = self.state.get("smashed_choice")
        return (None if rank is None else np.asarray(rank),
                None if choice is None else np.asarray(choice))

    def _state_frac(self) -> Optional[np.ndarray]:
        """The co-controller's per-client continuous topk keep fraction
        from round state, None under the static (bucket-only) policy."""
        frac = self.state.get("topk_frac")
        return None if frac is None else np.asarray(frac, np.float64)

    def _round_comm(self, cuts_np: np.ndarray, rank_np=None,
                    choice_np=None, frac_np=None
                    ) -> Dict[str, np.ndarray]:
        """Per-client comm bytes for a (cut, rank, compressor, frac)
        assignment — computed ONCE per round for the current state (and
        once per candidate when the co-controller prices moves),
        shared by the straggler model and the round record."""
        arch = self.arch
        names = (self.smashed_compress if choice_np is None
                 else [self.comp_buckets[int(k)] for k in choice_np])
        return comm.round_comm_bytes(
            self.model, cuts=cuts_np,
            batch_size=arch.train.batch_size,
            seq_len=arch.train.seq_len,
            smashed_compress=names,
            smashed_topk_frac=(self.smashed_topk_frac
                               if frac_np is None else frac_np),
            rank_cut=rank_np)

    @property
    def _flops_layer(self) -> float:
        arch = self.arch
        return 12 * arch.model.d_model ** 2 \
            * arch.train.batch_size * arch.train.seq_len

    def _phase_kwargs(self, r: int, cuts_np: np.ndarray,
                      cb: Dict[str, np.ndarray],
                      start_time: Optional[float] = None
                      ) -> Dict[str, Any]:
        """The SpeedModel.phase_times argument set for one assignment —
        shared verbatim by the charged clock, the pricer's predictions,
        and the telemetry baselines, so all three price the SAME bytes
        and layer split."""
        ea = (np.asarray(self.state["edge_assign"])
              if (self.num_edges > 1 and "edge_assign" in self.state)
              else None)
        kw = dict(
            cuts=cuts_np, flops_per_layer=self._flops_layer,
            smashed_bytes=cb["smashed_up"],
            smashed_down_bytes=cb["smashed_down"],
            adapter_bytes=cb["adapter_up"], round_idx=r,
            server_layers=self.model.num_flat_layers - cuts_np,
            edge_assign=ea, num_edges=self.num_edges,
            start_time=(self.sim_clock if start_time is None
                        else start_time))
        if self.sys.client_flops_per_s is not None:
            kw["ref_flops_per_s"] = float(self.sys.client_flops_per_s)
        return kw

    def _round_phases(self, r: int, cuts_np: np.ndarray,
                      cb: Dict[str, np.ndarray], *,
                      jitter: bool = True,
                      start_time: Optional[float] = None
                      ) -> Optional[np.ndarray]:
        """(5, N) per-phase durations of one local step (or None without
        a speed model): comm.py's per-channel byte split maps straight
        onto the wire phases (smashed -> f2/f4, adapter -> sync).
        jitter=True is the CHARGED clock (pricer.charge — jitter + trace
        factors); jitter=False is the controller's PREDICTION
        (pricer.predict — analytic / trace-window / measured-EWMA per
        SystemConfig.time_source).  start_time positions the launch on
        the simulated clock for trace-driven heterogeneity (None = now,
        i.e. self.sim_clock)."""
        if self.speed is None:
            return None
        kw = self._phase_kwargs(r, cuts_np, cb, start_time)
        if jitter:
            return self.pricer.charge(**kw)
        return self.pricer.predict(**kw)

    def _observe_phases(self, r: int, observed: np.ndarray, mask,
                        cb: Dict[str, np.ndarray], t0: float):
        """Feed one charged (5, N) phase matrix back to the telemetry
        consumers: the measured pricer's EWMA updates against the
        MODEL's stationary baseline (a mis-specified model is exactly
        what the ratios correct), while the trace recorder divides by
        the CLOCK's stationary baseline (recorded factors multiply the
        clock's own draws on replay).  mask selects the clients that
        actually ran; t0 is the launch instant on the simulated
        clock."""
        if not self._observing:
            return
        cuts_np = np.asarray(self.state["cuts"])
        kw = self._phase_kwargs(r, cuts_np, cb, t0)
        mask = np.asarray(mask, bool)
        observed = np.asarray(observed, np.float64)
        if self.pricer.source == "measured":
            self.pricer.observe(observed, mask,
                                self.pricer.model_baseline(**kw))
        if self.recorder is not None:
            self.recorder.observe(observed,
                                  self.pricer.clock_baseline(**kw),
                                  mask, t0)

    def predict_round_times(self, r: int, cuts, rank_cut=None,
                            comp_idx=None, topk_frac=None) -> np.ndarray:
        """(N,) predicted per-client one-step round time for a candidate
        (cut, rank-at-cut, compressor-index, topk-frac) assignment — the
        co-controller's objective.  Bytes come from the SAME
        comm.round_comm_bytes the simulated clock charges; durations
        come from the configured pricer's `predict` (jitter-free:
        analytic stationary model, trace-window factors, or
        measured-EWMA-corrected — SystemConfig.time_source).  With
        time_source='analytic'/'trace' and jitter_sigma == 0 prediction
        and simulation coincide exactly; under 'trace' the candidate is
        priced at the CURRENT trace window — the controller must answer
        "what would this assignment cost *now*", not under the
        stationary mean.  Serial phase sum; under overlap_comm, the
        steady-state per-step time of the double-buffered pipeline
        (makespan of K steps / K)."""
        cuts_np = np.asarray(cuts, int)
        cb = self._round_comm(
            cuts_np,
            None if rank_cut is None else np.asarray(rank_cut, int),
            None if comp_idx is None else np.asarray(comp_idx, int),
            (self._state_frac() if topk_frac is None
             else np.asarray(topk_frac, np.float64)))
        phases = self._round_phases(r, cuts_np, cb, jitter=False)
        if self.overlap_comm:
            k = max(2, self.scheduler.max_steps)
            steps = np.full(cuts_np.shape[0], k, np.int64)
            return straggler.pipelined_makespan(phases, steps) / k
        return straggler.serial_step_times(phases)

    def _trace_availability(self) -> Optional[np.ndarray]:
        """Barrier rounds under a trace: the availability mask at the
        round's start.  If NO pool-active client is available the round
        cannot form — the fleet idles, so the simulated clock advances
        to the earliest next-available instant (exactly what a real
        orchestrator does).  Past the trace's scan horizon we fall back
        to everyone-available rather than deadlocking the simulation."""
        if self.speed is None or self.speed.trace is None:
            return None
        act = np.asarray(self.pool.active, bool)
        avail = self.speed.available_mask(self.sim_clock)
        if act.any() and not (act & avail).any():
            t = min(self.speed.next_available(int(i), self.sim_clock)
                    for i in np.flatnonzero(act))
            if t > self.sim_clock:
                self.sim_clock = float(t)
                avail = self.speed.available_mask(self.sim_clock)
            if not (act & avail).any():
                avail = np.ones_like(avail)
        return avail.astype(np.float64)

    def _plan_round(self, r: int):
        """One scheduler decision: (RoundPlan, comm-bytes dict)."""
        avail = self._trace_availability()   # may advance sim_clock
        cuts_np = np.asarray(self.state["cuts"])
        rank_np, choice_np = self._state_policy()
        cb = self._round_comm(cuts_np, rank_np, choice_np,
                              self._state_frac())
        phases = self._round_phases(r, cuts_np, cb)
        times = (None if phases is None
                 else straggler.serial_step_times(phases))
        plan = self.scheduler.plan(
            active=self.pool.active.astype(np.float64), times=times,
            phases=phases, round_idx=r, available=avail)
        return plan, cb

    def _round_record(self, r: int, metrics, plan: RoundPlan,
                      cb: Dict[str, np.ndarray]) -> Dict[str, Any]:
        # async ticks train a subset, so the training loss ("total") is
        # not comparable to a barrier round's fleet average; the engine's
        # "fleet_total" (whole-fleet weighted loss at the flush tick) is
        loss_key = "fleet_total" if plan.buffer_fill is not None \
            else "total"
        rec: Dict[str, Any] = {
            "round": r,
            "loss": float(metrics[loss_key]),
            "ce": np.asarray(metrics["ce"]),
            "accuracy": np.asarray(metrics["accuracy"]),
            "cuts": np.asarray(self.state["cuts"]).copy(),
            "active": plan.active.copy(),
        }
        if "rank_cut" in self.state:
            rec["rank_cut"] = np.asarray(self.state["rank_cut"]).copy()
        if "smashed_choice" in self.state:
            rec["smashed_choice"] = np.asarray(
                self.state["smashed_choice"]).copy()
        if "topk_frac" in self.state:
            rec["topk_frac"] = np.asarray(
                self.state["topk_frac"]).copy()
        if plan.times is not None:
            rec["round_time_sim"] = plan.times
            rec["sim_time"] = plan.sim_time
            rec["sim_clock"] = self.sim_clock
        if plan.phases is not None:
            # (5, N) per-phase durations — bench_fleet compares the
            # charged server ingest + adapter-sync time flat vs two-tier
            rec["phase_times"] = np.asarray(plan.phases).copy()
        # each local step is a full f2/f4 exchange, and a dropped/inactive
        # client (budget 0) transmits nothing; it still receives the b3
        # adapter broadcast but sends no b1 update.  With everyone active
        # at one step this reduces exactly to cb["total"].
        steps = plan.step_budgets.astype(np.float64)
        smashed = (cb["smashed_up"] + cb["smashed_down"]) * steps
        if plan.buffer_fill is not None:
            # async: only the buffered clients upload b1 and receive the
            # b3 re-broadcast at this aggregation; in-flight clients
            # exchange nothing at the boundary
            rec["comm"] = (smashed + (cb["adapter_up"]
                                      + cb["adapter_down"]) * plan.active)
            rec["staleness"] = np.asarray(plan.staleness).copy()
            rec["buffer_fill"] = plan.buffer_fill
            rec["round_steps"] = plan.step_budgets.copy()
        else:
            rec["comm"] = (smashed + cb["adapter_up"] * plan.active
                           + cb["adapter_down"])
        rec["comm_smashed"] = smashed
        rec["smashed_ratio"] = cb["smashed_ratio"]
        if self.scheduler.max_steps > 1:
            rec["step_budgets"] = plan.step_budgets.copy()
        return rec

    def _adjust_c3(self, r: int, rec: Dict[str, Any], weights,
                   times: Optional[np.ndarray]):
        """C3: evaluate the global model per client, then adjust the
        allocation — cuts only (paper accuracy rule) or the full (cut,
        rank-at-cut, compressor) triple via the predicted-makespan
        co-controller (adaptive.co_adjust)."""
        with span("splitft.c3.batch"):
            eval_batch = self._eval_batch(r)
        with span("splitft.c3.dispatch"):
            e_loss, e_metrics = self.eval_step(
                self.base_params, self.state, eval_batch, weights)
        with span("splitft.wait.c3"):
            accs = np.asarray(e_metrics["accuracy"])
        with span("splitft.c3.rule"):
            self._c3_rule(r, rec, accs, e_metrics, times)

    def _c3_rule(self, r: int, rec: Dict[str, Any], accs: np.ndarray,
                 e_metrics, times: Optional[np.ndarray]):
        """C3's host side: aggregation weights from the evaluation, then
        the controller's new allocation written back to round state."""
        rec["eval_ce"] = np.asarray(e_metrics["ce"])
        rec["eval_accuracy"] = accs
        self.c3_weights = adaptive.update_weights(
            accs, self.arch.split.gamma)
        active = self.pool.active.astype(np.float64)
        if self.controller == "co":
            rank_np, choice_np = self._state_policy()
            frac_np = self._state_frac()
            if frac_np is None:
                new_cuts, new_rank, new_comp, pred = adaptive.co_adjust(
                    np.asarray(self.state["cuts"]), rank_np, choice_np,
                    accs, self.arch.split, self.model.num_flat_layers,
                    rank_buckets=self.rank_buckets,
                    num_compressors=len(self.comp_buckets),
                    price=lambda c, rk, ci: self.predict_round_times(
                        r + 1, c, rk, ci),
                    active=active, dead_band=self.acc_dead_band,
                    min_gain=self.min_gain, round_times=times)
            else:
                new_cuts, new_rank, new_comp, new_frac, pred = \
                    adaptive.co_adjust(
                        np.asarray(self.state["cuts"]), rank_np,
                        choice_np, accs, self.arch.split,
                        self.model.num_flat_layers,
                        rank_buckets=self.rank_buckets,
                        num_compressors=len(self.comp_buckets),
                        price=lambda c, rk, ci, fr:
                            self.predict_round_times(r + 1, c, rk, ci,
                                                     topk_frac=fr),
                        active=active, dead_band=self.acc_dead_band,
                        min_gain=self.min_gain, round_times=times,
                        topk_frac=frac_np)
                self.state["topk_frac"] = jnp.asarray(new_frac,
                                                      jnp.float32)
            self.state["cuts"] = jnp.asarray(new_cuts, jnp.int32)
            self.state["rank_cut"] = jnp.asarray(new_rank, jnp.int32)
            self.state["smashed_choice"] = jnp.asarray(new_comp,
                                                       jnp.int32)
            rec["predicted_time"] = pred
        else:
            new_cuts = adaptive.adjust_cuts(
                np.asarray(self.state["cuts"]), accs, self.arch.split,
                self.model.num_flat_layers, round_times=times,
                active=active)
            self.state["cuts"] = jnp.asarray(new_cuts, jnp.int32)
        rec["weights"] = self.c3_weights.copy()

    def _finish_round(self, r: int, rec: Dict[str, Any], log_every: int,
                      callback: Optional[Callable]):
        """Round epilogue shared by the barrier and async host loops:
        C3 adjustment, history, callback, checkpoint cadence, logging."""
        if self._adaptive and (r + 1) % self.sys.adjust_every == 0:
            with span("splitft.c3", round=r):
                weights = jnp.asarray(self.combined_weights(), jnp.float32)
                self._adjust_c3(r, rec, weights, rec.get("round_time_sim"))
        self.history.append(rec)
        if callback:
            callback(rec)
        if self.ckpt and self.sys.checkpoint_every and \
                (r + 1) % self.sys.checkpoint_every == 0:
            with span("splitft.round.checkpoint", round=r):
                self.save(r + 1)
        if log_every and (r + 1) % log_every == 0:
            print(f"[round {r + 1}] loss={rec['loss']:.4f} "
                  f"acc={rec['accuracy'].mean():.4f} "
                  f"cuts={rec['cuts'].tolist()}")

    # ------------------------------------------------------------------
    def run(self, num_rounds: int, *, log_every: int = 10,
            callback: Optional[Callable] = None) -> List[Dict[str, Any]]:
        if self.scheduler.name == "async":
            hist = self._run_async(num_rounds, log_every=log_every,
                                   callback=callback)
        else:
            hist = self._run_barrier(num_rounds, log_every=log_every,
                                     callback=callback)
        if self.recorder is not None:
            # cumulative: a second run() re-dumps the extended recording
            self.recorder.dump(self.sys.record_trace)
        return hist

    def _run_barrier(self, num_rounds: int, *, log_every: int = 10,
                     callback: Optional[Callable] = None
                     ) -> List[Dict[str, Any]]:
        """One plan -> one engine call -> one record per round."""
        arch = self.arch
        lr_c = jnp.float32(arch.train.lr_client)
        lr_s = jnp.float32(arch.train.lr_server)
        k = self.scheduler.max_steps
        start = int(self.state["round"])
        for r in range(start, start + num_rounds):
            with span("splitft.round", round=r):
                self._barrier_round(r, k, lr_c, lr_s, log_every, callback)
        return self.history

    def _barrier_round(self, r: int, k: int, lr_c, lr_s, log_every: int,
                       callback: Optional[Callable]):
        """One barrier round, one span per phase (runtime.spans); the
        host's waits on the device are `splitft.wait.*` spans of their
        own."""
        if self.store is not None:
            with span("splitft.round.gather"):
                self._pop_gather()     # population mode: next cohort in
        with span("splitft.round.plan"):
            plan, cb = self._plan_round(r)
        t0 = self.sim_clock            # the round's launch instant
        with span("splitft.round.batch"):
            batch = (self._train_batch(r) if k == 1
                     else self._train_batches(r, k))
        with span("splitft.round.dispatch"):
            weights = jnp.asarray(self.combined_weights(), jnp.float32)
            if "step_budgets" in self.state:
                self.state["step_budgets"] = jnp.asarray(
                    plan.step_budgets, jnp.int32)
            active_j = jnp.asarray(plan.active, jnp.float32)
            self.state, metrics = self.train_step(
                self.base_params, self.state, batch, weights, active_j,
                lr_c, lr_s)
        self.sim_clock += plan.sim_time
        if plan.phases is not None:
            # telemetry feedback: the plan's charged phase matrix is
            # exactly what the clock just billed this round
            self._observe_phases(r, plan.phases, plan.active, cb, t0)

        with span("splitft.round.record"):
            with span("splitft.wait.round"):
                jax.block_until_ready(metrics)
            rec = self._round_record(r, metrics, plan, cb)
        self._finish_round(r, rec, log_every, callback)
        if self.store is not None:
            with span("splitft.round.scatter"):
                self._pop_scatter()    # cohort rows back to their slots

    # ------------------------------------------------------------------
    # async (FedBuff) host loop: event-queue simulation, no barrier

    def _cached_comm(self, cuts_np: np.ndarray) -> Dict[str, np.ndarray]:
        """_round_comm memo for the event loop: cuts change only in the
        per-aggregation C3 epilogue, but ticks fire many times per
        round."""
        rank_np, choice_np = self._state_policy()
        frac_np = self._state_frac()
        key = (cuts_np.tobytes(),
               None if rank_np is None else rank_np.tobytes(),
               None if choice_np is None else choice_np.tobytes(),
               None if frac_np is None else frac_np.tobytes())
        if self._comm_cache is None or self._comm_cache[0] != key:
            self._comm_cache = (key, self._round_comm(
                cuts_np, rank_np, choice_np, frac_np))
        return self._comm_cache[1]

    def _cached_phases(self, round_idx: int, cuts_np: np.ndarray,
                       cb: Dict[str, np.ndarray],
                       start_time: Optional[float] = None) -> np.ndarray:
        """_round_phases memo keyed by (launch index, trace window, cuts
        + controller policy): relaunching clients at the same launch
        share one full-fleet draw instead of re-drawing the whole
        lognormal vector per client.  Traces are piecewise-constant per
        window, so keying by `trace.window(start)` keeps the memo exact
        under a non-stationary clock (and collapses to one window —
        key None/0 — without a trace)."""
        rank_np, choice_np = self._state_policy()
        frac_np = self._state_frac()
        start = self.sim_clock if start_time is None else start_time
        trace = None if self.speed is None else self.speed.trace
        win = None if trace is None else trace.window(start)
        key = (round_idx, win, cuts_np.tobytes(),
               None if rank_np is None else rank_np.tobytes(),
               None if choice_np is None else choice_np.tobytes(),
               None if frac_np is None else frac_np.tobytes())
        p = self._times_cache.get(key)
        if p is None:
            if len(self._times_cache) > 64:   # launches only grow; old
                self._times_cache.clear()     # entries never recur
            p = self._round_phases(round_idx, cuts_np, cb,
                                   start_time=start)
            self._times_cache[key] = p
        return p

    def _serial_time(self, i: int, launch: int, cuts_np: np.ndarray,
                     cb: Dict[str, np.ndarray],
                     start_time: Optional[float] = None) -> float:
        """Client i's serial one-step time at a launch index (priced at
        `start_time` on the simulated clock; None = now)."""
        ph = self._cached_phases(launch, cuts_np, cb, start_time)
        return float(straggler.serial_step_times(ph)[i])

    # -- overlap pipeline (double-buffered phase events) ----------------

    def _overlap_try_compute(self, i: int, cuts_np: np.ndarray,
                             cb: Dict[str, np.ndarray]):
        """Schedule client i's next `client_compute` phase if the
        pipeline allows: no compute in flight, and step k-2 fully done
        (double buffer, one outstanding transfer per direction, so the
        client trains at staleness <= 1)."""
        sched = self.scheduler
        if not self.pool.active[i]:
            return
        if int(sched.csched[i]) != int(sched.cfin[i]):
            return                 # a compute phase is already in flight
        k = int(sched.csched[i])
        if int(sched.launches[i]) < k - 1:
            return                 # step k-2 has not fully completed
        # trace availability defers the launch to the client's next
        # available instant (no trace / constant trace: t0 == now, and
        # max(t, t) == t keeps the clock bitwise)
        t0 = max(sched.queue.now, self.speed.next_available(
            i, sched.queue.now))
        ph = self._cached_phases(k, cuts_np, cb, t0)
        sched.queue.push((i, "client_compute", k), t0 + float(ph[0, i]))
        sched.csched[i] += 1

    def _overlap_advance(self, i: int, phase: str, k: int, t_now: float,
                         cuts_np: np.ndarray, cb: Dict[str, np.ndarray]):
        """One non-final phase of step k finished: hand the step to the
        next resource in the pipeline.  Every per-client stage — the
        wire channels (f2 up, f4 down, adapter sync) AND the server
        lane — serializes via the scheduler's busy-until times, so steps
        complete strictly in launch order even when per-launch durations
        vary (jitter, moved cuts): the engine may therefore index
        batches by `launches[i]`.  Durations are drawn at hand-off, so a
        C3-moved cut takes effect at the client's next scheduled
        phase."""
        sched = self.scheduler
        q = sched.queue
        ph = self._cached_phases(k, cuts_np, cb, t_now)
        if phase == "client_compute":
            sched.cfin[i] += 1
            start = max(t_now, float(sched.eu[i]))
            sched.eu[i] = start + float(ph[1, i])
            q.push((i, "f2_uplink", k), sched.eu[i])
            # the compute unit is free: step k+1 may start while step
            # k's transfers are still in flight — the tentpole overlap
            self._overlap_try_compute(i, cuts_np, cb)
        elif phase == "f2_uplink":
            start = max(t_now, float(sched.es[i]))
            sched.es[i] = start + float(ph[2, i])
            q.push((i, "server_compute", k), sched.es[i])
        elif phase == "server_compute":
            start = max(t_now, float(sched.ed[i]))
            sched.ed[i] = start + float(ph[3, i])
            q.push((i, "f4_downlink", k), sched.ed[i])
        elif phase == "f4_downlink":
            start = max(t_now, float(sched.ea[i]))
            sched.ea[i] = start + float(ph[4, i])
            q.push((i, "adapter_sync", k), sched.ea[i])
        else:
            raise ValueError(f"unknown pipeline phase {phase!r}")

    def _async_launch(self, i: int, cuts_np: np.ndarray,
                      cb: Dict[str, np.ndarray]):
        """Put client i's next local step in flight at the current clock:
        one whole-step event (serial) or its first pipeline phase
        (overlap)."""
        sched = self.scheduler
        if sched.overlap:
            self._overlap_try_compute(i, cuts_np, cb)
        else:
            launch = int(sched.launches[i])
            # trace availability: an unavailable client launches at its
            # next available instant instead of now (max(t, t) == t
            # keeps the no-trace / constant-trace clock bitwise)
            t0 = max(sched.queue.now, self.speed.next_available(
                i, sched.queue.now))
            t_i = self._serial_time(i, launch, cuts_np, cb, t0)
            sched.queue.push((i, scheduler_lib.PHASE_STEP, launch),
                             t0 + t_i)

    def _async_ensure_started(self):
        """Launch every ACTIVE client's first local round onto the event
        queue (no-op when the simulation is already in flight, e.g. after
        a checkpoint restore repopulated it)."""
        sched = self.scheduler
        if sched.started:
            return
        n = self.pool.active.shape[0]
        sched.start(n, clock=self.sim_clock)
        if self._cohort_cursors is not None:
            # population mode: each slot resumes its pid's persistent
            # batch stream — launch counters ARE the cursors
            cur = np.asarray(self._cohort_cursors, np.int64)
            sched.launches = cur.copy()
            sched.csched = cur.copy()
            sched.cfin = cur.copy()
        cuts_np = np.asarray(self.state["cuts"])
        cb = self._cached_comm(cuts_np)
        # baseline for the flush record before anyone has completed
        sched.last_times = straggler.serial_step_times(
            self._cached_phases(0, cuts_np, cb)).copy()
        for i in range(n):
            if self.pool.active[i]:
                self._async_launch(i, cuts_np, cb)

    def _async_sync_membership(self):
        """Reconcile the event simulation with elastic pool membership:
        a leaver's in-flight events are dropped (it must never tick
        again), and an active client with nothing in flight — a fresh
        join or a rejoin after a mid-flight leave — enters at the CURRENT
        clock with its next batch index."""
        sched = self.scheduler
        active = self.pool.active
        cuts_np = np.asarray(self.state["cuts"])
        cb = self._cached_comm(cuts_np)
        for i in range(active.shape[0]):
            if not active[i] and sched.queue.discard_client(i):
                sched.reset_client(i)
        # a departed client cannot honor a deferred relaunch either
        sched.pending_relaunch = [i for i in sched.pending_relaunch
                                  if active[i]]
        in_flight = sched.queue.clients()
        for i in range(active.shape[0]):
            if active[i] and i not in in_flight \
                    and i not in sched.pending_relaunch:
                self._async_launch(i, cuts_np, cb)

    def _async_tick(self, r: int, lr_c, lr_s) -> Optional[Dict[str, Any]]:
        """Advance the simulation by one completion tick: pop the
        earliest-finishing phase events, pipeline non-final phases
        onward, run the step-completing clients through the engine
        (pushing their updates into the buffer), and keep their pipelines
        fed.  Returns the round record when this tick flushed the buffer
        (closing round r); None for intermediate ticks (no step finished,
        or the buffer is still filling)."""
        sched = self.scheduler
        cuts_np = np.asarray(self.state["cuts"])
        cb = self._cached_comm(cuts_np)
        t_now, keys = sched.queue.pop_next()
        self.sim_clock = sched.queue.now

        finishers: List[int] = []
        for key in keys:
            if isinstance(key, tuple):
                i, phase, k = int(key[0]), key[1], int(key[2])
            else:   # whole-step key from a pre-phase checkpoint
                i, phase = int(key), scheduler_lib.PHASE_STEP
                k = int(sched.launches[i])
            if not self.pool.active[i]:
                # elastic leave mid-flight: the event dies with the
                # membership — no engine contribution, no relaunch
                sched.queue.discard_client(i)
                sched.reset_client(i)
                continue
            if phase in (scheduler_lib.PHASE_STEP,
                         scheduler_lib.PHASE_FINAL):
                finishers.append(i)
            else:
                self._overlap_advance(i, phase, k, t_now, cuts_np, cb)
        if not finishers:
            return None            # pipeline hand-offs only

        act = np.zeros(len(self.loaders), np.float64)
        act[finishers] = 1.0
        # client i's tick consumes its own launch-indexed batch stream
        # (launch L <-> the batch a barrier scheduler would use at round
        # L), so constant speeds reproduce the sync data order exactly
        batch = stack_client_batches(
            [l.batch(int(sched.launches[i]))
             for i, l in enumerate(self.loaders)])
        weights = jnp.asarray(self.combined_weights(), jnp.float32)
        self.state, metrics = self.train_step(
            self.base_params, self.state, batch, weights,
            jnp.asarray(act, jnp.float32), lr_c, lr_s)

        sched.round_steps[act > 0] += 1
        aggregated = bool(np.asarray(metrics["aggregated"]))
        for i in finishers:
            # the flush record reports the serial step time each client
            # actually experienced at ITS launch index — not a fresh
            # full-fleet draw at the aggregation-round index
            launch = int(sched.launches[i])
            ph = self._cached_phases(launch, cuts_np, cb, t_now)
            sched.last_times[i] = float(
                straggler.serial_step_times(ph)[i])
            if self._observing:
                # telemetry feedback: this finisher's charged phase
                # column at its own launch index
                m = np.zeros(ph.shape[1], bool)
                m[i] = True
                self._observe_phases(launch, ph, m, cb, t_now)
            sched.launches[i] += 1
        if aggregated:
            # this tick's finishers just received the new global model;
            # their next step launches after the round epilogue (C3 may
            # move cuts, changing its duration) — _async_relaunch
            sched.pending_relaunch = list(finishers)
        else:
            for i in finishers:
                self._async_launch(i, cuts_np, cb)

        if not aggregated:
            return None
        plan = RoundPlan(
            active=np.asarray(metrics["buffer_mask"], np.float64).copy(),
            step_budgets=sched.round_steps.copy(),
            sim_time=t_now - sched.last_agg_clock,
            times=sched.last_times.copy(),
            staleness=np.asarray(metrics["staleness"], np.float64),
            buffer_fill=float(np.asarray(metrics["buffer_fill"])))
        rec = self._round_record(r, metrics, plan, cb)
        sched.round_steps[:] = 0
        sched.last_agg_clock = t_now
        return rec

    def _async_relaunch(self):
        """Launch the aggregation tick's finishers' next steps with
        post-epilogue cuts (their durations track the layer count they
        now hold).  Under overlap this is a no-op for any finisher whose
        next compute already self-scheduled mid-pipeline."""
        sched = self.scheduler
        if not sched.pending_relaunch:
            return
        cuts_np = np.asarray(self.state["cuts"])
        cb = self._cached_comm(cuts_np)
        for i in sched.pending_relaunch:
            if self.pool.active[i]:    # may have left in the epilogue
                self._async_launch(i, cuts_np, cb)
        sched.pending_relaunch = []

    def _pop_async_boundary(self):
        """Population mode's aggregation-boundary hook: scatter the live
        cohort, draw the next one, and — only if membership actually
        changed — restart the event pipeline for the new cohort at the
        current clock.  An unchanged cohort (P == C in particular) keeps
        its in-flight events, reproducing the fleet event stream."""
        if self.store is None:
            return
        self._pop_scatter()
        old = self._cohort_pids
        pids = self.sampler.sample()
        if old is not None and np.array_equal(pids, old):
            self._cohort_pids = pids
            self._cohort_scattered = False
            return
        self._install_cohort(pids)
        sched = self.scheduler
        n = self.pool.active.shape[0]
        sched.start(n, clock=self.sim_clock)   # drops old in-flight work
        cur = np.asarray(self._cohort_cursors, np.int64)
        sched.launches = cur.copy()
        sched.csched = cur.copy()
        sched.cfin = cur.copy()
        sched.last_agg_clock = self.sim_clock
        cuts_np = np.asarray(self.state["cuts"])
        cb = self._cached_comm(cuts_np)
        sched.last_times = np.array(
            [self._serial_time(i, int(sched.launches[i]), cuts_np, cb)
             for i in range(n)])
        for i in range(n):
            if self.pool.active[i]:
                self._async_launch(i, cuts_np, cb)

    def _run_async(self, num_rounds: int, *, log_every: int = 10,
                   callback: Optional[Callable] = None
                   ) -> List[Dict[str, Any]]:
        """Event-queue host loop: tick until the buffer flushes, emit one
        record per aggregation (one round == one aggregation)."""
        arch = self.arch
        lr_c = jnp.float32(arch.train.lr_client)
        lr_s = jnp.float32(arch.train.lr_server)
        if self.store is not None and self._cohort_pids is None:
            self._pop_gather()         # first cohort before the pipeline
        self._async_ensure_started()
        if self.scheduler.last_times is None:
            # pre-phase checkpoint restore: seed real per-launch serial
            # times so the first flush (and C3's straggler detection)
            # never sees fake zeros
            cuts_np = np.asarray(self.state["cuts"])
            cb = self._cached_comm(cuts_np)
            self.scheduler.last_times = np.array(
                [self._serial_time(i, int(self.scheduler.launches[i]),
                                   cuts_np, cb)
                 for i in range(self.pool.active.shape[0])])
        self._async_relaunch()         # resume from a mid-epilogue save
        start = int(self.state["round"])
        for r in range(start, start + num_rounds):
            # a shrunken fleet (elastic leave) can strand the buffer below
            # its flush threshold: fail loudly instead of ticking forever
            n_active = int(self.pool.active.sum())
            if n_active < self.scheduler.buffer_size:
                raise RuntimeError(
                    f"async buffer_size={self.scheduler.buffer_size} can "
                    f"never fill: only {n_active} clients are active in "
                    "the pool; rejoin clients or rebuild the system with "
                    "a smaller buffer_size")
            self._async_sync_membership()
            rec = None
            while rec is None:
                rec = self._async_tick(r, lr_c, lr_s)
            self._finish_round(r, rec, log_every, callback)
            self._pop_async_boundary()
            self._async_relaunch()
        return self.history

    # ------------------------------------------------------------------
    def evaluate(self, *, num_batches: int = 4) -> Dict[str, float]:
        """Global-model perplexity/accuracy on held-out data."""
        weights = jnp.asarray(self.combined_weights(), jnp.float32)
        ces, accs = [], []
        for b in range(num_batches):
            loss, metrics = self.eval_step(
                self.base_params, self.state, self._eval_batch(10_000 + b),
                weights)
            ces.append(np.asarray(metrics["ce"]).mean())
            accs.append(np.asarray(metrics["accuracy"]).mean())
        ce = float(np.mean(ces))
        return {"ce": ce, "perplexity": float(np.exp(ce)),
                "accuracy": float(np.mean(accs))}

    # ------------------------------------------------------------------
    def save(self, step: int):
        assert self.ckpt is not None
        meta = {
            "round": int(self.state["round"]),
            "c3_weights": self.c3_weights.tolist(),
            "active": self.pool.active.tolist(),
            "seed": self.seed,
            "sim_clock": self.sim_clock,
            "scheduler": self.scheduler.name,
            # template signature: lets restore() explain a leaf-count
            # mismatch instead of silently restarting from round 0
            "state_keys": sorted(self.state.keys()),
        }
        if self.scheduler.name == "async" and self.store is None:
            # host-side simulation state (event queue, launch counters);
            # the buffer/version arrays are in self.state already.  Saving
            # mid-buffer is legal: restore resumes the tick stream exactly.
            # (Population mode instead restarts the pipeline from the
            # restored cohort cursors — launch counters live in the
            # store's slots.)
            meta["async_sim"] = self.scheduler.state_dict()
        if self.speed is not None and self.speed.trace is not None:
            # trace cursor (e.g. the Markov availability chain's per-pid
            # position): every trace value is a pure function of (pid,
            # window), so the cursor is only a cache — but restoring it
            # spares the resumed run an O(t/step) replay on first query
            meta["trace"] = self.speed.trace.state_dict()
        if self.pricer is not None:
            tm = self.pricer.state_dict()
            if tm:
                # measured-EWMA telemetry (pid-keyed ratios): resume ==
                # straight run, bitwise
                meta["timemodel"] = tm
        if self.store is not None:
            # cohort rows back to their slots first so the slot map is
            # the single source of per-pid truth in the checkpoint
            self._pop_scatter()
            meta["population"] = self.store.population
            meta["cohort"] = self.store.cohort
            # the sampler's RNG round-trips so a restored run resumes
            # the identical cohort sequence (satellite b)
            meta["cohort_sampler"] = self.sampler.state_dict()
            tree = {"engine": self.state, "pop": self.store.state_tree()}
        else:
            tree = self.state
        self.ckpt.save(step, tree, metadata=meta)

    def restore(self) -> bool:
        assert self.ckpt is not None
        like = (self.state if self.store is None
                else {"engine": self.state, "pop": self.store.state_tree()})
        got = self.ckpt.restore_latest(like)
        if got is None:
            # distinguish "no checkpoints" from "checkpoints exist but the
            # state template changed" — resuming with a different
            # scheduler or smashed/EF config makes step_budgets /
            # smashed_ef leaves appear or vanish, which must not silently
            # restart from round 0
            steps = self.ckpt.steps()
            if steps:
                meta = self.ckpt.metadata(steps[-1]) or {}
                saved_pop = meta.get("population")
                if saved_pop is not None and saved_pop != self.population:
                    raise ValueError(
                        f"checkpoint step {steps[-1]} was written with "
                        f"population={saved_pop} but this run has "
                        f"population={self.population or 'fleet mode'}; "
                        "per-pid slot state is not transferable — "
                        "resume with the original --population or use "
                        "a fresh checkpoint dir")
                saved = meta.get("scheduler")
                if saved and saved != self.scheduler.name:
                    raise ValueError(
                        f"checkpoint step {steps[-1]} was written with "
                        f"scheduler={saved!r} but this run uses "
                        f"{self.scheduler.name!r}; resume with the same "
                        "scheduler or point at a fresh checkpoint dir")
                saved_keys = meta.get("state_keys")
                now_keys = sorted(self.state.keys())
                if saved_keys and saved_keys != now_keys:
                    raise ValueError(
                        f"checkpoint step {steps[-1]} state template "
                        f"{saved_keys} does not match this run's "
                        f"{now_keys} (scheduler / smashed-EF / adapter-"
                        "compression config changed); resume with the "
                        "original config or use a fresh checkpoint dir")
            return False
        tree, meta, step = got
        if self.store is not None:
            # loud mismatch checks AFTER a successful load so they are
            # not swallowed by restore_latest's corruption fallback
            if meta.get("population") is not None \
                    and int(meta["population"]) != self.population:
                raise ValueError(
                    f"checkpoint step {step} holds population="
                    f"{meta['population']} but this run has "
                    f"population={self.population}; pid state is not "
                    "transferable — resume with the original "
                    "--population or use a fresh checkpoint dir")
            if "cohort_sampler" not in meta:
                raise ValueError(
                    f"checkpoint step {step} was written in fleet mode "
                    "(no cohort sampler state) but this run sets "
                    f"population={self.population}; resume without "
                    "--population or use a fresh checkpoint dir")
            self.sampler.load_state_dict(meta["cohort_sampler"])
            self.state = jax.tree.map(jnp.asarray, tree["engine"])
            self.store.load_state_tree(tree["pop"])
            self._cohort_pids = None
            self._cohort_cursors = None
            self._cohort_scattered = True
        else:
            self.state = jax.tree.map(jnp.asarray, tree)
        self.c3_weights = np.asarray(meta.get("c3_weights",
                                              self.c3_weights))
        if "active" in meta:
            self.pool.active = np.asarray(meta["active"], bool)
        self.sim_clock = float(meta.get("sim_clock", 0.0))
        if self.scheduler.name == "async" and self.store is None:
            self.scheduler.load_state_dict(meta.get("async_sim") or {})
        if self.speed is not None and self.speed.trace is not None \
                and meta.get("trace") is not None:
            self.speed.trace.load_state_dict(meta["trace"])
        if self.pricer is not None and meta.get("timemodel") is not None:
            self.pricer.load_state_dict(meta["timemodel"])
        return True

    # ------------------------------------------------------------------
    def serve_model(self):
        """(base_params, global adapters) for the serving path."""
        weights = jnp.asarray(self.combined_weights(), jnp.float32)
        eff = serve_adapters(self.model, self.state["client_adapters"],
                             self.state["server_adapters"],
                             self.state["cuts"], weights,
                             rank_cut=self.state.get("rank_cut"))
        return self.base_params, eff
