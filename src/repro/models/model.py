"""Model factory: composes block groups into the 13 supported architectures.

A model is a list of *block groups* (homogeneous stacks of layers with
parameters stacked along a leading layer axis) plus embedding/head.  The
flat layer index space 0..M_total-1 is what the SplitFT cut layer indexes;
`flat_runs()` exposes the execution order as (group, lo, hi) runs so both
scanned (deep homogeneous) and unrolled (heterogeneous / per-layer-window)
stacks execute correctly.

Entry points (all pure functions of pytrees):

  init_params(key, dtype)                        -> params
  loss(params, adapters, batch, ...)             -> (loss, metrics)
  prefill(params, adapters, batch, cache, ...)   -> (logits_last, cache)
  decode_step(params, adapters, tokens, cache,..)-> (logits, cache)
  init_cache(lead, max_len, dtype)               -> cache pytree

Adapters are optional everywhere (None = no LoRA).  Their tree layout is
{group: {target: {"A": (Lg,[N,]din,r), "B": (Lg,[N,]r,dout),
 "scale": (Lg[,N])}}} — built by repro.core.lora from adapter_spec().
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig, ModelConfig
from repro.models import common, ssm, transformer
from repro.models.common import NO_SHARDING, ShardingPolicy, apply_norm

Params = Dict[str, Any]


def _ce_sums(logits, labels, mask, keep: int):
    """(nll_sum, hit_sum, count) reduced over all but the first `keep` dims.

    Written vocab-sharding-safe: no one-hot materialization; max/lse/select
    reduce over the vocab axis and fuse under XLA SPMD."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), -1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    correct = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), -1)
    nll = (lse - correct) * mask
    hits = (jnp.argmax(lf, -1) == labels) * mask
    axes = tuple(range(keep, nll.ndim))
    return (jnp.sum(nll, axes), jnp.sum(hits, axes),
            jnp.sum(mask, axes))


# ---------------------------------------------------------------------------
# Group structure


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    name: str                      # params/adapters key
    kind: str                      # attn_mlp | attn_moe | ssm | attn
    layer_ids: Tuple[int, ...]     # flat layer ids, ascending
    causal: bool = True
    cross: bool = False            # decoder cross-attention (whisper)
    scan: bool = True              # lax.scan vs unrolled python loop
    windows: Tuple[int, ...] = ()  # per-layer attention window (0=global)

    @property
    def size(self) -> int:
        return len(self.layer_ids)

    def window_of(self, local_idx: int) -> int:
        return self.windows[local_idx] if self.windows else 0


def build_groups(cfg: ModelConfig) -> Tuple[GroupSpec, ...]:
    L = cfg.num_layers
    if cfg.family in ("dense", "vlm", "moe"):
        kind = "attn_moe" if cfg.family == "moe" else "attn_mlp"
        windows: Tuple[int, ...] = ()
        scan = True
        if cfg.local_window:
            if cfg.local_every_other:
                windows = tuple(cfg.local_window if i % 2 else 0
                                for i in range(L))
                scan = False       # per-layer window is static structure
            else:
                windows = (cfg.local_window,) * L
        return (GroupSpec("dec", kind, tuple(range(L)), scan=scan,
                          windows=windows),)
    if cfg.family == "ssm":
        return (GroupSpec("ssm", "ssm", tuple(range(L))),)
    if cfg.family == "hybrid":
        attn_ids = tuple(sorted(cfg.attn_layer_indices))
        ssm_ids = tuple(i for i in range(L) if i not in attn_ids)
        return (GroupSpec("ssm", "ssm", ssm_ids),
                GroupSpec("attn", "attn_mlp", attn_ids, scan=False))
    if cfg.family == "audio":
        le = cfg.num_encoder_layers
        return (GroupSpec("enc", "attn_mlp", tuple(range(le)), causal=False),
                GroupSpec("dec", "attn_mlp", tuple(range(le, le + L)),
                          cross=True))
    raise ValueError(cfg.family)


def flat_runs(groups: Sequence[GroupSpec]) -> List[Tuple[str, int, int]]:
    """Execution plan: maximal contiguous runs [(group_name, lo, hi)] in
    flat-layer order."""
    owner = {}
    for g in groups:
        for j, fid in enumerate(g.layer_ids):
            owner[fid] = (g.name, j)
    runs: List[Tuple[str, int, int]] = []
    for fid in sorted(owner):
        name, j = owner[fid]
        if runs and runs[-1][0] == name and runs[-1][2] == j:
            runs[-1] = (name, runs[-1][1], j + 1)
        else:
            runs.append((name, j, j + 1))
    return [tuple(r) for r in runs]


# ---------------------------------------------------------------------------
# The Model


class Model:
    def __init__(self, arch: ArchConfig, *, unroll: bool = False):
        """unroll=True replaces lax.scan over layers with a python loop:
        identical math, straight-line HLO, so cost_analysis() counts
        every layer (XLA reports while-loop bodies once, not x
        trip-count)."""
        self.arch = arch
        self.cfg = arch.model
        groups = build_groups(self.cfg)
        if unroll:
            groups = tuple(dataclasses.replace(g, scan=False)
                           for g in groups)
        self.groups: Tuple[GroupSpec, ...] = groups
        self.runs = flat_runs(self.groups)
        self.group_by_name = {g.name: g for g in self.groups}
        self.num_flat_layers = sum(g.size for g in self.groups)

    # -- parameter init ------------------------------------------------------

    def init_params(self, key, dtype=jnp.float32) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 4 + len(self.groups))
        p: Params = {"embed": {"tok": common.embed_init(
            keys[0], cfg.vocab_size, cfg.d_model, dtype)}}
        if cfg.learned_pos:
            p["embed"]["pos"] = common.embed_init(
                keys[1], cfg.max_position_embeddings, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            p["embed"]["head"] = common.dense_init(
                keys[2], cfg.d_model, cfg.vocab_size, dtype)
        p["final_norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
        if cfg.norm == "layernorm":
            p["final_norm"]["bias"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.family == "audio":
            p["embed"]["enc_pos"] = common.embed_init(
                keys[3], cfg.encoder_seq_len, cfg.d_model, dtype)
            p["enc_norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
            if cfg.norm == "layernorm":
                p["enc_norm"]["bias"] = jnp.zeros((cfg.d_model,), dtype)

        for i, g in enumerate(self.groups):
            gk = jax.random.split(keys[4 + i], 2)
            if g.kind == "ssm":
                p[g.name] = ssm.init_ssm(gk[0], cfg, g.size, dtype=dtype)
            else:
                p[g.name] = transformer.init_attention(
                    gk[0], cfg, g.size, cross=g.cross, dtype=dtype)
                if g.kind == "attn_moe":
                    p[g.name].update(transformer.init_moe(
                        gk[1], cfg, g.size, dtype=dtype))
                elif g.kind == "attn_mlp" and cfg.d_ff:
                    p[g.name].update(transformer.init_mlp(
                        gk[1], cfg, g.size, dtype=dtype))
        return p

    # -- adapter spec (consumed by repro.core.lora) ---------------------------

    def adapter_spec(self) -> Dict[str, Dict[str, Tuple[int, int]]]:
        """{group: {target: (d_in, d_out)}} for every LoRA-targetable
        projection present in this architecture, filtered by lora.targets."""
        cfg = self.cfg
        want = set(self.arch.lora.targets)
        h, kvh, hd, d = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                         cfg.d_model)
        spec: Dict[str, Dict[str, Tuple[int, int]]] = {}
        for g in self.groups:
            t: Dict[str, Tuple[int, int]] = {}
            if g.kind == "ssm":
                if "ssm_in" in want:
                    t["ssm_in"] = (d, ssm.in_proj_dim(cfg))
                if "ssm_out" in want:
                    t["ssm_out"] = (cfg.d_inner, d)
            else:
                if "q" in want:
                    t["q"] = (d, h * hd)
                if "k" in want:
                    t["k"] = (d, kvh * hd)
                if "v" in want:
                    t["v"] = (d, kvh * hd)
                if "o" in want:
                    t["o"] = (h * hd, d)
                if g.kind == "attn_mlp" and cfg.d_ff:
                    if "mlp_in" in want:
                        t["mlp_in"] = (d, cfg.d_ff)
                    if "mlp_out" in want:
                        t["mlp_out"] = (cfg.d_ff, d)
                if g.cross and "xq" in want:
                    t["xq"] = (d, h * hd)
                    t["xo"] = (h * hd, d)
            if t:
                spec[g.name] = t
        return spec

    # -- embedding / head ------------------------------------------------------

    def embed(self, params: Params, tokens, *, positions=None, prefix=None,
              policy: ShardingPolicy = NO_SHARDING):
        cfg = self.cfg
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        if prefix is not None:
            plen = prefix.shape[-2]
            x = jnp.concatenate(
                [prefix.astype(x.dtype), x[..., plen:, :]], axis=-2)
        if cfg.learned_pos:
            if positions is None:
                positions = jnp.arange(tokens.shape[-1])
            pos_tab = params["embed"]["pos"]
            positions = jnp.clip(positions, 0, pos_tab.shape[0] - 1)
            x = x + jnp.take(pos_tab, positions, axis=0).astype(x.dtype)
        return policy.act(x)

    def head(self, params: Params, x, *, policy: ShardingPolicy = NO_SHARDING):
        if self.cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", x, params["embed"]["tok"])
        else:
            logits = x @ params["embed"]["head"]
        return policy.logits(logits)

    # -- block execution -------------------------------------------------------

    def _rope(self, positions):
        if not self.cfg.use_rope:
            return None
        cos, sin = common.rope_angles(positions, self.cfg.head_dim,
                                      self.cfg.rope_theta)
        return (cos, sin)

    def _layer_body(self, g: GroupSpec, *, policy, mode, rope, memory,
                    window: int):
        cfg = self.cfg

        def body(x, p_l, ad_l, cache_l, mem_l):
            aux = jnp.float32(0.0)
            if g.kind == "ssm":
                out, new_cache = ssm.ssm_apply(
                    p_l, ad_l, x, cfg=cfg, policy=policy, mode=mode,
                    cache=cache_l)
                x = policy.act(x + out)
                return x, aux, new_cache, mem_l
            attn_out, new_cache, new_mem = transformer.attention_apply(
                p_l, ad_l, x, cfg=cfg, policy=policy, mode=mode,
                causal=g.causal, window=window, rope=rope,
                cache=cache_l, memory=memory, mem_cache=mem_l)
            x = policy.act(x + attn_out)
            if g.kind == "attn_moe":
                out, aux = transformer.moe_apply(p_l, ad_l, x, cfg=cfg,
                                                 policy=policy)
                x = policy.act(x + out)
            elif g.kind == "attn_mlp" and cfg.d_ff:
                x = policy.act(
                    x + transformer.mlp_apply(p_l, ad_l, x, cfg=cfg,
                                              policy=policy))
            return x, aux, new_cache, new_mem

        return body

    def _maybe_remat(self, fn, remat: str):
        if remat == "none":
            return fn
        if remat == "dots":
            pol = jax.checkpoint_policies.checkpoint_dots
            return jax.checkpoint(fn, policy=pol)
        return jax.checkpoint(fn)    # "full": save nothing

    def run_blocks(self, params: Params, adapters: Optional[Params], x, *,
                   policy: ShardingPolicy = NO_SHARDING, mode: str = "train",
                   remat: str = "none", cache: Optional[Params] = None,
                   memory=None, layer_lo: int = 0,
                   layer_hi: Optional[int] = None, boundary=None):
        """Run flat layers [layer_lo, layer_hi) over activations x.

        Returns (x, aux_total, new_cache, boundary_carry).  `cache` is the
        model-level cache pytree (or None); `memory` the encoder output for
        cross-attention groups.

        `boundary(x, flat_id) -> x` is applied to every layer output with
        its flat layer id (traced inside scans).  The SplitFT round engine
        uses it to compress the smashed activation exactly where each
        client's cut sits — since the id is data, the hook keeps the
        single-executable property of the mask-based split.

        A *stateful* boundary (attribute `stateful = True`, e.g. the
        smashed error-feedback hook) additionally threads a carry:
        `x, carry = boundary(x, carry, flat_id)`, initialized from
        `boundary.init()` and returned as `boundary_carry` (an empty tuple
        for stateless hooks — zero extra leaves, so the compiled HLO is
        unchanged)."""
        cfg = self.cfg
        hi_total = self.num_flat_layers if layer_hi is None else layer_hi
        aux_total = jnp.float32(0.0)
        b_stateful = bool(getattr(boundary, "stateful", False))
        bcarry = boundary.init() if b_stateful else ()
        new_cache = dict(cache) if cache is not None else None
        cache_len = cache["len"] if cache is not None else None

        # flat positions for RoPE
        if mode == "decode":
            positions = cache_len[..., None]              # (B,1)
        else:
            s = x.shape[-2]
            positions = jnp.arange(s)
        rope = self._rope(positions)

        flat_base = 0
        for name, lo, hi in self.runs:
            g = self.group_by_name[name]
            run_flat_lo = flat_base
            flat_base += hi - lo
            # intersect [run_flat_lo, flat_base) with [layer_lo, hi_total)
            a = max(run_flat_lo, layer_lo)
            b = min(flat_base, hi_total)
            if a >= b:
                continue
            glo = lo + (a - run_flat_lo)
            ghi = lo + (b - run_flat_lo)
            x, aux_total, new_cache, bcarry = self._run_group(
                g, params, adapters, x, glo, ghi, policy=policy, mode=mode,
                remat=remat, cache=new_cache, cache_len=cache_len, rope=rope,
                memory=memory, aux_total=aux_total, flat_lo=a,
                boundary=boundary, bcarry=bcarry)
        if new_cache is not None and mode == "decode":
            new_cache["len"] = cache_len + 1
        elif new_cache is not None and mode == "prefill":
            new_cache["len"] = cache_len + x.shape[-2]
        return x, aux_total, new_cache, bcarry

    def _run_group(self, g: GroupSpec, params, adapters, x, lo, hi, *,
                   policy, mode, remat, cache, cache_len, rope, memory,
                   aux_total, flat_lo: int = 0, boundary=None, bcarry=()):
        p_g = params[g.name]
        ad_g = adapters.get(g.name) if adapters else None
        cache_g = cache.get(g.name) if cache else None
        # paged serving cache: the (B, P_max) page table is layer-shared
        # (one table addresses every layer's page pool)
        cache_pages = cache.get("pages") if cache else None

        def slice_tree(t, a, b):
            return jax.tree.map(lambda v: v[a:b], t) if t is not None else None

        def index_tree(t, i):
            return jax.tree.map(lambda v: v[i], t) if t is not None else None

        def split_layer_cache(c_l):
            """Per-layer cache slice -> (self-cache, mem-cache) args."""
            if c_l is None:
                return None, ({} if (g.cross and mode != "decode"
                                     and cache_g is not None) else None)
            if g.kind == "ssm":
                return {"conv": c_l["conv"], "state": c_l["state"]}, None
            self_c = {"k": c_l["k"], "v": c_l["v"], "len": cache_len}
            if cache_pages is not None:
                self_c["pages"] = cache_pages
            mem_c = None
            if g.cross:
                mem_c = ({"k": c_l["xk"], "v": c_l["xv"]}
                         if mode == "decode" else {})
            return self_c, mem_c

        def pack_new(c_new, m_new):
            """(self-cache', mem-cache') -> per-layer cache slice for ys."""
            if c_new is None:
                return None
            if g.kind == "ssm":
                return {"conv": c_new["conv"], "state": c_new["state"]}
            out = {"k": c_new["k"], "v": c_new["v"]}
            if g.cross:
                if m_new:
                    out["xk"], out["xv"] = m_new["k"], m_new["v"]
                else:   # decode: cross cache unchanged, thread it through
                    out["xk"], out["xv"] = c_new["xk"], c_new["xv"]
            return out

        mem = memory if g.cross else None
        b_stateful = bool(getattr(boundary, "stateful", False))
        if g.scan and (hi - lo) > 1:
            window = g.window_of(lo)
            body = self._layer_body(g, policy=policy, mode=mode, rope=rope,
                                    memory=mem, window=window)

            def scan_body(carry, xs):
                xc, aux, bc = carry
                p_l, ad_l, c_l, fid = xs
                self_c, mem_c = split_layer_cache(c_l)
                xc, a, c_new, m_new = body(xc, p_l, ad_l, self_c, mem_c)
                if boundary is not None:
                    if b_stateful:
                        xc, bc = boundary(xc, bc, fid)
                    else:
                        xc = boundary(xc, fid)
                ys = None
                if c_l is not None:
                    if g.kind != "ssm":
                        c_new = dict(c_new)
                        c_new.pop("len", None)
                        c_new.pop("pages", None)
                        if g.cross and mode == "decode":
                            c_new["xk"], c_new["xv"] = c_l["xk"], c_l["xv"]
                    ys = pack_new(c_new, m_new)
                return (xc, aux + a, bc), ys

            if mode == "train":
                scan_body = self._maybe_remat(scan_body, remat)
            (x, aux_total, bcarry), new_c = jax.lax.scan(
                scan_body, (x, aux_total, bcarry),
                (slice_tree(p_g, lo, hi), slice_tree(ad_g, lo, hi),
                 slice_tree(cache_g, lo, hi),
                 jnp.arange(flat_lo, flat_lo + (hi - lo))))
            if cache_g is not None:
                cache = dict(cache)
                merged = dict(cache_g)
                for k, v in new_c.items():
                    merged[k] = jax.lax.dynamic_update_slice_in_dim(
                        merged[k], v.astype(merged[k].dtype), lo, axis=0)
                cache[g.name] = merged
            return x, aux_total, cache, bcarry

        # unrolled path: static layer indices (per-layer windows, short runs)
        new_cache_g = dict(cache_g) if cache_g is not None else None
        for i in range(lo, hi):
            p_l = index_tree(p_g, i)
            ad_l = index_tree(ad_g, i)
            c_l = index_tree(new_cache_g, i)
            self_c, mem_c = split_layer_cache(c_l)
            window = g.window_of(i)
            body = self._layer_body(g, policy=policy, mode=mode, rope=rope,
                                    memory=mem, window=window)
            if mode == "train":
                body = self._maybe_remat(body, remat)
            x, a, c_new, m_new = body(x, p_l, ad_l, self_c, mem_c)
            if boundary is not None:
                if b_stateful:
                    x, bcarry = boundary(x, bcarry, flat_lo + (i - lo))
                else:
                    x = boundary(x, flat_lo + (i - lo))
            aux_total = aux_total + a
            if new_cache_g is not None and c_new is not None:
                if g.kind != "ssm":
                    c_new = dict(c_new)
                    c_new.pop("len", None)
                    c_new.pop("pages", None)
                    if g.cross and mode == "decode":
                        c_new["xk"], c_new["xv"] = c_l["xk"], c_l["xv"]
                packed = pack_new(c_new, m_new)
                for k, v in packed.items():
                    new_cache_g[k] = new_cache_g[k].at[i].set(
                        v.astype(new_cache_g[k].dtype))
        if cache is not None and new_cache_g is not None:
            cache = dict(cache)
            cache[g.name] = new_cache_g
        return x, aux_total, cache, bcarry

    # -- encoder (whisper) -----------------------------------------------------

    def encode(self, params: Params, adapters, frames, *, policy=NO_SHARDING,
               remat: str = "none", boundary=None):
        """frames ([N,]B, S_enc, d) stub embeddings -> encoder output."""
        cfg = self.cfg
        if getattr(boundary, "stateful", False):
            raise NotImplementedError(
                "stateful (error-feedback) smashed boundaries are not "
                "supported across the encoder stack")
        x = frames + params["embed"]["enc_pos"].astype(frames.dtype)
        x = policy.act(x)
        g = self.group_by_name["enc"]
        n_enc = g.size
        x, aux, _, _ = self.run_blocks(params, adapters, x, policy=policy,
                                       mode="train", remat=remat,
                                       layer_lo=0, layer_hi=n_enc,
                                       boundary=boundary)
        return apply_norm(params["enc_norm"], x, kind=cfg.norm,
                          eps=cfg.norm_eps)

    # -- top-level entry points ------------------------------------------------

    def forward(self, params, adapters, batch, *, policy=NO_SHARDING,
                remat="none", cache=None, mode="train", boundary=None,
                return_boundary: bool = False):
        """Full forward to hidden states (pre-head).

        batch: {"tokens": ([N,]B,S)[, "prefix": ([N,]B,P,d)]
                [, "frames": ([N,]B,S_enc,d)]}.

        return_boundary=True appends the boundary carry (the smashed EF
        residual for stateful hooks) to the return tuple."""
        cfg = self.cfg
        tokens = batch["tokens"]
        memory = None
        lo = 0
        if cfg.family == "audio":
            if mode == "decode":
                memory = None   # cross K/V come from the cache
            else:
                memory = self.encode(params, adapters, batch["frames"],
                                     policy=policy, remat=remat,
                                     boundary=boundary)
            lo = self.group_by_name["enc"].size
        positions = (cache["len"][..., None] if mode == "decode"
                     else jnp.arange(tokens.shape[-1]))
        x = self.embed(params, tokens, positions=positions,
                       prefix=batch.get("prefix"), policy=policy)
        x, aux, new_cache, bcarry = self.run_blocks(
            params, adapters, x, policy=policy, mode=mode, remat=remat,
            cache=cache, memory=memory, layer_lo=lo, boundary=boundary)
        x = apply_norm(params["final_norm"], x, kind=cfg.norm,
                       eps=cfg.norm_eps)
        if return_boundary:
            return x, aux, new_cache, bcarry
        return x, aux, new_cache

    def loss(self, params, adapters, batch, *, policy=NO_SHARDING,
             remat="none", ce_chunk: int = 0, per_client: bool = False,
             boundary=None):
        """Next-token CE.  batch needs "tokens", "labels"[, "loss_mask"].

        per_client=True keeps the leading client axis un-reduced: returns
        ((N,) nll, metrics with (N,) entries) — the SplitFT round engine
        weights and combines them (paper formula 2).  `boundary` is the
        cut-layer hook (see run_blocks) used for smashed compression; a
        stateful (EF) boundary's new residual is returned as
        metrics["smashed_ef"]."""
        b_stateful = bool(getattr(boundary, "stateful", False))
        if b_stateful:
            x, aux, _, bcarry = self.forward(
                params, adapters, batch, policy=policy, remat=remat,
                mode="train", boundary=boundary, return_boundary=True)
        else:
            x, aux, _ = self.forward(params, adapters, batch,
                                     policy=policy, remat=remat,
                                     mode="train", boundary=boundary)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        keep = 1 if per_client else 0
        if ce_chunk and x.shape[-2] > ce_chunk and \
                x.shape[-2] % ce_chunk == 0:
            sums = self._chunked_ce(params, x, labels, mask, ce_chunk,
                                    policy, keep)
        else:
            logits = self.head(params, x, policy=policy)
            sums = _ce_sums(logits, labels, mask, keep)
        nll_sum, hits, cnt = sums
        cnt = jnp.maximum(cnt, 1.0)
        nll, acc = nll_sum / cnt, hits / cnt
        metrics = {"ce": nll, "aux": aux, "accuracy": acc, "tokens": cnt}
        if b_stateful:
            metrics["smashed_ef"] = bcarry
        return nll + aux, metrics

    def _chunked_ce(self, params, x, labels, mask, chunk, policy, keep):
        """CE over sequence chunks; logits for one chunk at a time are live
        (the backward recomputes them under jax.checkpoint)."""
        s = x.shape[-2]
        nch = s // chunk
        lead = x.shape[:-2]
        xs = jnp.moveaxis(
            x.reshape(lead + (nch, chunk, x.shape[-1])), -3, 0)
        ls = jnp.moveaxis(labels.reshape(lead + (nch, chunk)), -2, 0)
        ms = jnp.moveaxis(mask.reshape(lead + (nch, chunk)), -2, 0)
        zero = jnp.zeros(lead[:keep], jnp.float32)

        @jax.checkpoint
        def body(carry, inp):
            x_c, l_c, m_c = inp
            logits = self.head(params, x_c, policy=policy)
            nll_s, hit_s, cnt_s = _ce_sums(logits, l_c, m_c, keep)
            a, b, c = carry
            return (a + nll_s, b + hit_s, c + cnt_s), None

        sums, _ = jax.lax.scan(body, (zero, zero, zero), (xs, ls, ms))
        return sums

    def prefill(self, params, adapters, batch, cache, *, policy=NO_SHARDING,
                remat="none"):
        x, _, cache = self.forward(params, adapters, batch, policy=policy,
                                   remat=remat, cache=cache, mode="prefill")
        logits = self.head(params, x[..., -1:, :], policy=policy)
        return logits, cache

    def decode_step(self, params, adapters, tokens, cache, *,
                    policy=NO_SHARDING, frames=None):
        batch = {"tokens": tokens}
        x, _, cache = self.forward(params, adapters, batch, policy=policy,
                                   cache=cache, mode="decode")
        logits = self.head(params, x, policy=policy)
        return logits, cache

    # -- caches ----------------------------------------------------------------

    def init_cache(self, lead: Tuple[int, ...], max_len: int,
                   dtype=jnp.float32) -> Params:
        """lead = ([N,]B). One stacked cache entry per group."""
        cfg = self.cfg
        batch = lead[-1]
        cache: Params = {"len": jnp.zeros((batch,), jnp.int32)}
        for g in self.groups:
            if g.name == "enc":
                continue
            if g.kind == "ssm":
                per = ssm.init_ssm_cache(cfg, lead, dtype)
                cache[g.name] = {
                    "conv": jnp.zeros((g.size,) + per["conv"].shape, dtype),
                    "state": jnp.zeros((g.size,) + per["state"].shape,
                                       jnp.float32),
                }
            else:
                kvh, hd = cfg.num_kv_heads, cfg.head_dim
                kv_shape = (g.size,) + lead + (max_len, kvh, hd)
                cache[g.name] = {"k": jnp.zeros(kv_shape, dtype),
                                 "v": jnp.zeros(kv_shape, dtype)}
                if g.cross:
                    xs = (g.size,) + lead + (cfg.encoder_seq_len, kvh, hd)
                    cache[g.name]["xk"] = jnp.zeros(xs, dtype)
                    cache[g.name]["xv"] = jnp.zeros(xs, dtype)
        return cache


def build_model(arch: ArchConfig, *, unroll: bool = False) -> Model:
    return Model(arch, unroll=unroll)
