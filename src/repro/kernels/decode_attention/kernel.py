"""Flash-decode Pallas TPU kernel.

Decode attention is memory-bound: the whole KV cache is streamed from HBM
for one query token.  The kernel's job is to hit the streaming roofline:

  * GQA amortization — the grid iterates (B, KVH, S-blocks) and computes the
    WHOLE GQA group (`group` query heads) against each KV tile, so KV bytes
    are read once per group instead of once per query head (an 8x HBM saving
    for the assigned kv=8 archs vs. a per-head loop).
  * Online softmax over S-blocks in fp32 scratch, exactly as prefill flash,
    with a (group, 1) running max / normalizer.
  * Cache-length masking — cache_len is a per-batch scalar (SMEM); KV tiles
    entirely past cache_len are skipped at tile level (real skip: Mosaic
    grids execute sequentially per core).

Block: (bs, hd) KV tiles, bs=512 default; q tile (group, hd) stays resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu

NEG_INF = -1e30
DEFAULT_BS = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, bs: int, n_s: int, group: int, window: int):
    ib = pl.program_id(0)
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    clen = len_ref[ib]
    s_start = isb * bs
    run = s_start < clen
    if window > 0:
        run = jnp.logical_and(run, s_start + bs > clen - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bs, hd)
        prec = mxu.precision(q_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        valid = pos < clen
        if window > 0:
            valid = jnp.logical_and(valid, pos >= clen - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], precision=prec,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(isb == n_s - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref,
                  *, scale: float, ps: int, n_p: int, group: int,
                  window: int):
    """Same online softmax as _kernel, but the S axis is walked page by
    page: the (ps, hd) KV tile for grid step ip is fetched from pool page
    pt_ref[ib, ip] (scalar-prefetched, so the gather happens in the
    BlockSpec index map, not in the body)."""
    ib = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    clen = len_ref[ib]
    s_start = ip * ps
    run = s_start < clen
    if window > 0:
        run = jnp.logical_and(run, s_start + ps > clen - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, hd)
        prec = mxu.precision(q_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (group, ps), 1)
        valid = pos < clen
        if window > 0:
            valid = jnp.logical_and(valid, pos >= clen - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], precision=prec,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ip == n_p - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def decode_attention_paged_pallas(q, k_pool, v_pool, page_table, cache_len,
                                  *, scale: float | None = None,
                                  window: int = 0, interpret: bool = False):
    """q: (B,H,hd); k_pool/v_pool: (n_pages, ps, KVH, hd);
    page_table: (B, P_max) int32; cache_len: (B,) -> (B,H,hd).

    Table entries past the allocated prefix must still be valid pool
    indices (callers point them at the reserved trash page); their tiles
    are skipped by the cache_len gate but the index map always fires."""
    b, h, hd = q.shape
    n_pages, ps, kvh, _ = k_pool.shape
    p_max = page_table.shape[1]
    group = h // kvh
    if scale is None:
        scale = hd ** -0.5

    qt = q.reshape(b, kvh, group, hd)
    kt = k_pool.transpose(0, 2, 1, 3)   # (n_pages, KVH, ps, hd)
    vt = v_pool.transpose(0, 2, 1, 3)

    grid = (b, kvh, p_max)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, ps=ps, n_p=p_max,
                          group=group, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, group, hd),
                             lambda ib, ih, ip, lr, pt: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, ps, hd),
                             lambda ib, ih, ip, lr, pt: (pt[ib, ip], ih, 0, 0)),
                pl.BlockSpec((1, 1, ps, hd),
                             lambda ib, ih, ip, lr, pt: (pt[ib, ip], ih, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, hd),
                                   lambda ib, ih, ip, lr, pt: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), page_table.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, h, hd)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bs", "window", "interpret"))
def decode_attention_pallas(q, k, v, cache_len, *, scale: float | None = None,
                            bs: int = DEFAULT_BS, window: int = 0,
                            interpret: bool = False):
    """q: (B,H,hd); k/v: (B,S,KVH,hd); cache_len: (B,) -> (B,H,hd)."""
    b, h, hd = q.shape
    _, s, kvh, _ = k.shape
    group = h // kvh
    if scale is None:
        scale = hd ** -0.5
    bs = min(bs, s)
    if s % bs:
        raise ValueError(f"cache length {s} not divisible by block {bs}")
    n_s = s // bs

    qt = q.reshape(b, kvh, group, hd)
    kt = k.transpose(0, 2, 1, 3)   # (B, KVH, S, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, kvh, n_s)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, n_s=n_s, group=group,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # len
            pl.BlockSpec((1, 1, group, hd), lambda ib, ih, isb: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda ib, ih, isb: (ib, ih, isb, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda ib, ih, isb: (ib, ih, isb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda ib, ih, isb: (ib, ih, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, h, hd)
