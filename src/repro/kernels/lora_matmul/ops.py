"""jit'd public wrapper for the fused LoRA matmul.

Dispatch policy (shared by all kernels in repro.kernels):
  * on TPU                      -> Pallas kernel
  * REPRO_PALLAS_INTERPRET=1    -> Pallas kernel in interpret mode (CPU tests)
  * otherwise (CPU/GPU)         -> ref.py jnp oracle

The wrapper owns shape management (flattening batch dims, padding to block
multiples) and the custom VJP.  Forward and backward are both Pallas on
the kernel path: the forward saves the fp32 (M, r) intermediate xa as a
residual, and the backward computes dx / dA / dB / dscale with the fused
kernels in kernel.py instead of re-deriving them in jnp.  The oracle path
keeps the jnp backward — it is the numerical contract the kernels are
tested against (tests/test_grads.py).

lora_only=True (the fine-tuning hot path: base weights frozen, only the
adapters train) skips the dW = x^T g term entirely — the frozen-base
gradient, the single largest backward tensor, is never materialized; the
cotangent returned for W is a symbolic zero that XLA dead-code-eliminates.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.kernels.lora_matmul import ref
from repro.kernels.lora_matmul.kernel import (lora_matmul_bwd_pallas,
                                              lora_matmul_indexed_pallas,
                                              lora_matmul_pallas)
from repro.kernels.spmd import per_batch_shard


def _use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET") == "1"


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _divisor_block(dim: int, candidates) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return dim


def _blocks_for(m: int, n: int, k_dim: int):
    bm = 256 if m >= 256 else max(8, 1 << (m - 1).bit_length())
    bn = _divisor_block(n, (256, 128))
    bk = _divisor_block(k_dim, (512, 256, 128))
    return bm, bn, bk


def _pallas_path(x, w, a, b, scale):
    """Flatten leading dims, pad every dim to MXU-aligned blocks, call.

    Returns (y (*lead, N), xa (M, r_pad) fp32) — xa rows are the original
    (unpadded) tokens in kernel layout, the backward residual."""
    *lead, k_dim = x.shape
    n = w.shape[1]
    x2 = x.reshape(-1, k_dim)
    m = x2.shape[0]

    bm, bn, bk = _blocks_for(m, n, k_dim)

    x2, m0 = _pad_to(x2, bm, 0)
    # pad rank to the fp32 sublane multiple so (bk, r)/(r, bn) tiles are legal
    a_p, _ = _pad_to(a, 8, 1)
    b_p, _ = _pad_to(b, 8, 0)

    y, xa = lora_matmul_pallas(x2, w, a_p, b_p, scale, bm=bm, bn=bn, bk=bk,
                               interpret=_interpret())
    return y[:m0].reshape(*lead, n), xa[:m0]


def _sharded_fwd(x, w, a, b, scale):
    """_pallas_path per batch shard of x (kernels.spmd)."""
    return per_batch_shard(_pallas_path, x, w, a, b, scale,
                           split=(True, False, False, False, False))


def _sharded_bwd(x, w, a, b, scale, g, xa):
    """_pallas_bwd_path per batch shard: dA/dB/dscale are sums over the
    rows, so each shard returns its partial and they are added here."""
    def partials(*t):
        dx, da, db, dscale = _pallas_bwd_path(*t)
        return dx, da[None], db[None], dscale[None]

    dx, da, db, dscale = per_batch_shard(
        partials, x, w, a, b, scale, g, xa,
        split=(True, False, False, False, False, True, True))
    return dx, da.sum(0), db.sum(0), dscale.sum(0)


def _pallas_bwd_path(x, w, a, b, scale, g, xa):
    """Fused Pallas backward (see kernel.py).  xa: (M, r_pad) fp32 residual
    from _pallas_path.  Returns (dx, da, db, dscale) in primal dtypes."""
    *lead, k_dim = x.shape
    n = w.shape[1]
    r = a.shape[1]
    x2 = x.reshape(-1, k_dim)
    g2 = g.reshape(-1, n)
    m = x2.shape[0]

    bm, bn, bk = _blocks_for(m, n, k_dim)

    x2, m0 = _pad_to(x2, bm, 0)
    g2, _ = _pad_to(g2, bm, 0)
    xa_p, _ = _pad_to(xa, bm, 0)
    a_p, _ = _pad_to(a, 8, 1)
    b_p, _ = _pad_to(b, 8, 0)

    dx, da, db, dscale = lora_matmul_bwd_pallas(
        x2, w, a_p, b_p, scale, g2, xa_p, bm=bm, bn=bn, bk=bk,
        interpret=_interpret())
    dx = dx[:m0].reshape(*lead, k_dim)
    # padded rank rows/cols of A/B are zero, so their gradient slices are
    # exactly zero — slicing them off loses nothing
    return (dx, da[:, :r].astype(a.dtype), db[:r].astype(b.dtype),
            dscale.astype(scale.dtype))


def _jnp_bwd(x, w, a, b, scale, g, *, lora_only: bool):
    """The jnp oracle backward (also the CPU/GPU execution path)."""
    gf = g.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    # dx = g W^T + s (g B^T) A^T
    gb = jnp.einsum("...n,rn->...r", gf, b.astype(jnp.float32))
    dx = (jnp.einsum("...n,kn->...k", gf, w.astype(jnp.float32))
          + s * jnp.einsum("...r,kr->...k", gb, a.astype(jnp.float32)))
    # dA = s x^T (g B^T);  dB = s (x A)^T g
    da = s * jnp.einsum("...k,...r->kr", xf, gb)
    xa = jnp.einsum("...k,kr->...r", xf, a.astype(jnp.float32))
    db = s * jnp.einsum("...r,...n->rn", xa, gf)
    dscale = jnp.sum(xa * gb).astype(scale.dtype)
    if lora_only:
        dw = jnp.zeros_like(w)
    else:
        dw = jnp.einsum("...k,...n->kn", xf, gf).astype(w.dtype)
    return (dx.astype(x.dtype), dw, da.astype(a.dtype), db.astype(b.dtype),
            dscale)


@functools.lru_cache(maxsize=2)
def _make_lora(lora_only: bool):
    """Build the custom_vjp fn for one dW policy (two cached instances)."""

    @jax.custom_vjp
    def f(x, w, a, b, scale):
        if _use_pallas():
            return _sharded_fwd(x, w, a, b, scale)[0]
        return ref.lora_matmul(x, w, a, b, scale)

    def fwd(x, w, a, b, scale):
        if _use_pallas():
            y, xa = _sharded_fwd(x, w, a, b, scale)
        else:
            y = ref.lora_matmul(x, w, a, b, scale)
            xa = None
        return y, (x, w, a, b, scale, xa)

    def bwd(res, g):
        x, w, a, b, scale, xa = res
        if xa is not None and _use_pallas():
            dx, da, db, dscale = _sharded_bwd(x, w, a, b, scale, g, xa)
            if lora_only:
                # symbolic zero: never computed, DCE'd when unused
                dw = jnp.zeros_like(w)
            else:
                dw = jnp.einsum("...k,...n->kn", x.astype(jnp.float32),
                                g.astype(jnp.float32)).astype(w.dtype)
            return dx, dw, da, db, dscale
        return _jnp_bwd(x, w, a, b, scale, g, lora_only=lora_only)

    f.defvjp(fwd, bwd)
    return f


def lora_matmul_indexed(x, w, a_pool, b_pool, scale, ids):
    """Multi-adapter projection: y[i] = x[i] @ W + s[ids[i]] *
    (x[i] @ A[ids[i]]) @ B[ids[i]].

    x: (B, ..., K) with ids (B,) int32 picking each leading row's adapter
    from the stacked (P, K, r)/(P, r, N) pools; scale: (P,).  Inference
    only (serving) — no custom VJP; heterogeneous ranks ride masked rank
    slots in the pools exactly as state["rank_cut"] does in training."""
    if not _use_pallas():
        return ref.lora_matmul_indexed(x, w, a_pool, b_pool, scale, ids)
    lead = x.shape[:-1]
    k_dim = x.shape[-1]
    n = w.shape[1]
    x2 = x.reshape(-1, k_dim)
    # per-token row ids: repeat each slot's id over its trailing dims
    reps = math.prod(lead[1:]) if len(lead) > 1 else 1
    row_ids = jnp.repeat(ids.astype(jnp.int32), reps)

    _, bn, bk = _blocks_for(x2.shape[0], n, k_dim)
    a_p, _ = _pad_to(a_pool, 8, 2)
    b_p, _ = _pad_to(b_pool, 8, 1)
    y = lora_matmul_indexed_pallas(x2[:, None], w, a_p, b_p, scale, row_ids,
                                   bn=bn, bk=bk, interpret=_interpret())
    return y.reshape(lead + (n,))


def lora_matmul(x, w, a, b, scale, *, lora_only: bool = False):
    """y = x @ W + scale * (x @ A) @ B with fused-kernel forward/backward
    on TPU.

    lora_only=True declares W frozen: its cotangent is a symbolic zero and
    the dW matmul is skipped (use from training code where only the
    adapters receive gradient)."""
    return _make_lora(bool(lora_only))(x, w, a, b, scale)
