"""Fused LoRA matmul Pallas TPU kernels (forward and backward).

Forward:  y = x @ W + scale * (x @ A) @ B  in a single pass over x/W.

Why fused: the paper's central op is the LoRA-adapted projection.  Naively
this is three matmuls with two extra HBM round-trips (x re-read for x@A, the
(M, r) intermediate written + read back).  Since r <= 64 the A tile (bk, r)
and B tile (r, bn) always fit VMEM, so we fuse:

  grid = (M/bm, N/bn, K/bk), dimension order (i, j, k), k innermost.
  acc[bm, bn]  += x[i,k] @ W[k,j]           every (j, k) step
  xa[bm, r]    += x[i,k] @ A[k]             only when j == 0 (computed once
                                            per row-block, reused for all j:
                                            TPU grid is sequential per core,
                                            scratch persists across steps)
  epilogue (k == K-1): y[i,j] = acc + scale * xa @ B[j]

The fp32 (M, r) intermediate xa is also emitted as an output — it is the
residual the backward reuses (dB = s xa^T g, dscale = sum(xa * gb)), saved
by the custom_vjp instead of being recomputed.

Backward (fine-tuning is backward-dominated; this is the hot path):

  gb = g @ B^T                      (M, r)
  dx = g @ W^T + s gb @ A^T         (M, K)   <- the big term
  dA = s x^T @ gb                   (K, r)
  dB = s xa^T @ g                   (r, N)
  dscale = sum(xa * gb)             ()        (wrapper, one elementwise op)
  dW = x^T @ g                      (K, N)   <- NOT computed under
                                               lora_only (frozen base)

Kernel 1 (_bwd_dx): grid (M/bm, K/bk, N/bn), n innermost — mirrors the
forward: dx accumulates over n in fp32 scratch; gb accumulates only when
k == 0 and persists in scratch for every k block of the same row block;
the epilogue adds s * gb @ A[k]^T.  gb is emitted as a second output for
kernel 2 / dscale.

Kernel 2 (_bwd_dab): grid (M/bm,) — one pass over the row blocks with the
full-width (K, r) / (r, N) adapter-gradient tiles accumulated directly in
the (never-flushed) fp32 output windows.  The adapter side is rank-r thin,
so both gradients together are r*(K+N)*4 bytes of VMEM — ~1 MiB at
d_model 4096, r 32.

MXU alignment: bm/bn multiples of 128, r padded to >= 8 lanes by the wrapper.
Accumulation is fp32 regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, scale_ref, y_ref, xa_out_ref,
                acc_ref, xa_ref, *, n_k: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _zero_xa():
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    prec = mxu.precision(x_ref.dtype)
    acc_ref[...] += jnp.dot(x, w_ref[...], precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _accum_xa():
        xa_ref[...] += jnp.dot(x, a_ref[...], precision=prec,
                               preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = scale_ref[0].astype(jnp.float32)
        delta = jnp.dot(xa_ref[...], b_ref[...].astype(jnp.float32),
                        precision=mxu.precision(xa_ref.dtype),
                        preferred_element_type=jnp.float32)
        y_ref[...] = (acc_ref[...] + scale * delta).astype(y_ref.dtype)

    @pl.when(jnp.logical_and(j == 0, k == n_k - 1))
    def _save_xa():
        xa_out_ref[...] = xa_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lora_matmul_pallas(x, w, a, b, scale, *, bm: int = DEFAULT_BM,
                       bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                       interpret: bool = False):
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N); scale: scalar ->
    (y (M, N), xa (M, r) fp32 residual)."""
    m, k_dim = x.shape
    _, n = w.shape
    r = a.shape[1]

    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k_dim)
    if m % bm or n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim},{n}) not divisible by blocks "
                         f"({bm},{bk},{bn}); pad in the wrapper")
    n_k = k_dim // bk
    grid = (m // bm, n // bn, n_k)

    scale_arr = jnp.asarray(scale, jnp.float32).reshape((1,))

    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),       # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),       # w
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),        # a
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),        # b
            pl.BlockSpec(memory_space=pltpu.SMEM),                # scale
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),       # y
            pl.BlockSpec((bm, r), lambda i, j, k: (i, 0)),        # xa
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((m, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),   # acc
            pltpu.VMEM((bm, r), jnp.float32),    # xa
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, a, b, scale_arr)


# ---------------------------------------------------------------------------
# indexed multi-adapter forward (serving, inference-only)


def _indexed_kernel(ids_ref, scale_ref, x_ref, w_ref, a_ref, b_ref, y_ref,
                    acc_ref, xa_ref, *, n_k: int):
    """One grid row per request slot: the adapter tiles for this row were
    DMA'd by the scalar-prefetch index maps (a/b block index = ids[row]),
    so the body is exactly the fused forward at bm=1.  x/y blocks are
    (1, 1, b*) over (M, 1, *) arrays; [0] drops the unit row axis."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _zero_xa():
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[0]
    prec = mxu.precision(x_ref.dtype)
    acc_ref[...] += jnp.dot(x, w_ref[...], precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _accum_xa():
        xa_ref[...] += jnp.dot(x, a_ref[0], precision=prec,
                               preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = scale_ref[ids_ref[i]].astype(jnp.float32)
        delta = jnp.dot(xa_ref[...], b_ref[0].astype(jnp.float32),
                        precision=mxu.precision(xa_ref.dtype),
                        preferred_element_type=jnp.float32)
        y_ref[0] = (acc_ref[...] + scale * delta).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def lora_matmul_indexed_pallas(x, w, a_pool, b_pool, scale, ids, *,
                               bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                               interpret: bool = False):
    """x: (M, 1, K); w: (K, N); a_pool: (P, K, r); b_pool: (P, r, N);
    scale: (P,); ids: (M,) int32 -> y (M, 1, N).

    S-LoRA-style decode projection: every x row is one serving slot's
    token and gathers its own adapter out of the stacked pool via the
    scalar-prefetched ids in the a/b BlockSpec index maps — the pool
    stays in HBM, only the referenced (bk, r)/(r, bn) tiles move.  The
    unit middle axis lets the one-row x/y blocks span the arrays' last
    two dims: the chip's compiler refuses a (1, bk) block over (M, K)."""
    m, _, k_dim = x.shape
    _, n = w.shape
    r = a_pool.shape[2]

    bn = min(bn, n)
    bk = min(bk, k_dim)
    if n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim},{n}) not divisible by blocks "
                         f"({bk},{bn}); pad in the wrapper")
    n_k = k_dim // bk
    grid = (m, n // bn, n_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # ids, scale
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk),
                         lambda i, j, k, ids, s: (i, 0, k)),          # x
            pl.BlockSpec((bk, bn), lambda i, j, k, ids, s: (k, j)),   # w
            pl.BlockSpec((1, bk, r),
                         lambda i, j, k, ids, s: (ids[i], k, 0)),     # A[ids]
            pl.BlockSpec((1, r, bn),
                         lambda i, j, k, ids, s: (ids[i], 0, j)),     # B[ids]
        ],
        out_specs=pl.BlockSpec((1, 1, bn),
                               lambda i, j, k, ids, s: (i, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),    # acc
            pltpu.VMEM((1, r), jnp.float32),     # xa
        ],
    )
    return pl.pallas_call(
        functools.partial(_indexed_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(ids.astype(jnp.int32), scale.astype(jnp.float32), x, w, a_pool, b_pool)


# ---------------------------------------------------------------------------
# backward


def _bwd_dx_kernel(g_ref, w_ref, a_ref, b_ref, scale_ref, dx_ref, gb_ref,
                   acc_ref, gb_acc, *, n_n: int):
    k = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(k == 0, n == 0))
    def _zero_gb():
        gb_acc[...] = jnp.zeros_like(gb_acc)

    g = g_ref[...]
    prec = mxu.precision(g_ref.dtype)
    # dx accumulation: g[i, n] @ W[k, n]^T, contracting the n axis
    acc_ref[...] += jax.lax.dot_general(
        g, w_ref[...], (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _accum_gb():
        gb_acc[...] += jax.lax.dot_general(
            g, b_ref[...], (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _epilogue():
        scale = scale_ref[0].astype(jnp.float32)
        low = jax.lax.dot_general(
            gb_acc[...], a_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=mxu.precision(gb_acc.dtype),
            preferred_element_type=jnp.float32)
        dx_ref[...] = (acc_ref[...] + scale * low).astype(dx_ref.dtype)

    @pl.when(jnp.logical_and(k == 0, n == n_n - 1))
    def _save_gb():
        gb_ref[...] = gb_acc[...]


def _bwd_dab_kernel(x_ref, g_ref, xa_ref, gb_ref, scale_ref, da_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    scale = scale_ref[0].astype(jnp.float32)
    # dA += s x[i]^T @ gb[i]; dB += s xa[i]^T @ g[i] — the (K, r) / (r, N)
    # output windows never change block, so accumulating into them is safe.
    # gb and xa are f32: both dots take full precision.
    da_ref[...] += scale * jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), gb_ref[...], (((0,), (0,)), ((), ())),
        precision=mxu.precision(gb_ref.dtype),
        preferred_element_type=jnp.float32)
    db_ref[...] += scale * jax.lax.dot_general(
        xa_ref[...], g_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=mxu.precision(xa_ref.dtype),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lora_matmul_bwd_pallas(x, w, a, b, scale, g, xa, *,
                           bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                           bk: int = DEFAULT_BK, interpret: bool = False):
    """Fused LoRA backward.  x: (M, K); w: (K, N); a: (K, r); b: (r, N);
    g: (M, N) cotangent; xa: (M, r) fp32 forward residual.

    Returns (dx (M, K) x.dtype, da (K, r) fp32, db (r, N) fp32,
    dscale () fp32).  dW is intentionally NOT computed here: under
    lora_only the frozen-base gradient is never materialized."""
    m, k_dim = x.shape
    _, n = w.shape
    r = a.shape[1]

    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k_dim)
    if m % bm or n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim},{n}) not divisible by blocks "
                         f"({bm},{bk},{bn}); pad in the wrapper")
    n_n = n // bn

    scale_arr = jnp.asarray(scale, jnp.float32).reshape((1,))

    dx, gb = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, n_n=n_n),
        grid=(m // bm, k_dim // bk, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, k, n: (i, n)),       # g
            pl.BlockSpec((bk, bn), lambda i, k, n: (k, n)),       # w
            pl.BlockSpec((bk, r), lambda i, k, n: (k, 0)),        # a
            pl.BlockSpec((r, bn), lambda i, k, n: (0, n)),        # b
            pl.BlockSpec(memory_space=pltpu.SMEM),                # scale
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, n: (i, k)),       # dx
            pl.BlockSpec((bm, r), lambda i, k, n: (i, 0)),        # gb
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k_dim), x.dtype),
            jax.ShapeDtypeStruct((m, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bk), jnp.float32),   # dx accumulator
            pltpu.VMEM((bm, r), jnp.float32),    # gb accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(g, w, a, b, scale_arr)

    da, db = pl.pallas_call(
        _bwd_dab_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k_dim), lambda i: (i, 0)),          # x
            pl.BlockSpec((bm, n), lambda i: (i, 0)),              # g
            pl.BlockSpec((bm, r), lambda i: (i, 0)),              # xa
            pl.BlockSpec((bm, r), lambda i: (i, 0)),              # gb
            pl.BlockSpec(memory_space=pltpu.SMEM),                # scale
        ],
        out_specs=[
            pl.BlockSpec((k_dim, r), lambda i: (0, 0)),           # da
            pl.BlockSpec((r, n), lambda i: (0, 0)),               # db
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_dim, r), jnp.float32),
            jax.ShapeDtypeStruct((r, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, g, xa, gb, scale_arr)

    dscale = jnp.sum(xa * gb)
    return dx, da, db, dscale
