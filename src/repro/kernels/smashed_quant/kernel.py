"""Pallas TPU kernels for smashed-activation int8 compression.

Three kernels over x (G, M, d) — G client messages, M tokens, d channels:

  quantize   x -> (q int8, scale f32)    per-channel scale per message
  dequantize (q, scale) -> x_hat         elementwise expand
  roundtrip  x -> dequant(quant(x))      the in-graph wire simulation

The per-channel amax needs a reduction over ALL row blocks of a message
before any block can be quantized, so quantize/roundtrip run a two-phase
sequential grid (g, phase, i):

  phase 0:  amax[1, d] = max(amax, max_rows |x[g, i]|)   (VMEM scratch —
            the TPU grid is sequential per core, so the scratch persists
            across (phase, i) steps of one g)
  phase 1:  scale = amax / 127; emit q (and/or x_hat) block-by-block

x is read twice; q/x_hat are written once; the (M, d) int8 intermediate of
the round trip never touches HBM (that is the fusion — a jnp composition
materializes it between the two XLA kernels).

Alignment: callers pad M to the block multiple and d to the 128-lane
multiple (zero padding is amax-neutral).  Padded channels quantize against
scale EPS/127 and dequantize to exact zero.  The per-message scale travels
as (G, 1, d), so its (1, 1, d) block spans the array's last two dims: the
chip's compiler refuses a (1, d) block over a (G, d) array once G > 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
EPS = 1e-12


def _quant_body(x_ref, amax_ref, *, emit):
    """Shared two-phase body: reduce amax, then call emit(x, scale)."""
    p = pl.program_id(1)
    i = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)                       # (bm, d)

    @pl.when(jnp.logical_and(p == 0, i == 0))
    def _zero():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(p == 0)
    def _accum():
        amax_ref[...] = jnp.maximum(
            amax_ref[...], jnp.max(jnp.abs(x), axis=0, keepdims=True))

    @pl.when(p == 1)
    def _emit():
        scale = jnp.maximum(amax_ref[...], EPS) / 127.0    # (1, d)
        emit(x, scale)


def _quantize_kernel(x_ref, q_ref, scale_ref, amax_ref):
    def emit(x, scale):
        q_ref[0] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        scale_ref[0] = scale

    _quant_body(x_ref, amax_ref, emit=emit)


def _roundtrip_kernel(x_ref, y_ref, amax_ref):
    def emit(x, scale):
        q = jnp.clip(jnp.round(x / scale), -127, 127)
        y_ref[0] = (q * scale).astype(y_ref.dtype)

    _quant_body(x_ref, amax_ref, emit=emit)


def _dequantize_kernel(q_ref, scale_ref, x_ref):
    x_ref[0] = (q_ref[0].astype(jnp.float32) * scale_ref[0]) \
        .astype(x_ref.dtype)


def _two_phase_call(kernel, x, out_shapes, out_specs, *, bm, interpret):
    g, m, d = x.shape
    if m % bm:
        raise ValueError(f"rows {m} not divisible by block {bm}; "
                         "pad in the wrapper")
    grid = (g, 2, m // bm)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, d), lambda gi, p, i: (gi, i, 0))],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],   # amax
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_pallas(x, *, bm: int = DEFAULT_BM, interpret: bool = False):
    """x (G, M, d) -> (q (G, M, d) int8, scale (G, 1, d) f32)."""
    g, m, d = x.shape
    return _two_phase_call(
        _quantize_kernel, x,
        out_shapes=(jax.ShapeDtypeStruct((g, m, d), jnp.int8),
                    jax.ShapeDtypeStruct((g, 1, d), jnp.float32)),
        out_specs=(pl.BlockSpec((1, bm, d), lambda gi, p, i: (gi, i, 0)),
                   pl.BlockSpec((1, 1, d), lambda gi, p, i: (gi, 0, 0))),
        bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def roundtrip_pallas(x, *, bm: int = DEFAULT_BM, interpret: bool = False):
    """Fused dequant(quant(x)): (G, M, d) -> (G, M, d) in x.dtype."""
    g, m, d = x.shape
    return _two_phase_call(
        _roundtrip_kernel, x,
        out_shapes=jax.ShapeDtypeStruct((g, m, d), x.dtype),
        out_specs=pl.BlockSpec((1, bm, d), lambda gi, p, i: (gi, i, 0)),
        bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "dtype"))
def dequantize_pallas(q, scale, *, dtype=jnp.float32, bm: int = DEFAULT_BM,
                      interpret: bool = False):
    """(q (G, M, d) int8, scale (G, 1, d) f32) -> x_hat (G, M, d) `dtype`."""
    g, m, d = q.shape
    if m % bm:
        raise ValueError(f"rows {m} not divisible by block {bm}; "
                         "pad in the wrapper")
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(g, m // bm),
        in_specs=[pl.BlockSpec((1, bm, d), lambda gi, i: (gi, i, 0)),
                  pl.BlockSpec((1, 1, d), lambda gi, i: (gi, 0, 0))],
        out_specs=pl.BlockSpec((1, bm, d), lambda gi, i: (gi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, m, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, scale)
