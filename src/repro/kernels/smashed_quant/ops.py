"""Public wrappers for the smashed-activation int8 quantizer pair.

Dispatch policy (shared by all kernels in repro.kernels):
  * on TPU                      -> Pallas kernels
  * REPRO_PALLAS_INTERPRET=1    -> Pallas kernels in interpret mode (tests)
  * otherwise (CPU/GPU)         -> ref.py jnp oracle

The wrappers own shape management: inputs of shape (..., d) are
canonicalized to (G, M, d) — G the leading message axis (clients), M the
flattened token axis — padded to block/lane multiples, and unpadded on the
way out.  Scales come back as (G, d) (or (d,) for 2-D inputs).

Gradient handling is NOT here: the straight-through estimator that makes
the f4 gradient return compressed symmetrically lives in
repro.core.smashed, next to the other compressors.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.smashed_quant import ref
from repro.kernels.smashed_quant.kernel import (DEFAULT_BM, dequantize_pallas,
                                                quantize_pallas,
                                                roundtrip_pallas)
from repro.kernels.spmd import per_batch_shard


def _use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET") == "1"


def _canon(x):
    """(..., d) -> ((G, M, d), restore_shape).  dim 0 is the message axis
    for ndim >= 3; 2-D inputs are a single message."""
    if x.ndim < 2:
        raise ValueError(f"need at least (M, d), got {x.shape}")
    if x.ndim == 2:
        return x[None], x.shape
    g, d = x.shape[0], x.shape[-1]
    return x.reshape(g, -1, d), x.shape


def _block_rows(m: int) -> int:
    if m >= DEFAULT_BM:
        return DEFAULT_BM
    # int8 tiles need >= 32 sublanes; round up to a power of two
    return max(32, 1 << (m - 1).bit_length())


def _pad(x3):
    g, m, d = x3.shape
    bm = _block_rows(m)
    pm, pd = (-m) % bm, (-d) % 128
    if pm or pd:
        x3 = jnp.pad(x3, ((0, 0), (0, pm), (0, pd)))
    return x3, bm, m, d


def _quantize_path(x3):
    xp, bm, m, d = _pad(x3)
    q, scale = quantize_pallas(xp, bm=bm, interpret=_interpret())
    return q[:, :m, :d], scale[:, 0, :d]


def _dequantize_path(q3, scale3, dtype):
    g, m, d = q3.shape
    bm = _block_rows(m)
    pm, pd = (-m) % bm, (-d) % 128
    if pm or pd:
        q3 = jnp.pad(q3, ((0, 0), (0, pm), (0, pd)))
        scale3 = jnp.pad(scale3, ((0, 0), (0, pd)))
    return dequantize_pallas(q3, scale3[:, None], dtype=dtype, bm=bm,
                             interpret=_interpret())[:, :m, :d]


def _roundtrip_path(x3):
    xp, bm, m, d = _pad(x3)
    return roundtrip_pallas(xp, bm=bm, interpret=_interpret())[:, :m, :d]


def int8_quantize_smashed(x):
    """x (..., d) -> (q int8 same shape, scale (G, d) | (d,))."""
    x3, shape = _canon(x)
    if _use_pallas():
        q, scale = per_batch_shard(_quantize_path, x3, split=(True,))
    else:
        q, scale = ref.quantize(x3)
    q = q.reshape(shape)
    return q, (scale[0] if len(shape) == 2 else scale)


def int8_dequantize_smashed(q, scale, dtype=jnp.float32):
    """Inverse of int8_quantize_smashed (per-channel expand)."""
    q3, shape = _canon(q)
    scale3 = scale[None] if len(shape) == 2 else scale
    if _use_pallas():
        x = per_batch_shard(lambda q_, s_: _dequantize_path(q_, s_, dtype),
                            q3, scale3, split=(True, True))
    else:
        x = ref.dequantize(q3, scale3, dtype)
    return x.reshape(shape)


def int8_roundtrip_smashed(x):
    """Fused wire round trip dequant(quant(x)), same shape/dtype as x."""
    x3, shape = _canon(x)
    if _use_pallas():
        y = per_batch_shard(_roundtrip_path, x3, split=(True,))
    else:
        y = ref.roundtrip(x3)
    return y.reshape(shape)
