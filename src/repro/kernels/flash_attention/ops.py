"""Public wrapper for flash attention.

Dispatch: TPU -> Pallas kernel; REPRO_PALLAS_INTERPRET=1 -> interpret mode;
otherwise the jnp oracle (which XLA fuses into a perfectly fine CPU path).

Forward and backward are both Pallas on the kernel path: the forward saves
the (out, logsumexp) residuals and the backward rebuilds dQ/dK/dV from
them recompute-free (see kernel.py).  On the oracle path the backward is
jax.vjp through ref.attention — the numerical contract the kernels are
tested against (tests/test_grads.py).  custom_vjp keeps both backends on
one differentiation path so the round engine never branches on backend.

q_offset is a *traced* argument of the custom_vjp, not part of the
lru_cache key: decode calls flash_attention with a different offset every
step, and keying the cache on it would grow the cache (and its closures)
without bound over a generation loop.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import (flash_attention_bwd_pallas,
                                                  flash_attention_pallas)
from repro.kernels.spmd import per_batch_shard


def _use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET") == "1"


def _block_for(s: int, target: int) -> int:
    if s >= target:
        return target
    return max(1 << max(0, (s - 1).bit_length()), 1)


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, window: int, scale: float):
    """Build a custom_vjp attention fn closed over the static config.

    The cache key is (causal, window, scale) ONLY — q_offset flows through
    as a traced scalar so a decode loop reuses one cached fn (and one
    compiled executable) for every step."""

    def _blocks(q, k):
        return (_block_for(q.shape[1], 512), _block_for(k.shape[1], 512))

    @jax.custom_vjp
    def attn(q, k, v, q_off):
        bq, bk = _blocks(q, k)
        out, _ = flash_attention_pallas(
            q, k, v, q_off, causal=causal, window=window, scale=scale,
            bq=bq, bk=bk, interpret=_interpret())
        return out

    def fwd(q, k, v, q_off):
        bq, bk = _blocks(q, k)
        out, lse = flash_attention_pallas(
            q, k, v, q_off, causal=causal, window=window, scale=scale,
            bq=bq, bk=bk, interpret=_interpret())
        return out, (q, k, v, out, lse, q_off)

    def bwd(res, g):
        q, k, v, out, lse, q_off = res
        bq, bk = _blocks(q, k)
        dq, dk, dv = flash_attention_bwd_pallas(
            q, k, v, out, lse, g, q_off, causal=causal, window=window,
            scale=scale, bq=bq, bk=bk, interpret=_interpret())
        # q_off is int32: its cotangent type is float0
        return dq, dk, dv, np.zeros((), jax.dtypes.float0)

    attn.defvjp(fwd, bwd)
    return attn


CHUNKED_THRESHOLD = 1024    # non-TPU: S_k above this -> chunked online path


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, q_offset=0):
    """Differentiable attention: (B,Sq,H,hd) x (B,Sk,KVH,hd) -> (B,Sq,H,hd).

    q_offset (absolute position of q[0], decode with a KV cache) may be a
    python int or a traced int32 scalar; either way it does not trigger
    recompilation across decode steps."""
    s = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if not _use_pallas():
        if k.shape[1] > CHUNKED_THRESHOLD or \
                os.environ.get("REPRO_ATTN_IMPL") == "chunked":
            return ref.chunked_attention(q, k, v, causal=causal,
                                         window=window, scale=s,
                                         q_offset=q_offset)
        return ref.attention(q, k, v, causal=causal, window=window,
                             scale=s, q_offset=q_offset)
    return per_batch_shard(_make_flash(bool(causal), int(window), s),
                           q, k, v, jnp.asarray(q_offset, jnp.int32),
                           split=(True, True, True, False))
