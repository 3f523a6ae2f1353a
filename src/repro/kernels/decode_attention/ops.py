"""Public wrapper for flash-decode attention (inference only, no vjp)."""

from __future__ import annotations

import os
from typing import Optional

import jax

import jax.numpy as jnp

from repro.kernels.decode_attention import ref
from repro.kernels.decode_attention.kernel import (decode_attention_paged_pallas,
                                                  decode_attention_pallas)


def _use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def decode_attention(q, k, v, cache_len, *, scale: Optional[float] = None,
                     window: int = 0):
    """q: (B,H,hd); k/v cache: (B,S,KVH,hd); cache_len: (B,) -> (B,H,hd)."""
    s = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if not _use_pallas():
        return ref.decode_attention(q, k, v, cache_len, scale=s,
                                    window=window)
    interp = os.environ.get("REPRO_PALLAS_INTERPRET") == "1"
    bs = min(512, k.shape[1])
    return decode_attention_pallas(q, k, v, cache_len, scale=s, bs=bs,
                                   window=window, interpret=interp)


def decode_attention_paged(q, k_pool, v_pool, page_table, cache_len, *,
                           scale: Optional[float] = None, window: int = 0):
    """Paged-cache decode: q (B,H,hd); k/v pool (n_pages, ps, KVH, hd);
    page_table (B, P_max); cache_len (B,) -> (B,H,hd)."""
    s = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if not _use_pallas():
        return ref.decode_attention_paged(q, k_pool, v_pool, page_table,
                                          cache_len, scale=s, window=window)
    interp = os.environ.get("REPRO_PALLAS_INTERPRET") == "1"
    # clip so that even garbage entries past the allocated prefix are legal
    # pool indices for the scalar-prefetch index map (masked by cache_len)
    pt = jnp.clip(page_table, 0, k_pool.shape[0] - 1)
    return decode_attention_paged_pallas(q, k_pool, v_pool, pt, cache_len,
                                         scale=s, window=window,
                                         interpret=interp)
