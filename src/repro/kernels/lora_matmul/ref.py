"""Pure-jnp oracle for the fused LoRA matmul.

y = x @ W + scale * (x @ A) @ B

This is the semantics contract for the Pallas kernel; it is also the
execution path on CPU (tests, paper-scale experiments).
"""

from __future__ import annotations

import jax.numpy as jnp


def lora_matmul(x, w, a, b, scale):
    """x: (..., K); w: (K, N); a: (K, r); b: (r, N); scale: scalar."""
    base = jnp.einsum("...k,kn->...n", x, w)
    xa = jnp.einsum("...k,kr->...r", x, a)
    delta = jnp.einsum("...r,rn->...n", xa, b)
    return base + scale.astype(base.dtype) * delta


def lora_matmul_indexed(x, w, a_pool, b_pool, scale, ids):
    """Multi-adapter serving path: each leading row picks its own adapter.

    x: (B, ..., K); w: (K, N); a_pool: (P, K, r); b_pool: (P, r, N);
    scale: (P,); ids: (B,) int32 adapter index per row.  Rank
    heterogeneity across the pool is expressed by masked rank slots
    (zeroed A columns / B rows past each adapter's effective rank), the
    same idiom as state["rank_cut"] in training."""
    base = jnp.einsum("...k,kn->...n", x, w)
    a = jnp.take(a_pool, ids, axis=0)                   # (B, K, r)
    b = jnp.take(b_pool, ids, axis=0)                   # (B, r, N)
    s = jnp.take(scale.astype(jnp.float32), ids, axis=0)
    xa = jnp.einsum("b...k,bkr->b...r", x, a)
    delta = jnp.einsum("b...r,brn->b...n", xa, b)
    extra = (1,) * (x.ndim - 1)
    return base + s.reshape(s.shape[:1] + extra).astype(base.dtype) \
        * delta.astype(base.dtype)
