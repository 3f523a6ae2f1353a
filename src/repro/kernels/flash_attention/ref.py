"""Pure-jnp oracle for causal (optionally sliding-window) GQA attention.

Shapes follow the framework convention:
  q: (B, S, H, hd)   k/v: (B, S, KVH, hd)   with H % KVH == 0.

The oracle materializes the (S, S) score matrix — fine for tests and for
CPU paper-scale runs; the Pallas kernel never does.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import jax


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: Optional[float] = None,
              q_offset: int = 0):
    """window > 0 -> sliding-window attention of that width.

    q_offset: absolute position of q[0] (for decode with KV cache the query
    sits at the end of the key sequence)."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    if scale is None:
        scale = hd ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # broadcast KV heads over the GQA group
    kf = jnp.repeat(kf, groups, axis=2)
    vf = jnp.repeat(vf, groups, axis=2)

    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)

    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      scale: Optional[float] = None, q_offset: int = 0,
                      block: int = 1024):
    """Flash-semantic attention in pure jnp: lax.scan over KV blocks with a
    running (max, normalizer, accumulator).

    This is the XLA-analyzable stand-in for the Pallas kernel on non-TPU
    backends: it has the kernel's O(S) memory profile, so
    memory_analysis() and cost_analysis() reflect the TPU execution plan
    rather than a materialized S^2 score tensor."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    if scale is None:
        scale = hd ** -0.5
    block = min(block, sk)
    if sk % block:
        return attention(q, k, v, causal=causal, window=window, scale=scale,
                         q_offset=q_offset)
    nblk = sk // block

    qf = q.astype(jnp.float32) * scale                    # (B,Sq,H,hd)
    kb = k.reshape(b, nblk, block, kvh, hd)
    vb = v.reshape(b, nblk, block, kvh, hd)
    q_pos = jnp.arange(sq) + q_offset

    def body(carry, inp):
        m_run, l_run, acc = carry
        idx, kc, vc = inp                                  # kc (B,blk,KVH,hd)
        kc = jnp.repeat(kc.astype(jnp.float32), groups, axis=2)
        vc = jnp.repeat(vc.astype(jnp.float32), groups, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc)
        k_pos = idx * block + jnp.arange(block)
        mask = jnp.ones((sq, block), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, -1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vc)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (jnp.arange(nblk), kb.transpose(1, 0, 2, 3, 4),
         vb.transpose(1, 0, 2, 3, 4)))
    l_f = jnp.where(l_f == 0.0, 1.0, l_f)
    out = acc / l_f[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
