"""Operations and bytes, counted from shapes.

Model FLOPs count what the mathematics requires, so the same work reads
the same whatever implements it:

* a frozen-base LoRA training token: the forward pass and the input
  gradient of every base matmul and of the tied LM head, attention scores
  and values under the layer's mask (forward 2 matmuls, backward 4), and
  the LoRA terms forward (2) and backward (4).  Frozen-weight gradients,
  recomputed ops and the C3 evaluation forward are left out.
* kernels: the pairs of query and key that the mask keeps, not the tiles
  visited; bytes are each operand and result once.  A backward kernel
  that keeps no probabilities must rebuild the scores, so its count is 5
  matmuls over the kept pairs.
"""

from __future__ import annotations

import numpy as np


def kept_keys(n, window=0):
    """Keys seen by the n query positions 0..n-1 of a causal row (within
    `window` keys when window > 0), summed: the kept (q, k) pairs."""
    n = np.asarray(n, np.int64)
    if window <= 0 or np.all(n <= window):
        return n * (n + 1) // 2
    w = np.int64(window)
    head = np.minimum(n, w)
    return head * (head + 1) // 2 + np.maximum(n - w, 0) * w


def mean_kept_pairs(s, windows):
    """Kept pairs of one length-s row, averaged over the layers."""
    return float(np.mean([kept_keys(s, w) for w in windows]))


def _matmul_macs_per_token(dims):
    d, ff = dims["d_model"], dims["d_ff"]
    return 4 * d * d + 2 * d * ff


def train_flops(dims, lora_ranks, real_lengths):
    """Model FLOPs of one training pass over rows with `real_lengths`
    real tokens each (pads cost nothing here).  lora_ranks: (L,) the
    effective rank of each layer (targets q, k, v, o)."""
    lens = np.asarray(real_lengths, np.int64).ravel()
    tokens = int(lens.sum())
    d, v, L = dims["d_model"], dims["vocab"], dims["layers"]
    base = 4 * L * _matmul_macs_per_token(dims)          # fwd 2 + dx 2
    head = 4 * d * v
    lora = 12 * 4 * d * int(np.sum(lora_ranks))           # 4 targets
    pairs = sum(int(np.sum(kept_keys(lens, w))) for w in dims["windows"])
    attn = 12 * d * pairs                                 # (2 + 4) matmuls
    return tokens * (base + head + lora) + attn


def prefill_flops(dims, n, lora_rank_sum):
    """Forward FLOPs of one prompt of n tokens, logits of the last only."""
    d, v = dims["d_model"], dims["vocab"]
    per_tok = 2 * dims["layers"] * _matmul_macs_per_token(dims) \
        + 4 * 4 * d * lora_rank_sum
    pairs = sum(int(kept_keys(n, w)) for w in dims["windows"])
    return n * per_tok + 4 * d * pairs + 2 * d * v


def decode_flops(dims, attended, lora_rank_sum):
    """Forward FLOPs of one decoded token that attends over `attended`
    positions (itself included) in every layer."""
    d, v = dims["d_model"], dims["vocab"]
    per_tok = 2 * dims["layers"] * _matmul_macs_per_token(dims) \
        + 4 * 4 * d * lora_rank_sum + 2 * d * v
    keys = sum(min(attended, w) if w > 0 else attended
               for w in dims["windows"])
    return per_tok + 4 * d * keys


def flash_fwd(rows, s, heads, head_dim, pairs, itemsize=4):
    """(flops, bytes) of one causal flash forward over `rows` sequences of
    length s with `pairs` kept pairs per row and head."""
    flops = 4 * rows * heads * head_dim * pairs
    act = rows * s * heads * head_dim * itemsize
    lse = rows * heads * s * 4
    return flops, 4 * act + lse                 # q, k, v in; o out


def flash_bwd(rows, s, heads, head_dim, pairs, itemsize=4):
    """(flops, bytes) of the flash backward: scores rebuilt, dP, dV, dQ,
    dK (5 matmuls over the kept pairs)."""
    flops = 10 * rows * heads * head_dim * pairs
    act = rows * s * heads * head_dim * itemsize
    lse = rows * heads * s * 4
    return flops, 8 * act + lse     # q k v o do in; dq dk dv out


def decode_attention(attended, heads, kv_heads, head_dim, itemsize=4):
    """(flops, bytes) of decode attention for one query row attending
    over `attended` cached positions: q K^T and p V; K and V read once."""
    flops = 4 * heads * head_dim * attended
    byts = 2 * kv_heads * head_dim * attended * itemsize \
        + 2 * heads * head_dim * itemsize
    return flops, byts


def roofline_seconds(flops, byts, peak_flops, peak_bw):
    """The least time the chip could take, and which bound it is."""
    tf, tb = flops / peak_flops, byts / peak_bw
    return (tf, "compute") if tf >= tb else (tb, "memory")
