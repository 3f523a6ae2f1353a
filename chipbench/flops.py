"""Operations and bytes, counted from shapes.

Model FLOPs count what the mathematics requires, so the same work reads
the same whatever implements it:

* a frozen-base LoRA training token: the forward pass and the input
  gradient of every base matmul and of the LM head (the family's `macs`
  and `head_macs`; of routed experts the active ones), attention scores
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


def attn_groups(dims):
    """{(heads, kv_heads, qk_dim, v_dim): [window of each layer of that
    attention shape]}, from the family's per-layer attention."""
    out = {}
    for lay in dims["layer"]:
        a = lay["attn"]
        key = (a["heads"], a["kv_heads"], a["qk_dim"], a["v_dim"])
        out.setdefault(key, []).append(a["window"])
    return out


def _attn_macs(lay):
    """Multiply-adds per kept (query, key) pair: q k^T and p v."""
    a = lay["attn"]
    return a["heads"] * (a["qk_dim"] + a["v_dim"])


def _lora_macs(dims, ranks):
    """LoRA multiply-adds per token, forward: x A and (x A) B of every
    target of every layer at its rank."""
    return sum(int(r) * sum(di + do for di, do in lay["targets"].values())
               for r, lay in zip(ranks, dims["layer"]))


def _base_macs(dims):
    return sum(lay["macs"] for lay in dims["layer"])


def train_flops(dims, lora_ranks, real_lengths):
    """Model FLOPs of one training pass over rows with `real_lengths`
    real tokens each (pads cost nothing here).  lora_ranks: (L,) the
    effective rank of each layer."""
    lens = np.asarray(real_lengths, np.int64).ravel()
    tokens = int(lens.sum())
    base = 4 * _base_macs(dims)                         # fwd 2 + dx 2
    head = 4 * dims["head_macs"]
    lora = 6 * _lora_macs(dims, lora_ranks)             # fwd 2 + bwd 4
    attn = sum(6 * _attn_macs(lay)                      # (2 + 4) matmuls
               * int(np.sum(kept_keys(lens, lay["attn"]["window"])))
               for lay in dims["layer"])
    return tokens * (base + head + lora) + attn


def prefill_flops(dims, n, lora_ranks):
    """Forward FLOPs of one prompt of n tokens, logits of the last only."""
    per_tok = 2 * _base_macs(dims) + 2 * _lora_macs(dims, lora_ranks)
    attn = sum(2 * _attn_macs(lay) * int(kept_keys(n, lay["attn"]["window"]))
               for lay in dims["layer"])
    return n * per_tok + attn + 2 * dims["head_macs"]


def decode_flops(dims, attended, lora_ranks):
    """Forward FLOPs of one decoded token that attends over `attended`
    positions (itself included) in every layer."""
    per_tok = 2 * _base_macs(dims) + 2 * _lora_macs(dims, lora_ranks) \
        + 2 * dims["head_macs"]
    attn = 0
    for lay in dims["layer"]:
        w = lay["attn"]["window"]
        attn += 2 * _attn_macs(lay) * (min(attended, w) if w > 0
                                       else attended)
    return per_tok + attn


def flash_fwd(rows, s, heads, qk_dim, v_dim, pairs, itemsize=4):
    """(flops, bytes) of one causal flash forward over `rows` sequences of
    length s with `pairs` kept pairs per row and head: q k^T over qk_dim,
    p v over v_dim."""
    flops = 2 * rows * heads * (qk_dim + v_dim) * pairs
    act = rows * s * heads * (2 * qk_dim + 2 * v_dim) * itemsize
    lse = rows * heads * s * 4
    return flops, act + lse                     # q, k, v in; o out


def flash_bwd(rows, s, heads, qk_dim, v_dim, pairs, itemsize=4):
    """(flops, bytes) of the flash backward: scores rebuilt, dQ, dK over
    qk_dim; dP, dV over v_dim (5 matmuls over the kept pairs)."""
    flops = 2 * rows * heads * (3 * qk_dim + 2 * v_dim) * pairs
    act = rows * s * heads * (4 * qk_dim + 4 * v_dim) * itemsize
    lse = rows * heads * s * 4
    return flops, act + lse         # q k v o do in; dq dk dv out


def decode_attention(attended, heads, kv_heads, qk_dim, v_dim, itemsize=4):
    """(flops, bytes) of decode attention for one query row attending
    over `attended` cached positions: q K^T and p V; K and V read once."""
    flops = 2 * heads * (qk_dim + v_dim) * attended
    byts = kv_heads * (qk_dim + v_dim) * attended * itemsize \
        + heads * (qk_dim + v_dim) * itemsize
    return flops, byts


def roofline_seconds(flops, byts, peak_flops, peak_bw):
    """The least time the chip could take, and which bound it is."""
    tf, tb = flops / peak_flops, byts / peak_bw
    return (tf, "compute") if tf >= tb else (tb, "memory")
