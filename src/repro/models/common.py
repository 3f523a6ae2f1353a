"""Shared model primitives: norms, LoRA-aware dense layers, RoPE, sharding.

All models are pure-functional: parameters are pytrees of jnp arrays, apply
functions are stateless.  LoRA adapters are carried in a *separate* tree from
the (frozen) base parameters so that the SplitFT round engine can aggregate,
compress, and ship adapters without touching base weights.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Sharding policy — "phase sharding" for the SplitFT TPU mapping.
#
# The policy names logical axes; `constrain` is a no-op when no policy is
# active (CPU tests / single-device runs).


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Maps logical tensor axes onto mesh axes via with_sharding_constraint.

    Two activation layouts flow through the models:
      * client layout (SplitFT training): leading client axis N, i.e.
        (N, B, S, ...) with N sharded over `client_axis` and B over the
        remaining batch axes;
      * serve layout: (B, S, ...) with B sharded over all batch axes.
    The helpers dispatch on tensor rank, so block code stays layout-free.
    """

    mesh: Any = None                      # jax.sharding.Mesh | None
    batch_axes: Tuple[str, ...] = ("pod", "data")
    model_axis: str = "model"
    client_axis: str = "data"             # mesh axis carrying client groups
    client_mode: bool = False             # activations carry a leading N dim
    seq_shard: bool = False               # sequence parallelism: residual
                                          # stream seq dim sharded over the
                                          # TP axis between blocks (XLA
                                          # inserts the SP all-gather /
                                          # reduce-scatter pair per block)

    def _axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names) if self.mesh is not None else ()

    def spec(self, *axes) -> Optional[P]:
        """Build a PartitionSpec keeping only axes present in the mesh."""
        if self.mesh is None:
            return None
        present = set(self._axes())

        def keep(a):
            if a is None:
                return None
            if isinstance(a, tuple):
                sub = tuple(x for x in a if x in present)
                return sub if sub else None
            return a if a in present else None

        return P(*[keep(a) for a in axes])

    def constrain(self, x, *axes):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(*axes)))

    # -- layout helpers ------------------------------------------------------
    def _batch_specs(self, n_lead: int):
        """Specs for the leading batch-like dims.

        n_lead == 2 -> (client, batch): (client_axis, other batch axes)
        n_lead == 1 -> (batch,): all batch axes together."""
        if n_lead == 2:
            rest = tuple(a for a in self.batch_axes if a != self.client_axis)
            return (self.client_axis, rest)
        return (self.batch_axes,)

    # Logical shorthands -----------------------------------------------------
    def act(self, x):
        """([N,]B,S,d) activations — batch-sharded; with seq_shard the
        sequence dim additionally takes the TP axis (Korthikanti-style
        sequence parallelism — norms/residuals run on 1/TP of the tokens,
        which is also what bounds the fp32 norm upcasts in HBM)."""
        lead = self._batch_specs(x.ndim - 2)
        seq_ax = None
        if self.seq_shard and self.mesh is not None \
                and self.model_axis in self.mesh.shape \
                and x.shape[-2] % self.mesh.shape[self.model_axis] == 0:
            seq_ax = self.model_axis
        return self.constrain(x, *lead, seq_ax, None)

    def heads(self, x):
        """([N,]B,S,H,hd) — heads TP-sharded.

        Non-divisible head counts >= the axis size (e.g. 24 or 40 heads
        on 16-way TP) use XLA's padded sharding: <=2x padding waste vs
        16x replication otherwise.  Head counts below the axis size (GQA
        KV heads) stay replicated."""
        lead = self._batch_specs(x.ndim - 3)
        ax = self.model_axis
        if self.mesh is not None and ax in self.mesh.shape:
            size = self.mesh.shape[ax]
            h = x.shape[-2]
            if h % size != 0 and h < size:
                ax = None
        return self.constrain(x, *lead, None, ax, None)

    def ffn(self, x):
        """([N,]B,S,ff) — hidden dim TP-sharded."""
        lead = self._batch_specs(x.ndim - 2)
        return self.constrain(x, *lead, None, self.model_axis)

    def _group_spec(self):
        if self.client_mode:
            rest = tuple(a for a in self.batch_axes if a != self.client_axis)
            return (self.client_axis,) + rest
        return self.batch_axes

    def experts(self, x):
        """(G,E,C,d) dispatched MoE tensor — experts over the model axis.

        G is the flattened ([N,]B[,seq-groups]) group dim; in client mode
        the client axis is major in the flattening, so it leads."""
        return self.constrain(x, self._group_spec(), self.model_axis,
                              None, None)

    def moe_dispatch(self, t):
        """(G,T,E,C) one-hot dispatch/combine tensors: G batch-sharded,
        E expert-sharded.  Without this constraint XLA replicates them —
        at 384 experts that is tens of GiB per layer."""
        return self.constrain(t, self._group_spec(), None, self.model_axis,
                              None)

    def logits(self, x):
        """([N,]B,S,V) — vocab TP-sharded."""
        lead = self._batch_specs(x.ndim - 2)
        return self.constrain(x, *lead, None, self.model_axis)

    def cache_kv(self, t):
        """KV cache ([N,]B,Smax,KVH,hd): SEQUENCE-sharded over the TP axis
        (sequence-parallel decode).  Seq-sharding is uniform across all
        archs (head counts rarely divide the axis, and a heads-sharded
        cache bounces layouts against the seq-blocked decode scan).
        Must be re-asserted INSIDE the computation after every cache
        update, or XLA propagates the replicated update sharding through
        the layer scan (N layers x replicated KV = OOM)."""
        if self.mesh is None or self.model_axis not in self.mesh.shape:
            return t
        size = self.mesh.shape[self.model_axis]
        lead = self._batch_specs(t.ndim - 3)
        if t.shape[-3] % size == 0:
            return self.constrain(t, *lead, self.model_axis, None, None)
        return self.constrain(t, *lead, None, None, None)


NO_SHARDING = ShardingPolicy(mesh=None)


# ---------------------------------------------------------------------------
# Initializers


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = (1.0 / d_in) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), dtype) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), dtype) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms


def init_norm(d: int, *, bias: bool, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if bias:
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x, *, kind: str, eps: float):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations


@functools.partial(jax.checkpoint, static_argnums=(2,))
def activate(x, gate, kind: str):
    """Apply activation. `gate` is the gate branch for GLU variants (or None).

    Checkpointed: under autodiff the backward keeps only the inputs (the
    MLP's pre-activations) and recomputes the elementwise internals from
    them, so a scanned layer stack writes one `d_ff`-wide residual per
    layer, not one per internal of the activation.  Un-differentiated it
    lowers inline."""
    if kind == "swiglu":
        return jax.nn.silu(gate) * x
    if kind == "geglu":
        return jax.nn.gelu(gate) * x
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)


def is_glu(kind: str) -> bool:
    return kind in ("swiglu", "geglu")


# ---------------------------------------------------------------------------
# LoRA-aware dense application
#
# adapter = {"A": (d_in, r), "B": (r, d_out), "scale": scalar} or None.
# The fused Pallas kernel path is selected in repro.kernels.lora_matmul.ops.


def init_lora(key, d_in: int, d_out: int, r: int, alpha: float,
              dtype=jnp.float32) -> Params:
    """Paper init: A ~ N(0, 1/r), B = 0 so the adapter starts as identity."""
    a = jax.random.normal(key, (d_in, r), dtype) * (1.0 / max(r, 1)) ** 0.5
    return {
        "A": a.astype(dtype),
        "B": jnp.zeros((r, d_out), dtype),
        "scale": jnp.asarray(alpha / max(r, 1), dtype=jnp.float32),
    }


def lora_dense(x, w, b=None, adapter: Optional[Params] = None):
    """y = x @ W (+ b) (+ scale * (x @ A) @ B).

    lora_only: base weights are frozen in this codebase (LoRA fine-tuning),
    so the dW = x^T g backward term is skipped entirely."""
    from repro.kernels.lora_matmul import ops as lora_ops
    if adapter is not None:
        y = lora_ops.lora_matmul(x, w, adapter["A"], adapter["B"],
                                 adapter["scale"], lora_only=True)
    else:
        y = x @ w
    if b is not None:
        y = y + b
    return y


def adapter_num_params(adapter: Params) -> int:
    return adapter["A"].size + adapter["B"].size


# ---------------------------------------------------------------------------
# Rotary position embeddings


def rope_angles(positions, head_dim: int, theta: float):
    """positions: (...,) int32 -> cos/sin of shape (..., head_dim // 2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., T, H, hd); cos/sin: (..., T, hd//2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss (vocab-sharded-safe cross entropy)


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE.  Written so a vocab-sharded logits tensor reduces
    without materializing a one-hot: max/logsumexp/select all reduce over the
    vocab axis and fuse under XLA."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    vocab = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    correct = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - correct
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def token_accuracy(logits, labels, mask=None):
    pred = jnp.argmax(logits, axis=-1)
    hit = (pred == labels).astype(jnp.float32)
    if mask is None:
        return jnp.mean(hit)
    mask = mask.astype(jnp.float32)
    return jnp.sum(hit * mask) / jnp.maximum(jnp.sum(mask), 1.0)
