"""The split boundary (paper C1) as a soft, mask-based structure.

A client with cut m owns flat layers [0, m); the server owns [m, M).  The
effective adapter used at layer l for client i's batch is

    eff[i, l] = client_mask[i, l] ? client_adapters[i, l]
                                  : server_adapters[l]

computed with `where` over stacked trees.  Because the mask is a traced
input, *every* cut configuration — including heterogeneous per-client cuts
and adaptive movement between rounds — runs in one compiled executable.

`smashed_constraint` marks the activation resharding boundary at the cut:
on a mesh this is where the paper's "smashed data transmission" (f2/f4)
bytes cross; XLA lowers the layout change to real collectives.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.core import lora as lora_lib
from repro.models.model import Model

Params = Dict[str, Any]


def client_layer_masks(flat_layers: int, cuts):
    """cuts (N,) -> (N, M) float {1=client-side, 0=server-side}."""
    layers = jnp.arange(flat_layers)
    return (layers[None, :] < jnp.asarray(cuts)[:, None]).astype(jnp.float32)


def group_masks(model: Model, masks):
    """(N, M) -> {group: (Lg, N, 1, 1)} broadcast-ready masks."""
    out = {}
    for g in model.groups:
        ids = jnp.asarray(g.layer_ids)
        sub = jnp.take(masks, ids, axis=1)       # (N, Lg)
        out[g.name] = jnp.moveaxis(sub, 1, 0)[..., None, None]
    return out


def _grad_scaled(x, scale):
    """Per-client gradient scaling on axis 1, forward-preserving.

    a*x + (1-a)*stop_gradient(x) has cotangent a * g; its forward value
    is x up to rounding, and at a == 1 it is x BITWISE (1.0*x = x;
    0.0*stop_gradient(x) is a sign-matched zero, and x + (+/-0 matching
    x's sign) = x under IEEE-754) — which is what pins the K == 1 path
    bit-identical when the server-step normalization is enabled."""
    a = scale.reshape((1, -1) + (1,) * (x.ndim - 2))
    return a * x + (1.0 - a) * jax.lax.stop_gradient(x)


def merge_adapters(model: Model, client_adapters: Params,
                   server_adapters: Params, cuts,
                   rank_cut=None, server_scale=None) -> Params:
    """Build the apply-ready effective adapter tree for a SplitFT step.

    client_adapters: rank-max tree with client axis (Lg, N, din, r).
    server_adapters: rank-max tree without client axis (Lg, din, r).
    Output leaves carry the client axis and are rank-masked + scaled with
    the per-client rank policy.  rank_cut: optional (N,) per-client
    rank-at-cut override (the co-controller's rank bucket assignment,
    state["rank_cut"]); None keeps the static LoRAConfig.r_cut.

    server_scale: optional (N,) per-client gradient scale applied to the
    SERVER adapters' contribution (forward-unchanged, see _grad_scaled).
    The local-steps/async engines pass 1/K_i so that a client running K_i
    inner steps pushes the same total gradient mass into the shared
    server adapters as a one-step client — without it, fast clients
    over-train the server side (ROADMAP carry).  None or all-ones is the
    legacy gradient bitwise."""
    masks = client_layer_masks(model.num_flat_layers, cuts)    # (N, M)
    gmasks = group_masks(model, masks)
    ranks = lora_lib.effective_ranks(model.num_flat_layers, cuts,
                                     model.arch.lora,
                                     r_cut=rank_cut)           # (N, M)

    merged: Params = {}
    for gname, targets in client_adapters.items():
        m = gmasks[gname]                                      # (Lg,N,1,1)
        merged[gname] = {}
        for tname, ad in targets.items():
            srv = server_adapters[gname][tname]
            srv_a = srv["A"][:, None]
            srv_b = srv["B"][:, None]
            if server_scale is not None:
                srv_a = _grad_scaled(srv_a, server_scale)
                srv_b = _grad_scaled(srv_b, server_scale)
            merged[gname][tname] = {
                "A": m * ad["A"] + (1.0 - m) * srv_a,
                "B": m * ad["B"] + (1.0 - m) * srv_b,
            }
    return lora_lib.mask_adapters(model, merged, ranks)


def serve_adapters(model: Model, client_adapters: Params,
                   server_adapters: Params, cuts, weights,
                   rank_cut=None) -> Params:
    """Global-model adapters for evaluation/serving (paper b4).

    Per flat layer: the FedAvg-weighted mix of the client copies (for
    clients that own the layer) and the server copy (for the rest).  With
    homogeneous cuts this reduces exactly to the paper's global model
    (client layers from the aggregate, server layers from the server).
    rank_cut: optional (N,) per-client rank-at-cut (see merge_adapters)."""
    masks = client_layer_masks(model.num_flat_layers, cuts)    # (N, M)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    ranks = lora_lib.effective_ranks(model.num_flat_layers, cuts,
                                     model.arch.lora,
                                     r_cut=rank_cut)           # (N, M)
    # weighted mean rank per layer -> serving scale stays consistent
    mean_ranks = jnp.sum(w[:, None] * ranks, axis=0)           # (M,)

    out: Params = {}
    for gname, targets in client_adapters.items():
        g = model.group_by_name[gname]
        ids = jnp.asarray(g.layer_ids)
        m = jnp.moveaxis(jnp.take(masks, ids, axis=1), 1, 0)   # (Lg, N)
        wm = m * w[None, :]                                    # client share
        ws = (1.0 - m) * w[None, :]                            # server share
        out[gname] = {}
        for tname, ad in targets.items():
            srv = server_adapters[gname][tname]
            mix_a = (jnp.einsum("ln,ln...->l...", wm, ad["A"])
                     + jnp.sum(ws, axis=1)[:, None, None] * srv["A"])
            mix_b = (jnp.einsum("ln,ln...->l...", wm, ad["B"])
                     + jnp.sum(ws, axis=1)[:, None, None] * srv["B"])
            out[gname][tname] = {"A": mix_a, "B": mix_b}
    return lora_lib.mask_adapters(model, out, mean_ranks.astype(jnp.int32))


def smashed_constraint(policy, x):
    """Resharding boundary at the cut layer (f2/f4).  The client phase and
    server phase share activation layout in this SPMD mapping, so this is
    an identity constraint hook — kept explicit so alternative server-phase
    layouts (§Perf experiments) plug in here."""
    return policy.act(x)
