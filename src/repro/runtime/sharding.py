"""Sharding rules: pytree -> PartitionSpec trees for the production mesh.

Scheme (DESIGN.md §5):
  * FSDP  — base weights sharded over the ("pod","data") axes on their
    d_model-like dimension; XLA inserts per-layer all-gathers inside the
    layer scan (weights are re-gathered per layer, never fully resident).
  * TP    — head/ffn/vocab dimensions sharded over "model".
  * EP    — MoE expert dimension sharded over "model" (attention stays TP).
  * Client axis — stacked per-client adapters shard their N dim over
    "data", aligning client groups with the data mesh axis.
  * Divisibility fallback — every rule is filtered through fit_spec(),
    which drops mesh axes that do not divide the corresponding dim (e.g.
    batch=1 long-context decode).

All functions take the *abstract* tree (ShapeDtypeStructs ok) — nothing
here touches real device memory, which is what the dry-run requires.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FSDP_AXES = ("pod", "data")
TP_AXIS = "model"
CLIENT_AXIS = "data"

# ---------------------------------------------------------------------------
# Round-state client-slot rules.
#
# The round engine's state dict mixes global leaves (server adapters,
# round counter) with per-client ones.  These tables are THE source of
# truth for which top-level keys carry a client axis and where — shared
# by the sharding constraints below (client axis -> the data mesh axis)
# and by runtime.population.PopulationStore (client axis -> per-pid
# slot rows), so the two can never disagree about what "per-client"
# means.

# (N, ...) leaves: the client axis leads.
STATE_CLIENT_VECTOR_KEYS = frozenset({
    "cuts", "step_budgets", "buffer_mask", "buffer_steps",
    "adapter_version", "rank_cut", "smashed_choice", "smashed_ef",
    "edge_assign",
})
# Trees of client-stacked adapter-shaped leaves ((Lg, N, din, r)): the
# client axis is axis 1.  opt_c mirrors client_adapters leaf-for-leaf
# except its step counter ("count"), which is (N,) after
# with_per_client_opt_steps and a global scalar before.
STATE_CLIENT_TREE_KEYS = frozenset({"client_adapters", "ef", "opt_c"})


def state_client_axis(path: Tuple[str, ...], ndim: int) -> Optional[int]:
    """Client-axis position of a round-state leaf at `path` (top-level
    key first), or None for global leaves."""
    if not path:
        return None
    top = path[0]
    if top in STATE_CLIENT_VECTOR_KEYS:
        return 0
    if top in STATE_CLIENT_TREE_KEYS:
        if path[-1] == "count":
            return 0 if ndim == 1 else None
        return 1 if ndim >= 2 else None
    return None


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", "?")))
                 for p in path)


def state_specs(state, mesh: Mesh):
    """PartitionSpec tree for the round-engine state: every client axis
    (state_client_axis) shards over the data mesh axis, everything else
    replicates.  fit_spec drops the axis when the cohort size does not
    divide it (divisibility fallback)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    specs = []
    for path, leaf in flat:
        nd = np.ndim(leaf)
        ax = state_client_axis(_path_keys(path), nd)
        if ax is None:
            logical = (None,) * nd
        else:
            logical = tuple(CLIENT_AXIS if i == ax else None
                            for i in range(nd))
        specs.append(fit_spec(np.shape(leaf), logical, mesh))
    return jax.tree.unflatten(treedef, specs)


def under_mesh(fn, mesh: Optional[Mesh]):
    """fn, traced with `mesh` as the context mesh (fn itself without a
    mesh): Pallas calls read it to run per shard (kernels.spmd)."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


def constrain_state(state, mesh: Optional[Mesh]):
    """with_sharding_constraint the round state's client axis over the
    data mesh axis (no-op without a mesh).  Called at engine entry and
    exit, this doubles as the jitted step's in/out shardings for the
    state argument."""
    if mesh is None:
        return state
    specs = state_specs(state, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)), state, specs)


def constrain_client_batch(batch, mesh: Optional[Mesh], *,
                           step_axis: bool = False):
    """with_sharding_constraint a client-stacked batch ((N, B, S) leaves,
    or (K, N, B, S) with step_axis=True under the local-steps engine):
    clients over the data axis, per-client batch over the remaining FSDP
    axes (batch_specs' client_dim=True rule)."""
    if mesh is None:
        return batch
    rest = tuple(a for a in FSDP_AXES if a != CLIENT_AXIS)

    def spec_of(leaf):
        nd = np.ndim(leaf)
        pre = (None,) if step_axis else ()
        logical = pre + (CLIENT_AXIS, rest)
        logical = logical + (None,) * (nd - len(logical))
        return fit_spec(np.shape(leaf), logical, mesh)

    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_of(x))), batch)


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return math.prod(_axis_size(mesh, n) for n in name)
    return mesh.shape[name] if name in mesh.shape else 1


def fit_spec(shape: Tuple[int, ...], spec: Tuple, mesh: Mesh) -> P:
    """Drop axes that are absent from the mesh or do not divide the dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        kept, prod = [], 1
        for a in axes:
            if a in mesh.shape and dim % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def _leaf_spec_for_path(path: str, ndim: int) -> Tuple:
    """Logical spec by parameter name; dims right-aligned to the leaf."""
    name = path.split("/")[-1]
    full: Tuple

    def pad(spec):
        return (None,) * (ndim - len(spec)) + tuple(spec)

    if name in ("tok",):
        return pad((TP_AXIS, FSDP_AXES))      # vocab TP, d FSDP
    if name in ("head",):
        return pad((FSDP_AXES, TP_AXIS))
    if name in ("pos", "enc_pos"):
        return pad((None, None))
    if name in ("wk", "wv", "xwk", "xwv"):
        # GQA KV projections: the head count rarely divides the TP axis,
        # so the out dim stays unsharded (the activations are replicated
        # across TP anyway); FSDP carries the weight bytes.
        return pad((FSDP_AXES, None))
    if name in ("wq", "xwq", "w_in", "w_gate",
                "in_proj", "router", "ws_in", "ws_gate"):
        return pad((FSDP_AXES, TP_AXIS))      # (.., d_in, d_out-TP)
    if name in ("wo", "xwo", "w_out", "out_proj", "ws_out"):
        return pad((TP_AXIS, FSDP_AXES))
    # MoE experts: EP over the TP axis; the FSDP axes shard the ff dim,
    # NOT d_model — a d-sharded expert weight would be all-gathered per
    # layer per microbatch (terabytes for 384-expert models), whereas
    # ff-sharding keeps weights resident and exchanges only
    # activation-sized tensors.
    if name in ("we_in", "we_gate"):
        return pad((TP_AXIS, None, FSDP_AXES))   # (L,E-EP,d,ff-FSDP)
    if name in ("we_out",):
        return pad((TP_AXIS, FSDP_AXES, None))   # (L,E-EP,ff-FSDP,d)
    if name in ("bq", "b_in"):
        return pad((TP_AXIS,))
    if name in ("conv_w", "conv_b"):
        return pad((TP_AXIS,)) if ndim <= 2 else pad((None, TP_AXIS))
    if name in ("A_log", "D", "dt_bias"):
        return pad((TP_AXIS,))
    # norms, biases, scalars: replicate
    return (None,) * ndim


def _tree_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "idx", "?")) for p in path]
        yield "/".join(str(k) for k in keys), leaf


def param_specs(params, mesh: Mesh):
    """PartitionSpec tree for model parameters."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        keys = "/".join(str(getattr(p, "key", "?")) for p in path)
        logical = _leaf_spec_for_path(keys, np.ndim(leaf))
        specs.append(fit_spec(np.shape(leaf), logical, mesh))
    return jax.tree.unflatten(treedef, specs)


def adapter_specs(adapters, mesh: Mesh, *, client_stacked: bool):
    """Adapters: {group:{target:{"A","B"}}}.

    Server adapters ((Lg, din, r)) are replicated (tiny); client-stacked
    adapters ((Lg, N, din, r)) shard N over the client/data axis."""
    def spec_of(leaf):
        nd = np.ndim(leaf)
        if client_stacked and nd >= 3:
            logical = (None, CLIENT_AXIS) + (None,) * (nd - 2)
        else:
            logical = (None,) * nd
        return fit_spec(np.shape(leaf), logical, mesh)

    return jax.tree.map(spec_of, adapters)


def batch_specs(batch, mesh: Mesh, *, client_dim: bool):
    """tokens/labels/mask ([N,]B,S[,d]) and frames/prefix embeddings."""
    def spec_of(leaf):
        nd = np.ndim(leaf)
        if client_dim:
            rest = tuple(a for a in FSDP_AXES if a != CLIENT_AXIS)
            logical = (CLIENT_AXIS, rest) + (None,) * (nd - 2)
        else:
            logical = (FSDP_AXES,) + (None,) * (nd - 1)
        return fit_spec(np.shape(leaf), logical, mesh)

    return jax.tree.map(spec_of, batch)


def cache_specs(cache, mesh: Mesh):
    """KV/SSM caches.

    KV leaves (Lg, B, Smax, KVH, hd): batch over FSDP axes when divisible;
    the sequence dim takes the model axis (sequence-parallel decode) —
    KV heads rarely divide a 16-way TP axis, sharded-S always does.
    SSM conv (Lg, B, W, C): C over model.  SSM state (Lg, B, H, P, N):
    H over model."""
    def spec_of(path: str, leaf):
        nd = np.ndim(leaf)
        name = path.split("/")[-1]
        if name == "len":
            return P()
        if name in ("k", "v", "xk", "xv"):
            # MUST match ShardingPolicy.cache_kv: sequence over the TP
            # axis (a mismatch makes XLA bounce the cache between layouts
            # every step — GBs of copies).
            return fit_spec(np.shape(leaf),
                            (None, FSDP_AXES, TP_AXIS, None, None), mesh)
        if name == "conv":
            return fit_spec(np.shape(leaf),
                            (None, FSDP_AXES) + (None,) * (nd - 3)
                            + (TP_AXIS,), mesh)
        if name == "state":
            return fit_spec(np.shape(leaf),
                            (None, FSDP_AXES, TP_AXIS) + (None,) * (nd - 3),
                            mesh)
        return P(*(None,) * nd)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        keys = "/".join(str(getattr(p, "key", "?")) for p in path)
        specs.append(spec_of(keys, leaf))
    return jax.tree.unflatten(treedef, specs)


def shardings_for(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))
