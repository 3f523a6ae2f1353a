"""Weights, adapters and adapter pools made by the harness from `--seed`.

Each tree is made on the device in one jitted call, in the layout the
program takes (layer-stacked, `dec` group), from the configuration
file's sizes alone.  The program and the reference are both handed
these arrays, so the reference takes nothing that the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import TARGETS


def base_shapes(dims):
    """{path: shape} of the base weights of a GPT-style model."""
    d, L, ff = dims["d_model"], dims["layers"], dims["d_ff"]
    s = {"embed/tok": (dims["vocab"], d), "embed/pos": (dims["positions"], d),
         "final_norm/scale": (d,), "final_norm/bias": (d,),
         "dec/norm1/scale": (L, d), "dec/norm1/bias": (L, d),
         "dec/norm2/scale": (L, d), "dec/norm2/bias": (L, d),
         "dec/wq": (L, d, d), "dec/wk": (L, d, d), "dec/wv": (L, d, d),
         "dec/wo": (L, d, d), "dec/w_in": (L, d, ff), "dec/w_out": (L, ff, d),
         "dec/b_in": (L, ff), "dec/b_out": (L, d)}
    if dims["qkv_bias"]:
        s.update({"dec/bq": (L, d), "dec/bk": (L, d), "dec/bv": (L, d)})
    if dims["out_bias"]:
        s["dec/bo"] = (L, d)
    return s


def _nest(flat):
    out = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def _draw(key, path, shape, dtype):
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":                       # layer-norm gains
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    if leaf in ("tok", "pos"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    if leaf.startswith("b") or path.endswith("/bias"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5


def make_base(dims, key, dtype=jnp.float32):
    shapes = base_shapes(dims)
    paths = sorted(shapes)

    def build(k):
        ks = jax.random.split(k, len(paths))
        return _nest({p: _draw(kk, p, shapes[p], dtype)
                      for p, kk in zip(paths, ks)})

    return jax.jit(build)(key)


def make_train_adapters(dims, lora, n_clients, key, dtype=jnp.float32):
    """(client_adapters, server_adapters) in the program's layout at the
    start of training: A ~ N(0, 1/r), B = 0, per client and for the
    server."""
    d, L, r = dims["d_model"], dims["layers"], lora["r_others"]

    def build(k):
        kc, ks = jax.random.split(k)
        cad, sad = {}, {}
        for i, t in enumerate(TARGETS):
            cad[t] = {"A": jax.random.normal(jax.random.fold_in(kc, i),
                                             (L, n_clients, d, r), dtype)
                      * r ** -0.5,
                      "B": jnp.zeros((L, n_clients, r, d), dtype)}
            sad[t] = {"A": jax.random.normal(jax.random.fold_in(ks, i),
                                             (L, d, r), dtype) * r ** -0.5,
                      "B": jnp.zeros((L, r, d), dtype)}
        return {"dec": cad}, {"dec": sad}

    return jax.jit(build)(key)


def make_pool(dims, lora, n_adapters, key, dtype=jnp.float32):
    """A serving pool of trained-looking adapters: every A and B drawn,
    rank r_cut (masked slots) with scale alpha / r_cut on the two layers
    around the configured cut, rank r_others elsewhere, as
    `merge_adapters` leaves a client's personalised adapter."""
    d, L, r = dims["d_model"], dims["layers"], lora["r_others"]
    cut = lora["cut_layer"]
    ranks = [lora["r_cut"] if l in (cut - 1, cut) else r for l in range(L)]
    rmask = (jnp.arange(r)[None, :] < jnp.asarray(ranks)[:, None]).astype(
        dtype)                                                  # (L, r)
    scale = jnp.broadcast_to(
        (lora["alpha"] / jnp.asarray(ranks, jnp.float32))[:, None],
        (L, n_adapters))

    def build(k):
        pool = {}
        for i, t in enumerate(TARGETS):
            ka, kb = jax.random.split(jax.random.fold_in(k, i))
            a = jax.random.normal(ka, (L, n_adapters, d, r), dtype) * r ** -0.5
            b = 0.02 * jax.random.normal(kb, (L, n_adapters, r, d), dtype)
            pool[t] = {"A": a * rmask[:, None, None, :],
                       "B": b * rmask[:, None, :, None],
                       "scale": scale}
        return {"dec": pool}

    return jax.jit(build)(key)


def check_layout(mine, theirs, what):
    """Raise unless two trees have the same paths, shapes and dtypes."""
    a = jax.tree_util.tree_flatten_with_path(mine)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    fa = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in a}
    fb = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in b}
    if fa != fb:
        diff = sorted(set(fa.items()) ^ set(fb.items()))
        raise ValueError(f"{what}: the harness's tree differs from the "
                         f"program's: {diff[:8]}")
