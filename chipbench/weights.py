"""Weights, adapters and adapter pools made by the harness from `--seed`.

Each tree is made on the device in one jitted call, in the layout the
program takes (layer-stacked, in the family's groups), from the
configuration file's sizes alone.  The program and the reference are
both handed these arrays, so the reference takes nothing that the
program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import layer_groups, static_ranks


def _nest(flat):
    out = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def make_base(dims, key, dtype=jnp.float32):
    fam = dims["family"]
    shapes = fam.base_shapes(dims)
    paths = sorted(shapes)

    def build(k):
        ks = jax.random.split(k, len(paths))
        return _nest({p: fam.draw(kk, p, shapes[p], dtype)
                      for p, kk in zip(paths, ks)})

    return jax.jit(build)(key)


def adapter_targets(dims):
    """[(group, target, its layers, d_in, d_out)] in the program's layout,
    in the order the adapter makers fold their keys."""
    return [(g, t, ls, *io) for g, ls in layer_groups(dims).items()
            for t, io in dims["layer"][ls[0]]["targets"].items()]


def make_train_adapters(dims, lora, n_clients, key, dtype=jnp.float32):
    """(client_adapters, server_adapters) in the program's layout at the
    start of training: A ~ N(0, 1/r), B = 0, per client and for the
    server."""
    r = lora["r_others"]

    def build(k):
        kc, ks = jax.random.split(k)
        cad, sad = {}, {}
        for i, (g, t, ls, din, dout) in enumerate(adapter_targets(dims)):
            n = len(ls)
            cad.setdefault(g, {})[t] = {
                "A": jax.random.normal(jax.random.fold_in(kc, i),
                                       (n, n_clients, din, r), dtype)
                * r ** -0.5,
                "B": jnp.zeros((n, n_clients, r, dout), dtype)}
            sad.setdefault(g, {})[t] = {
                "A": jax.random.normal(jax.random.fold_in(ks, i),
                                       (n, din, r), dtype) * r ** -0.5,
                "B": jnp.zeros((n, r, dout), dtype)}
        return cad, sad

    return jax.jit(build)(key)


def make_pool(dims, lora, n_adapters, key, dtype=jnp.float32):
    """A serving pool of trained-looking adapters: every A and B drawn,
    rank r_cut (masked slots) with scale alpha / r_cut on the layers
    around the configured cut, rank r_others elsewhere, as
    `merge_adapters` leaves a client's personalised adapter."""
    r = lora["r_others"]
    ranks = np.asarray(static_ranks(dims, lora))

    def build(k):
        pool = {}
        for i, (g, t, ls, din, dout) in enumerate(adapter_targets(dims)):
            rk = ranks[ls]
            rmask = (jnp.arange(r)[None, :] < jnp.asarray(rk)[:, None]
                     ).astype(dtype)                           # (L_g, r)
            n = len(ls)
            ka, kb = jax.random.split(jax.random.fold_in(k, i))
            a = jax.random.normal(ka, (n, n_adapters, din, r), dtype) \
                * r ** -0.5
            b = 0.02 * jax.random.normal(kb, (n, n_adapters, r, dout), dtype)
            pool.setdefault(g, {})[t] = {
                "A": a * rmask[:, None, None, :],
                "B": b * rmask[:, None, :, None],
                "scale": jnp.broadcast_to(
                    (lora["alpha"] / jnp.asarray(rk, jnp.float32))[:, None],
                    (n, n_adapters))}
        return pool

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
