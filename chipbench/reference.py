"""The plain reference: a decoder with LoRA, written from the
configuration file alone in straightforward `jax.numpy`.  This file holds
the SplitFT mechanics; the model half (embedding, one block, final norm
and head) is the family's (chipbench/families/).

It imports nothing of the program.  Weights, adapters and inputs come
from the harness (made from `--seed`), never from the program.  The
reference of record runs in float32 at `highest` matmul precision; the
control runs the same code with `dtype=bfloat16` (activations and
weights bf16, layer norms, softmax and the loss upcast to f32).

Training: one client's cut-split forward (client LoRA below the cut,
server LoRA from it on, rank `r_cut` on both sides of the cut), the
per-channel int8 round trip of the smashed activation and of its
gradient at the client's cut, the loss over real tokens, and the
gradients to both adapter sets.  Adam with global-norm clipping and
FedAvg over the clients that own each layer follow in `train_round`.

Serving: full-sequence logits of prompt plus served tokens, each row
through its own adapter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# building blocks


def attention(q, k, v, window, scale):
    """q, k, v (B, S, H, hd); causal, and within `window` keys when the
    (traced) window is positive."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    keep = (ki <= qi) & ((window <= 0) | (qi - ki < window))
    scores = jnp.where(keep, scores, -jnp.inf)
    p = jax.nn.softmax(scores, -1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def lora_linear(x, w, b, ad):
    """x W (+ b) (+ scale (x A) B); ad = (A, B, scale) or None."""
    y = x @ w
    if b is not None:
        y = y + b
    if ad is not None:
        a, bb, sc = ad
        y = y + (sc * ((x @ a) @ bb)).astype(y.dtype)
    return y


def layer_groups(dims):
    """{group: [its layers, in order]}: the program's layer stacks."""
    out = {}
    for l, lay in enumerate(dims["layer"]):
        out.setdefault(lay["group"], []).append(l)
    return out


def cast(tree, dtype):
    return jax.tree.map(
        lambda t: t.astype(dtype) if jnp.issubdtype(t.dtype, jnp.floating)
        else t, tree)


# ---------------------------------------------------------------------------
# the smashed channel: per-channel symmetric int8 of one message


def int8_roundtrip(x):
    """x (..., d): one message; scale per channel over all its tokens."""
    xf = x.astype(jnp.float32)
    d = x.shape[-1]
    flat = xf.reshape(-1, d)
    scale = jnp.maximum(jnp.max(jnp.abs(flat), 0), 1e-12) / 127.0
    q = jnp.clip(jnp.round(flat / scale), -127, 127)
    return (q * scale).reshape(x.shape).astype(x.dtype)


@jax.custom_vjp
def smashed(x):
    """The uplink round trip; its gradient takes the same round trip on
    the way down."""
    return int8_roundtrip(x)


smashed.defvjp(lambda x: (int8_roundtrip(x), None),
               lambda _, g: (int8_roundtrip(g),))


# ---------------------------------------------------------------------------
# training


def layer_ranks(dims, lora, cut):
    """(L,) effective ranks for a client whose cut is `cut` (traced)."""
    ls = jnp.arange(dims["layers"])
    at_cut = (ls == cut - 1)
    if lora["two_side_cut"]:
        at_cut = at_cut | (ls == cut)
    return jnp.where(at_cut, lora["r_cut"], lora["r_others"])


def static_ranks(dims, lora):
    """[effective rank of each layer] at the configured cut."""
    cut = lora["cut_layer"]
    sides = (cut - 1, cut) if lora["two_side_cut"] else (cut - 1,)
    return [lora["r_cut"] if l in sides else lora["r_others"]
            for l in range(dims["layers"])]


def client_loss(params, cad, sad, batch, cut, *, dims, lora, compress):
    """One client's mean next-token loss over its real tokens.

    cad / sad: {group: {target: {"A": (L_g, d_in, r), "B": (L_g, r,
    d_out)}}} client and server adapters; layer l < cut takes the
    client's, the rest the server's.  batch: tokens, labels, loss_mask,
    each (B, S)."""
    fam = dims["family"]
    dt = jax.tree.leaves(params)[0].dtype
    x = fam.embed(params, batch["tokens"], dims)
    ranks = layer_ranks(dims, lora, cut)
    r_max = lora["r_others"]
    for l, lay in enumerate(dims["layer"]):
        own = l < cut
        rk = ranks[l]
        cmask = (jnp.arange(r_max) < rk).astype(dt)
        sc = (lora["alpha"] / rk).astype(dt)
        c, s, j = cad[lay["group"]], sad[lay["group"]], lay["index"]
        ads = {}
        for t in lay["targets"]:
            a = jnp.where(own, c[t]["A"][j], s[t]["A"][j]) * cmask
            b = jnp.where(own, c[t]["B"][j], s[t]["B"][j]) * cmask[:, None]
            ads[t] = (a, b, sc)
        x = fam.block(x, params, l, ads, dims)
        if compress == "int8":
            x = jnp.where(l == cut - 1, smashed(x), x)
    logits = fam.head(params, x, dims)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    m = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum((lse - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)


@functools.partial(jax.jit, static_argnames=("dims_key", "dtype_name"))
def _client_grad(params, cad, sad, batch, cut, *, dims_key, dtype_name):
    dims, lora, compress = _STATIC[dims_key]
    dtype = jnp.dtype(dtype_name)
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        params, cad, sad = cast((params, cad, sad), dtype)
        f = functools.partial(client_loss, dims=dims, lora=lora,
                              compress=compress)
        loss, (gc, gs) = jax.value_and_grad(f, argnums=(1, 2))(
            params, cad, sad, batch, cut)
    return loss, cast(gc, jnp.float32), cast(gs, jnp.float32)


_STATIC = {}


def _static_key(dims, lora, compress):
    key = repr((sorted((k, repr(v)) for k, v in dims.items()),
                sorted(lora.items()), compress))
    _STATIC[key] = (dims, lora, compress)
    return key


def round_grads(params, cad, sad, batch, cuts, wl, *, dims, lora, compress,
                dtype=jnp.float32, devices=None):
    """Loss sum_i wl_i loss_i and its gradients, one client at a time;
    client i runs on devices[i % len(devices)] (its inputs are put
    there), so on several chips the clients run side by side.

    cad: client adapters {g: {t: {"A": (L_g, N, d_in, r), ...}}}; batch
    arrays (N, B, S).  Returns (total, per-client losses, g_cad,
    g_sad)."""
    key = _static_key(dims, lora, compress)
    devices = devices or [None]
    home = jax.tree.leaves(params)[0].devices().pop()
    here = [params if d is None or d == home else jax.device_put(params, d)
            for d in devices]
    n = len(cuts)
    outs = []
    for i in range(n):
        d = devices[i % len(devices)]
        put = (lambda t: t) if d is None else \
            (lambda t, d=d: jax.device_put(t, d))
        ci = put(jax.tree.map(lambda t: t[:, i], cad))
        bi = put({k: jnp.asarray(v[i]) for k, v in batch.items()})
        outs.append(_client_grad(here[i % len(devices)], ci, put(sad), bi,
                                 put(jnp.int32(cuts[i])), dims_key=key,
                                 dtype_name=jnp.dtype(dtype).name))
    back = (lambda t: t) if home is None else \
        (lambda t: jax.device_put(t, home))
    losses = jnp.stack([back(o[0]) for o in outs])
    g_cad = jax.tree.map(lambda *gs: jnp.stack(gs, 1),
                         *[jax.tree.map(lambda g, w=wl[i]: w * back(g), o[1])
                           for i, o in enumerate(outs)])
    g_sad = jax.tree.map(jnp.zeros_like, sad)
    for i, o in enumerate(outs):
        g_sad = jax.tree.map(lambda a, g, w=wl[i]: a + w * back(g), g_sad,
                             o[2])
    return jnp.sum(wl * losses), losses, g_cad, g_sad


def clip_by_global_norm(g, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda x: x * s, g)


def adam(p, g, st, *, lr, b1, b2, eps, clip):
    """One Adam step with global-norm clipping; st = (m, v, count)."""
    g = clip_by_global_norm(g, clip)
    m, v, t = st
    t = t + 1
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / c1)
                     / (jnp.sqrt(v_ / c2) + eps), p, m, v)
    return p, (m, v, t), g


def fedavg(cad, sad, cuts, w, dims):
    """Each layer's client rows -> the weighted mean over the clients that
    own it; rows of clients that do not own it mirror the server."""
    out = {}
    for g, ls in layer_groups(dims).items():
        own = (np.asarray(ls)[:, None] < np.asarray(cuts)[None, :])  # (L_g, N)
        mu = own * np.asarray(w, np.float64)[None, :]
        den = np.maximum(mu.sum(1), 1e-9)
        own_j = jnp.asarray(own, jnp.float32)
        mu_j = jnp.asarray(mu / den[:, None], jnp.float32)

        def one(c, s, own=own, own_j=own_j, mu_j=mu_j):
            agg = jnp.einsum("ln,ln...->l...", mu_j, c)
            o = own_j.reshape(own.shape + (1,) * (c.ndim - 2))
            return o * agg[:, None] + (1 - o) * s[:, None]

        out[g] = jax.tree.map(one, cad[g], sad[g])
    return out


def train_round(params, state, batch, cuts, weights, active, *, dims, lora,
                opt, compress, dtype=jnp.float32, groups=1, devices=None):
    """One SplitFT round: forward/backward of every client, Adam on both
    adapter sets, FedAvg.  state: dict with cad, sad, opt_c, opt_s
    ((m, v, t) each).  groups > 1 averages each run of N / groups clients
    on its own (what FedAvg gives when the exchange between chips is
    left out).  Returns (state', total loss, clipped client and server
    gradients)."""
    w = np.asarray(weights, np.float64) * np.asarray(active, np.float64)
    wl = jnp.asarray(w / max(w.sum(), 1e-9), jnp.float32)
    total, _, g_c, g_s = round_grads(params, state["cad"], state["sad"], batch,
                                     cuts, wl, dims=dims, lora=lora,
                                     compress=compress, dtype=dtype,
                                     devices=devices)
    kw = dict(lr=opt["lr"], b1=opt["beta1"], b2=opt["beta2"], eps=opt["eps"],
              clip=opt["grad_clip"])
    cad, opt_c, g_c = adam(state["cad"], g_c, state["opt_c"], **kw)
    sad, opt_s, g_s = adam(state["sad"], g_s, state["opt_s"], **kw)
    k = len(cuts) // groups
    parts = [fedavg(jax.tree.map(lambda c: c[:, g * k:(g + 1) * k], cad),
                    sad, cuts[g * k:(g + 1) * k], w[g * k:(g + 1) * k],
                    dims) for g in range(groups)]
    cad = jax.tree.map(lambda *p: jnp.concatenate(p, 1), *parts)
    new = dict(cad=cad, sad=sad, opt_c=opt_c, opt_s=opt_s)
    return new, total, g_c, g_s


def init_opt(tree):
    z = jax.tree.map(jnp.zeros_like, tree)
    return (z, jax.tree.map(jnp.zeros_like, tree), 0)


# ---------------------------------------------------------------------------
# serving


def serve_logits(params, pool, ids, tokens, *, dims, dtype=jnp.float32):
    """Logits (R, T, V) of rows `tokens` (R, T), row r through adapter
    ids[r] of the pool {group: {target: {"A": (L_g, n, d_in, r), "B":
    (L_g, n, r, d_out), "scale": (L_g, n)}}}."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        params, pool = cast((params, pool), dtype)
        fam = dims["family"]
        x = fam.embed(params, tokens, dims)
        for l, lay in enumerate(dims["layer"]):
            p, j = pool[lay["group"]], lay["index"]
            ads = {t: (p[t]["A"][j][ids], p[t]["B"][j][ids],
                       p[t]["scale"][j][ids][:, None, None])
                   for t in lay["targets"]}
            x = fam.block(x, params, l, ads, dims)
        return fam.head(params, x, dims)
