"""Pure-jnp oracles for the Mamba2 SSD (state-space duality) scan.

Semantics (per head h, state size N, head dim P):
  a_t   = dt_t * A_h                       (A_h < 0: log-decay per step)
  h_t   = exp(a_t) * h_{t-1} + dt_t * (x_t outer B_t)      h: (P, N)
  y_t   = C_t . h_t                        (contract N)

Two oracles:
  * ssd_sequential — the literal per-timestep recurrence (ground truth).
  * ssd_chunked    — the SSD chunked algorithm (intra-chunk quadratic part
    + inter-chunk state carry), the same math the Pallas kernel implements
    and the CPU execution path.

Shapes: x (B,S,H,P), dt (B,S,H) positive, A (H,) negative,
        Bm/C (B,S,G,N) with G | H.  Returns y (B,S,H,P) and final state
        (B,H,P,N) when requested.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _expand_groups(t, h):
    """(B,S,G,N) -> (B,S,H,N) by repeating each group over its heads."""
    g = t.shape[2]
    return jnp.repeat(t, h // g, axis=2)


def ssd_sequential(x, dt, a, bm, c, h0=None, *, return_state: bool = False):
    b, s, h, p = x.shape
    n = bm.shape[-1]
    bm = _expand_groups(bm, h).astype(jnp.float32)
    cm = _expand_groups(c, h).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)

    state = (jnp.zeros((b, h, p, n), jnp.float32) if h0 is None
             else h0.astype(jnp.float32))

    def step(state, inp):
        xt, dtt, bt, ct = inp            # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(dtt * af)[..., None, None]
        upd = dtt[..., None, None] * xt[..., :, None] * bt[..., None, :]
        state = decay * state + upd
        yt = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, yt

    xs = (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
          bm.transpose(1, 0, 2, 3), cm.transpose(1, 0, 2, 3))
    state, ys = jax.lax.scan(step, state, xs)
    y = ys.transpose(1, 0, 2, 3).astype(x.dtype)
    if return_state:
        return y, state.astype(x.dtype)
    return y


def ssd_chunked(x, dt, a, bm, c, h0=None, *, chunk: int = 256,
                return_state: bool = False):
    """SSD chunked algorithm — matches ssd_sequential to fp32 tolerance."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    nc = s // chunk
    q = chunk

    bm = _expand_groups(bm, h).astype(jnp.float32)
    cm = _expand_groups(c, h).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)

    # (B, NC, Q, H, ...) chunked views
    xs = xf.reshape(b, nc, q, h, p)
    dts = dtf.reshape(b, nc, q, h)
    bs = bm.reshape(b, nc, q, h, n)
    cs = cm.reshape(b, nc, q, h, n)

    aseq = dts * af[None, None, None, :]            # (B,NC,Q,H) log-decays
    cum = jnp.cumsum(aseq, axis=2)                  # inclusive cumsum

    state0 = (jnp.zeros((b, h, p, n), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def chunk_step(state, inp):
        xc, dtc, bc, cc, cumc = inp
        # xc (B,Q,H,P), dtc (B,Q,H), bc/cc (B,Q,H,N), cumc (B,Q,H)
        # inter-chunk: y_inter[t] = exp(cum[t]) * C_t . state
        decay_out = jnp.exp(cumc)                              # (B,Q,H)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp", cc, state) * decay_out[..., None]
        # intra-chunk quadratic part
        #   M[t,i] = (C_t . B_i) * exp(cum[t]-cum[i]) * dt_i   for i <= t
        rel = cumc[:, :, None, :] - cumc[:, None, :, :]        # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((q, q), bool))
        decay_m = jnp.where(tri[None, :, :, None], jnp.exp(rel), 0.0)
        cb = jnp.einsum("bqhn,bihn->bqih", cc, bc)
        m = cb * decay_m * dtc[:, None, :, :]
        y_intra = jnp.einsum("bqih,bihp->bqhp", m, xc)
        # state carry:
        #   state' = exp(cum[-1]) * state + sum_i exp(cum[-1]-cum[i]) dt_i x_i (x) B_i
        total = cumc[:, -1, :]                                  # (B,H)
        w = jnp.exp(total[:, None, :] - cumc) * dtc             # (B,Q,H)
        upd = jnp.einsum("bqhp,bqhn->bhpn", xc * w[..., None], bc)
        state = jnp.exp(total)[..., None, None] * state + upd
        return state, y_inter + y_intra

    inputs = (xs.transpose(1, 0, 2, 3, 4), dts.transpose(1, 0, 2, 3),
              bs.transpose(1, 0, 2, 3, 4), cs.transpose(1, 0, 2, 3, 4),
              cum.transpose(1, 0, 2, 3))
    # remat the chunk body: its O(Q^2) intra-chunk intermediates (decay
    # matrix, CB gram) would otherwise be saved for EVERY chunk by AD —
    # tens of GB at train_4k scale; recomputing them is one extra matmul.
    state, ys = jax.lax.scan(jax.checkpoint(chunk_step), state0, inputs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p).astype(x.dtype)
    if return_state:
        return y, state.astype(x.dtype)
    return y


def ssd_decode_step(state, xt, dtt, a, bt, ct):
    """One-token recurrence for serving.  state (B,H,P,N); xt (B,H,P);
    dtt (B,H); bt/ct (B,G,N) -> (y (B,H,P), state')."""
    h = xt.shape[1]
    g = bt.shape[1]
    bt = jnp.repeat(bt, h // g, axis=1).astype(jnp.float32)
    ct = jnp.repeat(ct, h // g, axis=1).astype(jnp.float32)
    sf = state.astype(jnp.float32)
    decay = jnp.exp(dtt.astype(jnp.float32) * a.astype(jnp.float32))
    upd = dtt.astype(jnp.float32)[..., None, None] * \
        xt.astype(jnp.float32)[..., :, None] * bt[..., None, :]
    sf = decay[..., None, None] * sf + upd
    y = jnp.einsum("bhpn,bhn->bhp", sf, ct)
    return y.astype(xt.dtype), sf.astype(state.dtype)
