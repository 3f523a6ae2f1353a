"""Flash attention Pallas TPU kernels (causal, GQA, sliding window).

Forward (DESIGN.md §4): blocked online-softmax over KV tiles.

  grid = (B * H, S_q / bq, S_k / bk), KV innermost ("arbitrary").
  Q tile (bq, hd) stays in VMEM for the whole KV loop; running max m,
  normalizer l and the un-normalized output accumulator live in fp32
  scratch.  K/V tiles are (bk, hd).  GQA is handled in the index_map:
  the (b*h) grid coordinate maps K/V to head h // group_size, so KV heads
  are never materialized per Q head in HBM.

  Tile skip: KV tiles strictly above the diagonal, or entirely left of
  every query's window, are skipped via pl.when on the whole tile body
  (Mosaic executes the grid sequentially per core, so the skip saves real
  time on TPU).  It needs more than one tile per row: at S <= 512 the one
  tile is the diagonal one and the skip never engages.

  Diagonal strips: a causal tile on the diagonal (q_start == k_start,
  read at run time, so with q_offset 0 that is iq == ik) is walked in
  static strips of STRIP = 128 rows, the MXU and lane width.  Query strip
  i takes only the keys its rows can see, [lo_i, 128 (i + 1)), lo_i the
  window's first key rounded down to 128 (0 without a window), and gives
  its rows of m, l and acc one online update.  At bq = bk = 512 the
  strips visit 62.5% of the tile's scores causal and 56.25% with a
  window of 256 (`visited_share`); the pairs they leave out were masked
  to exact zeros.  The dK/dV kernel walks the same plan transposed: key
  strip j takes the query rows that can see it.  The strips apply where
  the tile is causal, square and at least two strips tall; every other
  tile (off the diagonal, decode-style suffix queries, non-causal
  attention, bq < 256) runs the whole-tile body.  Each traced call counts
  under `flash.lowered.strips` or `flash.lowered.full_tile`
  (`repro.runtime.spans`).

  Besides the output the forward emits the logsumexp residual
  lse = m + log(l), shaped (B*H, S_q, 1) fp32 — everything the backward
  needs to rebuild the probabilities without a second online-softmax pass.

Backward: recompute-free dQ / dK / dV from the saved (out, lse).

  With s = scale * q k^T (masked), p = exp(s - lse) and
  delta = rowsum(dO * O) (computed by the wrapper, one elementwise pass):

    ds = p * (dO v^T - delta) * scale
    dq = ds k          dk = ds^T q          dv = p^T dO

  dQ kernel:   grid (B*H, S_q/bq, S_k/bk), KV innermost; dq accumulates
               in fp32 scratch over the KV loop exactly like the forward.
  dK/dV kernel: grid (B*KVH, S_k/bk, group, S_q/bq) — one pass per KV
               tile over every query head of its GQA group and every Q
               tile; dk/dv accumulate in fp32 scratch, so the per-Q-head
               KV gradients are never materialized in HBM (the group
               reduction happens in-grid).

  The same tile-level causal/window skip and diagonal strips apply on
  both sides: a (q-tile, kv-tile) pair participates iff some
  (q_pos, k_pos) in it is unmasked, which is one predicate shared by all
  three kernels.

q_offset (absolute position of q[0], decode with a KV cache) is a traced
SMEM scalar, NOT a static arg: decode calls with a different offset every
step, and a static offset would recompile (and, upstream, grow the
custom_vjp cache) per step.

Block sizes: bq/bk default 512/512 — head_dim (64..128) keeps tiles at
512*128*4B = 256 KiB, well under VMEM with double buffering.  At the
training length 512 that is one tile per (row, head), whose causal and
window saving comes from the diagonal strips alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu
from repro.runtime import spans

NEG_INF = -1e30

DEFAULT_BQ = 512
DEFAULT_BK = 512
STRIP = 128


def _tile_live(q_start, k_start, *, causal: bool, window: int,
               bq: int, bk: int):
    """True iff some (q_pos, k_pos) pair in the (bq, bk) tile is unmasked.

    Shared by forward, dQ and dK/dV: causal kills tiles strictly above the
    diagonal; a sliding window kills tiles entirely left of every query's
    window."""
    live = jnp.bool_(True)
    if causal:
        live = q_start + bq - 1 >= k_start
    if window > 0:
        live = jnp.logical_and(live, q_start - (k_start + bk - 1) < window)
    return live


def _pair_mask(q_start, k_start, *, causal: bool, window: int,
               bq: int, bk: int):
    # q_pos - k_pos from a column of rows and a row of keys
    d = ((q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
         - (k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)))
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= d >= 0
    if window > 0:
        mask &= d < window
    return mask


def _strips(causal: bool, window: int, bq: int, bk: int):
    """The diagonal tile's walk: per query strip, ((row0, rows),
    (key0, keys)), or None where the tile runs whole.  Query strip i sees
    keys up to its own last row (causal) and from its first row's window
    start, rounded down to a strip."""
    if not causal or bq != bk or bq % STRIP or bq < 2 * STRIP:
        return None
    plan = []
    for r0 in range(0, bq, STRIP):
        lo = 0 if window <= 0 else max(0, (r0 - window + 1) // STRIP * STRIP)
        plan.append(((r0, STRIP), (lo, r0 + STRIP - lo)))
    return tuple(plan)


def _strips_by_key(plan):
    """The same walk transposed: per key strip, ((row0, rows), (key0,
    keys)) with the query rows of every query strip that visits it."""
    out = []
    for k0 in range(0, len(plan) * STRIP, STRIP):
        rows = [r0 for (r0, _), (lo, n) in plan if lo <= k0 < lo + n]
        out.append(((rows[0], rows[-1] + STRIP - rows[0]), (k0, STRIP)))
    return tuple(out)


def visited_share(causal: bool, window: int, bq: int, bk: int) -> float:
    """Share of a diagonal (bq, bk) tile's score elements a call
    computes: 1.0 where the tile runs whole."""
    plan = _strips(causal, window, bq, bk)
    if plan is None:
        return 1.0
    return sum(r * k for (_, r), (_, k) in plan) / (bq * bk)


def _count_path(plan):
    spans.count("flash.lowered.full_tile" if plan is None
                else "flash.lowered.strips")


def _walk(q_start, k_start, plan, body, *, causal: bool, window: int,
          bq: int, bk: int):
    """Run `body(rows, keys, q_pos, k_pos)` over the live whole tile, or
    over the strips of `plan` where the tile is on the diagonal; q_pos and
    k_pos are the positions of the first row and key.  On the diagonal
    only their difference matters, so the strips pass static ones."""
    def whole():
        body(pl.ds(0, bq), pl.ds(0, bk), q_start, k_start)

    run = _tile_live(q_start, k_start, causal=causal, window=window,
                     bq=bq, bk=bk)
    if plan is None:
        pl.when(run)(whole)
        return
    diag = q_start == k_start
    pl.when(jnp.logical_and(run, jnp.logical_not(diag)))(whole)

    @pl.when(diag)
    def _strip_walk():
        for (r0, nr), (k0, nk) in plan:
            body(pl.ds(r0, nr), pl.ds(k0, nk), r0, k0)


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                window: int, bq: int, bk: int, n_kv: int, plan):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(rows, keys, q_pos, k_pos):
        """One online-softmax update of the running rows `rows` by the
        keys `keys` of this tile."""
        q = q_ref[0, rows].astype(jnp.float32) * scale
        k = k_ref[0, 0, keys].astype(jnp.float32)
        prec = mxu.precision(q_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)
        mask = _pair_mask(q_pos, k_pos, causal=causal, window=window,
                          bq=rows.size, bk=keys.size)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[rows]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0, keys], precision=prec,
            preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    _walk(iq * bq + qoff_ref[0], ik * bk, plan, body, causal=causal,
          window=window, bq=bq, bk=bk)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zero output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # empty rows: m = NEG_INF, l clamped to 1 -> lse = 0, so the
        # backward's p = exp(NEG_INF - 0) = 0 and their grads vanish
        m = jnp.where(m_ref[...] <= NEG_INF, 0.0, m_ref[...])
        lse_ref[0] = m + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, q_offset=0, *, causal: bool = True,
                           window: int = 0, scale: float | None = None,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False):
    """q: (B, Sq, H, hd); k/v: (B, Sk, KVH, hd) ->
    (out (B, Sq, H, hd), lse (B*H, Sq, 1) fp32).

    q_offset may be a traced int32 scalar (decode offsets change per
    step)."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    if scale is None:
        scale = hd ** -0.5
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq ({sq},{sk}) not divisible by ({bq},{bk})")
    n_kv = sk // bk
    plan = _strips(causal, window, bq, bk)
    _count_path(plan)

    # layout: (B*H, S, hd) for Q/O; K/V stay (B, KVH, S, hd), GQA via index_map
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape((1,))

    grid = (b * h, sq // bq, n_kv)

    def kv_index(bh, iq, ik):
        return (bh // h, (bh % h) // group, ik, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=n_kv, plan=plan),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # q_offset
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # normalizer
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, qt, kt, vt)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, keys,
              q_pos, k_pos, *, scale, causal, window):
    """Shared tile math over the query rows `rows` and keys `keys`:
    probabilities p and score gradient ds (both (rows, keys) fp32, scale
    folded into ds), and dO's rows."""
    q = q_ref[0, rows].astype(jnp.float32) * scale
    k = k_ref[0, 0, keys].astype(jnp.float32)
    v = v_ref[0, 0, keys].astype(jnp.float32)
    do = do_ref[0, rows].astype(jnp.float32)
    prec = mxu.precision(q_ref.dtype)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32)
    mask = _pair_mask(q_pos, k_pos, causal=causal, window=window,
                      bq=rows.size, bk=keys.size)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0, rows])                    # (rows, keys)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, rows]) * scale
    return p, ds, do


def _bwd_dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale: float, causal: bool,
                   window: int, bq: int, bk: int, n_kv: int, plan):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(rows, keys, q_pos, k_pos):
        _, ds, _ = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, rows, keys, q_pos, k_pos,
                             scale=scale, causal=causal, window=window)
        dq_acc[rows] += jax.lax.dot(
            ds, k_ref[0, 0, keys].astype(jnp.float32),
            precision=mxu.precision(q_ref.dtype),
            preferred_element_type=jnp.float32)

    _walk(iq * bq + qoff_ref[0], ik * bk, plan, body, causal=causal,
          window=window, bq=bq, bk=bk)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, window: int, bq: int,
                    bk: int, n_q: int, group: int, plan):
    ik = pl.program_id(1)
    g = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(rows, keys, q_pos, k_pos):
        p, ds, do = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, rows, keys, q_pos, k_pos,
                              scale=scale, causal=causal, window=window)
        # contract over the q rows: p^T dO and ds^T q, no explicit transpose
        prec = mxu.precision(q_ref.dtype)
        dv_acc[keys] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)
        dk_acc[keys] += jax.lax.dot_general(
            ds, q_ref[0, rows].astype(jnp.float32), (((0,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)

    _walk(iq * bq + qoff_ref[0], ik * bk, plan, body, causal=causal,
          window=window, bq=bq, bk=bk)

    @pl.when(jnp.logical_and(g == group - 1, iq == n_q - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention_bwd_pallas(q, k, v, out, lse, do, q_offset=0, *,
                               causal: bool = True, window: int = 0,
                               scale: float | None = None,
                               bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                               interpret: bool = False):
    """dQ/dK/dV from the saved forward residuals (out, lse).

    q/do/out: (B, Sq, H, hd); k/v: (B, Sk, KVH, hd);
    lse: (B*H, Sq, 1) fp32 as returned by flash_attention_pallas.
    Returns (dq, dk, dv) in the input layouts/dtypes."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    if scale is None:
        scale = hd ** -0.5
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq ({sq},{sk}) not divisible by ({bq},{bk})")
    n_q, n_kv = sq // bq, sk // bk
    plan = _strips(causal, window, bq, bk)
    _count_path(plan)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    # delta = rowsum(dO * O): one fused elementwise pass, shared by dQ & dK
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape((1,))

    def kv_index(bh, iq, ik):
        return (bh // h, (bh % h) // group, ik, 0)

    q_spec = pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0))
    r_spec = pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=n_kv, plan=plan),
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # q_offset
            q_spec,
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            q_spec,                                               # dO
            r_spec,                                               # lse
            r_spec,                                               # delta
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, qt, kt, vt, dot, lse, delta)

    # dK/dV: grid walks each KV tile over the whole GQA group and all Q
    # tiles; the group-sum lands in the fp32 scratch accumulators, so dk/dv
    # come out already reduced to (B, KVH, Sk, hd).
    def head_of(bkv, ik, g, iq):
        return (bkv // kvh) * h + (bkv % kvh) * group + g

    def q_index(bkv, ik, g, iq):
        return (head_of(bkv, ik, g, iq), iq, 0)

    def r_index(bkv, ik, g, iq):
        return (head_of(bkv, ik, g, iq), iq, 0)

    def kv_index2(bkv, ik, g, iq):
        return (bkv // kvh, bkv % kvh, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_q=n_q,
                          group=group,
                          plan=None if plan is None
                          else _strips_by_key(plan)),
        grid=(b * kvh, n_kv, group, n_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # q_offset
            pl.BlockSpec((1, bq, hd), q_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index2),
            pl.BlockSpec((1, 1, bk, hd), kv_index2),
            pl.BlockSpec((1, bq, hd), q_index),                   # dO
            pl.BlockSpec((1, bq, 1), r_index),                    # lse
            pl.BlockSpec((1, bq, 1), r_index),                    # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), kv_index2),
            pl.BlockSpec((1, 1, bk, hd), kv_index2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, kvh, sk, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),    # dk accumulator
            pltpu.VMEM((bk, hd), jnp.float32),    # dv accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, qt, kt, vt, dot, lse, delta)

    dq = dq.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    dk = dk.transpose(0, 2, 1, 3)
    dv = dv.transpose(0, 2, 1, 3)
    return dq, dk, dv
