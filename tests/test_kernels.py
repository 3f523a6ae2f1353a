"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests.
"""

import os

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    """Route kernel dispatch through Pallas interpret mode for THIS module
    only.  A module-level os.environ write would leak into every test module
    collected after this one (collection imports all modules first) and force
    unrelated tests onto the Pallas path."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")


import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
from hypothesis_compat import given, settings, st   # noqa: E402

from repro.kernels.decode_attention import ops as dec_ops   # noqa: E402
from repro.kernels.decode_attention import ref as dec_ref   # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops     # noqa: E402
from repro.kernels.flash_attention import kernel as fa_kernel  # noqa: E402
from repro.kernels.flash_attention import ref as fa_ref     # noqa: E402
from repro.kernels.lora_matmul import ops as lora_ops       # noqa: E402
from repro.kernels.lora_matmul import ref as lora_ref       # noqa: E402
from repro.kernels.lora_matmul.kernel import lora_matmul_pallas  # noqa: E402
from repro.kernels.ssd_scan import ops as ssd_ops           # noqa: E402
from repro.kernels.ssd_scan import ref as ssd_ref           # noqa: E402
from repro.runtime import spans                             # noqa: E402


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# lora_matmul


@pytest.mark.parametrize("m,k,n,r", [
    (128, 256, 128, 8), (256, 512, 256, 16), (128, 128, 384, 4),
    (512, 256, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_shapes(m, k, n, r, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (k, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, n)) * 0.05).astype(dtype)
    s = jnp.float32(0.5)
    got, xa = lora_matmul_pallas(x, w, a, b, s, bm=128, bn=128, bk=128,
                                 interpret=True)
    want = lora_ref.lora_matmul(x, w, a, b, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
    # the fp32 residual the backward reuses
    want_xa = x.astype(jnp.float32) @ a.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(xa), np.asarray(want_xa),
                               **tol(dtype))


def test_lora_matmul_vjp_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (128, 256))
    w = jax.random.normal(ks[1], (256, 128)) * 0.05
    a = jax.random.normal(ks[2], (256, 8)) * 0.05
    b = jax.random.normal(ks[3], (8, 128)) * 0.05
    s = jnp.float32(0.7)

    def f_ops(*args):
        return jnp.sum(lora_ops.lora_matmul(*args) ** 2)

    def f_ref(*args):
        return jnp.sum(lora_ref.lora_matmul(*args) ** 2)

    g_ops = jax.grad(f_ops, argnums=(0, 1, 2, 3))(x, w, a, b, s)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, w, a, b, s)
    for go, gr in zip(g_ops, g_ref):
        np.testing.assert_allclose(go, gr, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(r=st.integers(1, 32), scale=st.floats(0.0, 4.0))
def test_lora_rank_zero_B_is_identity(r, scale):
    """Property: B=0 makes the adapter exactly the base matmul."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (64, 64))
    w = jax.random.normal(ks[1], (64, 64))
    a = jax.random.normal(ks[2], (64, r))
    b = jnp.zeros((r, 64))
    got = lora_ref.lora_matmul(x, w, a, b, jnp.float32(scale))
    np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,s,h,kvh,hd,window", [
    (2, 256, 4, 2, 64, 0),
    (1, 512, 8, 8, 64, 128),
    (2, 128, 4, 1, 32, 0),
    (1, 256, 8, 4, 128, 64),
    (1, 512, 2, 1, 64, 0),      # the training tile: diagonal strips
    (1, 512, 2, 2, 64, 256),    # strips with a window of 256
    (1, 1024, 1, 1, 64, 0),     # strips on the diagonal, whole tile below
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kvh, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    want = fa_ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window,share", [(0, 0.625), (256, 0.5625)])
def test_flash_strip_plan_and_counters(window, share):
    """At the training tile (S = bq = bk = 512) the diagonal strips visit
    5/8 of the scores causal and 9/16 with a window of 256, and lowering
    that call takes the strip path; a decode-style call with a traced
    offset and a non-causal call keep the whole tile."""
    assert fa_kernel.visited_share(True, window, 512, 512) == share
    assert fa_kernel.visited_share(False, 0, 512, 512) == 1.0
    assert fa_kernel.visited_share(True, 0, 64, 192) == 1.0

    def lowered(q, kv, *extra, **kw):
        fa_kernel.flash_attention_pallas.clear_cache()
        before = spans.counters()
        f32 = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (q, kv, kv)]
        fa_kernel.flash_attention_pallas.lower(*f32, *extra, interpret=True,
                                               **kw)
        after = spans.counters()
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in ("flash.lowered.strips", "flash.lowered.full_tile")}

    strips = {"flash.lowered.strips": 1, "flash.lowered.full_tile": 0}
    whole = {"flash.lowered.strips": 0, "flash.lowered.full_tile": 1}
    assert lowered((2, 512, 12, 64), (2, 512, 12, 64),
                   window=window) == strips
    assert lowered((1, 64, 4, 32), (1, 192, 2, 32),
                   jax.ShapeDtypeStruct((), jnp.int32)) == whole
    assert lowered((1, 512, 2, 64), (1, 512, 2, 64), causal=False) == whole


def test_chunked_attention_matches_direct():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 512, 4, 64))
    k = jax.random.normal(ks[1], (2, 512, 2, 64))
    v = jax.random.normal(ks[2], (2, 512, 2, 64))
    got = fa_ref.chunked_attention(q, k, v, causal=True, block=128)
    want = fa_ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(sq=st.sampled_from([64, 128]), off=st.sampled_from([0, 64, 128]))
def test_flash_q_offset_property(sq, off):
    """Decode-style offset q equals slicing the full computation."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    sk = sq + off
    q_full = jax.random.normal(ks[0], (1, sk, 4, 32))
    k = jax.random.normal(ks[1], (1, sk, 4, 32))
    v = jax.random.normal(ks[2], (1, sk, 4, 32))
    full = fa_ref.attention(q_full, k, v, causal=True)
    part = fa_ops.flash_attention(q_full[:, off:], k, v, causal=True,
                                  q_offset=off)
    np.testing.assert_allclose(part, full[:, off:], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("b,s,h,kvh,hd", [
    (2, 1024, 8, 2, 64), (4, 512, 16, 16, 32), (2, 1024, 8, 4, 128),
    (1, 2048, 4, 1, 64),
])
def test_decode_attention_sweep(b, s, h, kvh, hd):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, s, kvh, hd))
    v = jax.random.normal(ks[2], (b, s, kvh, hd))
    clen = jnp.asarray([s // 2, s, s // 4, 3 * s // 4][:b], jnp.int32)
    got = dec_ops.decode_attention(q, k, v, clen)
    want = dec_ref.decode_attention(q, k, v, clen)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_decode_attention_ignores_garbage_past_len():
    """Property: cache contents past cache_len must not affect output."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (2, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    clen = jnp.asarray([100, 37], jnp.int32)
    base = dec_ops.decode_attention(q, k, v, clen)
    noise = jax.random.normal(ks[3], k.shape) * 100
    pos = jnp.arange(256)[None, :, None, None]
    k2 = jnp.where(pos >= clen[:, None, None, None], noise, k)
    v2 = jnp.where(pos >= clen[:, None, None, None], noise, v)
    got = dec_ops.decode_attention(q, k2, v2, clen)
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan


@pytest.mark.parametrize("b,s,h,p,g,n,q", [
    (2, 128, 4, 32, 1, 16, 32),
    (1, 256, 2, 64, 1, 64, 64),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 64, 8, 16, 4, 8, 16),
])
def test_ssd_chunked_vs_sequential(b, s, h, p, g, n, q):
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    c = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    want = ssd_ref.ssd_sequential(x, dt, a, bm, c)
    chunked = ssd_ref.ssd_chunked(x, dt, a, bm, c, chunk=q)
    pallas = ssd_ops.ssd_scan(x, dt, a, bm, c, chunk=q)
    np.testing.assert_allclose(chunked, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(pallas, want, rtol=2e-4, atol=2e-4)


def test_ssd_decode_step_continues_scan():
    b, s, h, p, g, n = 2, 64, 4, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 10)
    x = jax.random.normal(ks[0], (b, s + 1, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s + 1, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s + 1, g, n)) * 0.3
    c = jax.random.normal(ks[4], (b, s + 1, g, n)) * 0.3
    full = ssd_ref.ssd_sequential(x, dt, a, bm, c)
    _, st_ = ssd_ref.ssd_sequential(x[:, :s], dt[:, :s], a, bm[:, :s],
                                    c[:, :s], return_state=True)
    yd, _ = ssd_ref.ssd_decode_step(st_, x[:, s], dt[:, s], a, bm[:, s],
                                    c[:, s])
    np.testing.assert_allclose(yd, full[:, s], rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(decay=st.floats(0.1, 3.0))
def test_ssd_state_decay_bounded(decay):
    """Property: with A<0, dt>0, all decay factors <= 1, so the output is
    bounded by sum of |dt x B C| contributions (no blow-up with length)."""
    b, s, h, p, g, n = 1, 128, 2, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    x = jnp.ones((b, s, h, p))
    dt = jnp.full((b, s, h), 0.5)
    a = -jnp.full((h,), decay)
    bm = jnp.ones((b, s, g, n)) * 0.1
    c = jnp.ones((b, s, g, n)) * 0.1
    y = ssd_ref.ssd_chunked(x, dt, a, bm, c, chunk=32)
    assert bool(jnp.all(jnp.isfinite(y)))
    # geometric series bound: dt*B*C*n / (1 - exp(dt*a))
    bound = 0.5 * 0.1 * 0.1 * n / (1 - np.exp(0.5 * -decay)) + 1e-3
    assert float(jnp.max(jnp.abs(y))) <= bound * 1.01
