"""Gradient-parity suite: every kernel with a custom_vjp, Pallas-interpret
backward vs the jnp oracle's jax.vjp.

The oracle (ref.py in each kernel package) is pure jnp, so jax.vjp through
it is the semantics contract for the hand-written Pallas backward kernels.
Property tests (hypothesis, optional via tests/hypothesis_compat) sample
awkward shapes — ragged S, GQA ratios, sliding windows, ranks that are not
sublane multiples — and both dtypes; plain parametrized tests keep coverage
on the bare-interpreter CI lane.

Per-dtype tolerances: fp32 backward accumulates in fp32 on both paths, so
parity is tight (2e-4).  bf16 oracles run their AD matmuls in bf16, which
carries an *absolute* accumulation error proportional to the reduction
length regardless of output magnitude — tolerances are rtol 3e-2 /
atol 1e-1 (the Pallas kernels, accumulating fp32, are the closer of the
two to the true value; see PR history).
"""

import os

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    """Route kernel dispatch through Pallas interpret mode for THIS module
    only (same pattern as test_kernels.py)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")


import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
from hypothesis_compat import given, settings, st   # noqa: E402

from repro.core import smashed as smashed_lib               # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops     # noqa: E402
from repro.kernels.flash_attention import ref as fa_ref     # noqa: E402
from repro.kernels.lora_matmul import ops as lora_ops       # noqa: E402
from repro.kernels.lora_matmul import ref as lora_ref       # noqa: E402
from repro.kernels.smashed_quant import ref as quant_ref    # noqa: E402


def grad_tol(dtype):
    return dict(rtol=3e-2, atol=1e-1) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


def assert_grads_close(got, want, dtype, names):
    for g, w, nm in zip(got, want, names):
        tol = grad_tol(dtype)
        if jnp.ndim(g) == 0 and dtype == jnp.bfloat16:
            # scalar cotangents (dscale) are one full M*N reduction: the
            # bf16 oracle's accumulation error grows with the term count
            tol = dict(rtol=1.5e-1, atol=5e-1)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=f"d{nm}", **tol)


# ---------------------------------------------------------------------------
# lora_matmul


def _lora_operands(m, k, n, r, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (k, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, n)) * 0.05).astype(dtype)
    g = jax.random.normal(ks[4], (m, n), dtype)
    return x, w, a, b, jnp.float32(0.7), g


def _check_lora_parity(m, k, n, r, dtype, *, lora_only=False):
    x, w, a, b, s, g = _lora_operands(m, k, n, r, dtype)
    _, vjp = jax.vjp(
        lambda *t: lora_ops.lora_matmul(*t, lora_only=lora_only),
        x, w, a, b, s)
    _, vjp_ref = jax.vjp(lora_ref.lora_matmul, x, w, a, b, s)
    got, want = list(vjp(g)), list(vjp_ref(g))
    if lora_only:
        # frozen base: dW is a symbolic zero, not the oracle's x^T g
        assert float(jnp.max(jnp.abs(got[1]))) == 0.0
        del got[1], want[1]
        assert_grads_close(got, want, dtype, ["x", "a", "b", "scale"])
    else:
        assert_grads_close(got, want, dtype, ["x", "w", "a", "b", "scale"])


@pytest.mark.parametrize("m,k,n,r", [
    (128, 256, 128, 8),     # aligned, multi-block
    (96, 256, 384, 16),     # ragged M, N a 128-multiple but not 256
    (64, 100, 96, 4),       # nothing aligned: single-block fallback
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_grad_parity(m, k, n, r, dtype):
    _check_lora_parity(m, k, n, r, dtype)


def test_lora_grad_parity_lora_only():
    _check_lora_parity(128, 256, 128, 8, jnp.float32, lora_only=True)


@settings(max_examples=8, deadline=None)
@given(m=st.sampled_from([17, 64, 200]),
       r=st.sampled_from([1, 3, 8, 20, 64]),       # incl. rank % 8 != 0
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_lora_grad_parity_property(m, r, dtype):
    _check_lora_parity(m, 128, 128, r, jnp.dtype(dtype).type)


# ---------------------------------------------------------------------------
# flash attention


def _check_flash_parity(b, sq, sk, h, kvh, hd, window, off, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, sk, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, sk, kvh, hd), dtype)
    g = jax.random.normal(ks[3], (b, sq, h, hd), dtype)

    _, vjp = jax.vjp(
        lambda *t: fa_ops.flash_attention(*t, causal=True, window=window,
                                          q_offset=off), q, k, v)
    _, vjp_ref = jax.vjp(
        lambda *t: fa_ref.attention(*t, causal=True, window=window,
                                    q_offset=off), q, k, v)
    assert_grads_close(vjp(g), vjp_ref(g), dtype, ["q", "k", "v"])


@pytest.mark.parametrize("b,s,h,kvh,hd,window", [
    (2, 256, 4, 2, 64, 0),      # GQA 2:1, multi KV tile
    (1, 128, 8, 8, 32, 64),     # MHA + sliding window
    (2, 128, 4, 1, 32, 0),      # MQA (group == h)
    (1, 96, 4, 2, 64, 32),      # ragged S + window
    (1, 512, 2, 1, 64, 0),      # the training tile: diagonal strips
    (1, 512, 2, 2, 64, 256),    # strips with a window of 256
    (1, 1024, 1, 1, 64, 0),     # strips on the diagonal, whole tile below
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grad_parity(b, s, h, kvh, hd, window, dtype):
    _check_flash_parity(b, s, s, h, kvh, hd, window, 0, dtype)


def test_flash_grad_parity_q_offset():
    """Decode-style suffix queries: grads through the offset match the
    oracle (and the offset's own cotangent is a float0, not a recompile)."""
    _check_flash_parity(1, 64, 192, 4, 2, 32, 0, 128, jnp.float32)


@settings(max_examples=8, deadline=None)
@given(sq=st.sampled_from([48, 128, 200]),
       ratio=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([0, 32]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_flash_grad_parity_property(sq, ratio, window, dtype):
    h = 4
    _check_flash_parity(1, sq, sq, h, h // ratio, 32, window, 0,
                        jnp.dtype(dtype).type)


# ---------------------------------------------------------------------------
# smashed_quant (straight-through estimator over the fused int8 round trip)


def _check_smashed_int8_parity(shape, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], shape)
    g = jax.random.normal(ks[1], shape)
    comp = smashed_lib.make_compressor("int8")
    _, vjp = jax.vjp(comp.apply, x)
    (dx,) = vjp(g)
    # STE contract: the cotangent comes back through the SAME compressor;
    # oracle = the pure-jnp round trip of g, canonicalized the way the ops
    # do it (axis 0 is the message axis for ndim >= 3, else one message)
    if g.ndim == 2:
        g3 = g.reshape(1, -1, g.shape[-1])
    else:
        g3 = g.reshape(g.shape[0], -1, g.shape[-1])
    want = quant_ref.roundtrip(g3).reshape(g.shape)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [
    (2, 64, 128),     # (clients, tokens, d)
    (3, 4, 16, 96),   # extra batch dim, ragged d
    (40, 100),        # 2-D single message, nothing aligned
])
def test_smashed_int8_ste_parity(shape):
    _check_smashed_int8_parity(shape)


@settings(max_examples=6, deadline=None)
@given(m=st.sampled_from([7, 33, 256]), d=st.sampled_from([32, 100, 128]))
def test_smashed_int8_ste_parity_property(m, d):
    _check_smashed_int8_parity((2, m, d))


# ---------------------------------------------------------------------------
# dispatch-policy regression: the decode offset must not grow the flash
# custom_vjp cache (ISSUE 3: unbounded _make_flash lru_cache during decode)


def test_flash_cache_bounded_across_q_offsets():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 4, 32))
    v = jax.random.normal(ks[2], (1, 256, 4, 32))
    fa_ops._make_flash.cache_clear()
    for off in range(0, 160, 16):
        fa_ops.flash_attention(q, k, v, causal=True, q_offset=off)
    assert fa_ops._make_flash.cache_info().currsize == 1
    # a different static config is a second entry — and no more
    fa_ops.flash_attention(q, k, v, causal=True, window=32, q_offset=3)
    assert fa_ops._make_flash.cache_info().currsize == 2


def test_flash_cache_bounded_under_grad():
    """The bug bites hardest through the custom_vjp closures: grads at
    many offsets must also reuse one cache entry."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    fa_ops._make_flash.cache_clear()
    for off in (0, 8, 16, 32):
        jax.grad(lambda q_: jnp.sum(fa_ops.flash_attention(
            q_, k, v, causal=True, q_offset=off)))(q)
    assert fa_ops._make_flash.cache_info().currsize == 1


def test_jnp_path_unaffected_by_cache_fix(monkeypatch):
    """Sanity: with interpret off (CPU oracle dispatch) q_offset still
    reaches the reference path."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":  # pragma: no cover
        pytest.skip("env leak")
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    sk, off = 96, 32
    q = jax.random.normal(ks[0], (1, sk - off, 4, 32))
    k = jax.random.normal(ks[1], (1, sk, 4, 32))
    v = jax.random.normal(ks[2], (1, sk, 4, 32))
    full = fa_ref.attention(jnp.pad(q, ((0, 0), (off, 0), (0, 0), (0, 0))),
                            k, v, causal=True)
    part = fa_ops.flash_attention(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(part, full[:, off:], rtol=2e-5, atol=2e-5)
