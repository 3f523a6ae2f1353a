"""Smashed-activation compression tests: round-trip error bounds, kernel
vs oracle, straight-through gradient symmetry (f4 == compressed f2), the
cut-boundary mask, comm accounting, and train-step loss parity."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import reduced
from repro.configs import get_config
from repro.core import comm, rounds, smashed
from repro.kernels.smashed_quant import ops as sq_ops
from repro.kernels.smashed_quant import ref as sq_ref
from repro.kernels.smashed_quant.kernel import (dequantize_pallas,
                                                quantize_pallas,
                                                roundtrip_pallas)
from repro.models.model import build_model


def _acts(key, shape, channel_spread=True):
    """Activation-like data: per-channel dynamic range varies strongly."""
    x = jax.random.normal(key, shape)
    if channel_spread:
        gain = jnp.exp(jax.random.normal(jax.random.PRNGKey(7),
                                         (shape[-1],)))
        x = x * gain
    return x


# ---------------------------------------------------------------------------
# int8 kernel pair vs jnp oracle (interpret mode)


@pytest.mark.parametrize("shape", [(2, 300, 96), (1, 256, 128), (3, 64, 40)])
def test_int8_kernels_match_ref(shape):
    x = _acts(jax.random.PRNGKey(0), shape)
    g, m, d = shape
    # pad to the kernel's block/lane multiples the way ops.py does
    bm = 256 if m >= 256 else max(32, 1 << (m - 1).bit_length())
    xp = jnp.pad(x, ((0, 0), (0, (-m) % bm), (0, (-d) % 128)))
    q, scale = quantize_pallas(xp, bm=bm, interpret=True)
    q_ref, scale_ref = sq_ref.quantize(x)
    np.testing.assert_array_equal(np.asarray(q[:, :m, :d]),
                                  np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(scale[:, 0, :d]),
                               np.asarray(scale_ref), rtol=1e-6)
    deq = dequantize_pallas(q, scale, bm=bm, interpret=True)[:, :m, :d]
    np.testing.assert_allclose(np.asarray(deq),
                               np.asarray(sq_ref.dequantize(q_ref,
                                                            scale_ref)),
                               rtol=1e-6)
    rt = roundtrip_pallas(xp, bm=bm, interpret=True)[:, :m, :d]
    np.testing.assert_allclose(np.asarray(rt), np.asarray(sq_ref.roundtrip(x)),
                               rtol=1e-6, atol=1e-7)


def test_ops_wrapper_interpret_path(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = _acts(jax.random.PRNGKey(1), (2, 3, 20, 48))   # (N, B, S, d)
    rt = sq_ops.int8_roundtrip_smashed(x)
    assert rt.shape == x.shape and rt.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(rt), np.asarray(sq_ref.roundtrip(x.reshape(2, -1, 48))
                                   .reshape(x.shape)), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# round-trip error bounds


def test_int8_roundtrip_error_bound():
    """|x - dequant(quant(x))| <= scale/2 per (message, channel) — the
    half-step bound of symmetric round-to-nearest."""
    x = _acts(jax.random.PRNGKey(2), (3, 200, 64))
    _, scale = sq_ref.quantize(x)
    err = jnp.abs(x - sq_ref.roundtrip(x))
    bound = scale[:, None, :] * 0.5 + 1e-6
    assert bool(jnp.all(err <= bound))


def test_fp8_roundtrip_error_bound():
    """e4m3 keeps ~2^-4 relative error for values within scale range."""
    x = _acts(jax.random.PRNGKey(3), (2, 128, 32))
    c = smashed.make_compressor("fp8")
    y = c.apply(x)
    amax = jnp.max(jnp.abs(x), axis=(1, 2), keepdims=True)
    rel = jnp.abs(y - x) / jnp.maximum(jnp.abs(x), amax * 1e-3)
    assert float(jnp.max(rel)) < 0.07


def test_topk_keeps_largest_exactly():
    x = _acts(jax.random.PRNGKey(4), (2, 16, 40), channel_spread=False)
    frac = 0.1
    k = max(1, int(40 * frac))
    c = smashed.make_compressor("topk", topk_frac=frac)
    y = np.asarray(c.apply(x))
    xn = np.asarray(x)
    kept = y != 0
    # kept entries are unchanged, and per token at least k survive
    np.testing.assert_allclose(y[kept], xn[kept])
    assert (kept.sum(-1) >= k).all()
    # nothing larger than a kept entry was dropped
    thresh = np.sort(np.abs(xn), axis=-1)[..., -k]
    assert (np.abs(xn[~kept]) <= thresh[..., None].repeat(40, -1)[~kept]
            + 1e-12).all()


# ---------------------------------------------------------------------------
# straight-through gradients (f4 symmetry)


@pytest.mark.parametrize("name", ["int8", "fp8", "topk"])
def test_gradient_is_compressed_symmetrically(name):
    """vjp(compressor)(g) == compressor(g): the gradient going back down
    the wire is compressed exactly like the activation going up."""
    c = smashed.make_compressor(name)
    key = jax.random.PRNGKey(5)
    x = _acts(key, (2, 4, 8, 16))
    g = _acts(jax.random.PRNGKey(6), x.shape)
    _, vjp = jax.vjp(c.apply, x)
    np.testing.assert_allclose(np.asarray(vjp(g)[0]),
                               np.asarray(c.apply(g)), rtol=1e-6)


def test_boundary_compresses_only_the_cut_client():
    c = smashed.make_compressor("int8")
    b = smashed.make_boundary(c, jnp.asarray([1, 3]))
    x = _acts(jax.random.PRNGKey(8), (2, 2, 8, 16))
    y = b(x, jnp.int32(0))          # flat layer 0 == cut-1 for client 0 only
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(x[1]))
    assert not np.allclose(np.asarray(y[0]), np.asarray(x[0]))
    assert smashed.make_boundary(None, jnp.asarray([1, 3])) is None


# ---------------------------------------------------------------------------
# comm accounting


def _small_model(layers=4):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=32,
                   vocab=128, seq_len=16, batch=2)
    return build_model(arch)


def test_comm_bytes_reflect_smashed_compressor():
    model = _small_model()
    kw = dict(cuts=[2, 2], batch_size=2, seq_len=16)
    base = comm.round_comm_bytes(model, **kw)
    i8 = comm.round_comm_bytes(model, smashed_compress="int8", **kw)
    f8 = comm.round_comm_bytes(model, smashed_compress="fp8", **kw)
    tk = comm.round_comm_bytes(model, smashed_compress="topk",
                               smashed_topk_frac=0.05, **kw)
    assert (base["smashed_ratio"] == 1.0).all()
    # int8/fp8 deliver the >= 3x the acceptance bar asks for (~4x on fp32)
    assert (i8["smashed_ratio"] >= 3.0).all()
    assert (f8["smashed_ratio"] >= 3.0).all()
    assert (tk["smashed_up"] < i8["smashed_up"]).all()
    assert (i8["smashed_up"] < base["smashed_up"]).all()
    # adapter channel is orthogonal to the smashed compressor
    np.testing.assert_allclose(i8["adapter_up"], base["adapter_up"])
    # measured side data is accounted: int8 wire > pure payload/4
    d = model.arch.model.d_model
    np.testing.assert_allclose(i8["smashed_up"],
                               2 * 16 * d * 1 + d * 4)


def test_wire_bytes_unknown_compressor_raises():
    with pytest.raises(ValueError):
        smashed.wire_bytes("gzip", batch=1, seq=1, d_model=8)
    with pytest.raises(ValueError):
        smashed.make_compressor("gzip")


# ---------------------------------------------------------------------------
# round engine integration


def test_train_step_int8_loss_parity():
    """3 rounds with smashed_compress='int8' stay within 2% of the
    uncompressed run (the acceptance bar, at reduced gpt2 scale)."""
    arch = reduced(get_config("gpt2-small"), layers=4, d_model=32,
                   vocab=128, seq_len=16, batch=2)
    model = build_model(arch)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    v = arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (2, 2, 16), 3, v),
             "labels": jax.random.randint(key, (2, 2, 16), 3, v),
             "loss_mask": jnp.ones((2, 2, 16), jnp.float32)}
    w = jnp.ones(2) / 2
    act = jnp.ones(2)
    lr = jnp.float32(1e-2)

    finals = {}
    grads_seen = {}
    for comp in ("none", "int8"):
        state = rounds.init_state(model, key, num_clients=2)
        step = rounds.make_train_step(model, smashed_compress=comp,
                                      jit=False)
        for _ in range(3):
            prev = state["client_adapters"]["dec"]["q"]["B"]
            state, metrics = step(params, state, batch, w, act, lr, lr)
        finals[comp] = float(metrics["total"])
        # client adapters below the cut still receive gradient through the
        # straight-through boundary (training is not silently frozen)
        moved = np.abs(np.asarray(state["client_adapters"]["dec"]["q"]["B"]
                                  - prev)).max()
        grads_seen[comp] = moved
    assert np.isfinite(finals["int8"])
    assert abs(finals["int8"] - finals["none"]) <= 0.02 * finals["none"]
    assert grads_seen["int8"] > 0
