"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a chip that is only
described (jax.experimental.topologies): it refuses what interpret mode
accepts — block shapes the tiling forbids, more fast memory than a
kernel may use, a program larger than the chip's 16 GiB, a kernel that
cannot be partitioned.  Nothing runs, so these tests say nothing about
results or times.

Kernels compile through their ops.py wrappers at gpt2-small widths, with
dispatch steered to Pallas by monkeypatching each module's
`_use_pallas` (the CPU backend would otherwise pick the jnp oracles).
The whole gpt2-small training round compiles for one chip, and the
client-axis-sharded round for the four chips of a 2x2 host.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import rounds
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.lora_matmul import ops as lora_ops
from repro.kernels.smashed_quant import ops as sq_ops
from repro.models.common import ShardingPolicy
from repro.models.model import build_model
from repro.runtime import sharding as rules

HBM_BYTES = 16 * 2 ** 30          # one v5e chip
D, H, HD, S, R = 768, 12, 64, 512, 16     # gpt2-small widths


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    for mod in (fa_ops, lora_ops, sq_ops, da_ops):
        monkeypatch.setattr(mod, "_use_pallas", lambda: True)


def _compile(fn, *shapes):
    """(program text, memory analysis) of fn compiled for the shapes."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2 ** 30:.2f} GiB > 16 GiB"
    return text, ma


def _flash(dt):
    def f(q, k, v, g):
        out, vjp = jax.vjp(lambda *t: fa_ops.flash_attention(*t, causal=True),
                           q, k, v)
        return (out,) + vjp(g)
    return f, [((2, S, H, HD), dt)] * 4


def _lora(dt):
    def f(x, w, a, b, s, g):
        y, vjp = jax.vjp(
            lambda *t: lora_ops.lora_matmul(*t, lora_only=True),
            x, w, a, b, s)
        return (y,) + vjp(g)
    return f, [((4 * S, D), dt), ((D, D), dt), ((D, R), dt), ((R, D), dt),
               ((), jnp.float32), ((4 * S, D), dt)]


def _indexed_lora(dt):
    return lora_ops.lora_matmul_indexed, [
        ((8, 1, D), dt), ((D, 3 * D), dt), ((4, D, R), dt),
        ((4, R, 3 * D), dt), ((4,), jnp.float32), ((8,), jnp.int32)]


def _smashed_roundtrip(dt):
    return sq_ops.int8_roundtrip_smashed, [((5, 4, S, D), dt)]


def _smashed_quantize(dt):
    return sq_ops.int8_quantize_smashed, [((5, 4, S, D), dt)]


def _smashed_dequantize(dt):
    return (lambda q, s: sq_ops.int8_dequantize_smashed(q, s, dt),
            [((5, 4, S, D), jnp.int8), ((5, D), jnp.float32)])


def _decode_dense(dt):
    return da_ops.decode_attention, [
        ((4, H, HD), dt), ((4, S, H, HD), dt), ((4, S, H, HD), dt),
        ((4,), jnp.int32)]


def _decode_paged(dt):
    pages = 4 * S // 16 + 1
    return da_ops.decode_attention_paged, [
        ((4, H, HD), dt), ((pages, 16, H, HD), dt), ((pages, 16, H, HD), dt),
        ((4, S // 16), jnp.int32), ((4,), jnp.int32)]


KERNELS = {"flash_fwd_bwd": _flash, "lora_fwd_bwd": _lora,
           "indexed_lora": _indexed_lora,
           "smashed_roundtrip": _smashed_roundtrip,
           "smashed_quantize": _smashed_quantize,
           "smashed_dequantize": _smashed_dequantize,
           "decode_dense": _decode_dense, "decode_paged": _decode_paged}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, dtype, one_chip, pallas):
    fn, shapes = KERNELS[kernel](dtype)
    _compile(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                   for s, d in shapes))


def _round_args(model, n, batch, *, rep, batch_sh, state_sh=None):
    """Shapes of one round step's arguments: the batch placed by
    batch_sh, the state by the tree state_sh(state) (default rep), the
    rest by rep."""
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init_params, key)
    state = jax.eval_shape(
        lambda k: rounds.init_state(model, k, num_clients=n), key)
    s = model.arch.train.seq_len

    def sds(shape, dtype, where=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    place = lambda t, w: sds(t.shape, t.dtype, w)
    state = (jax.tree.map(lambda t: place(t, rep), state)
             if state_sh is None
             else jax.tree.map(place, state, state_sh(state)))
    return (jax.tree.map(lambda t: place(t, rep), params), state,
            {"tokens": sds((n, batch, s), jnp.int32, batch_sh),
             "labels": sds((n, batch, s), jnp.int32, batch_sh),
             "loss_mask": sds((n, batch, s), jnp.float32, batch_sh)},
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((), jnp.float32), sds((), jnp.float32))


def test_gpt2_small_round_compiles_for_one_v5e(one_chip, pallas):
    """The paper config: 5 clients x batch 4 x seq 512, int8 smashed.

    The scanned stack saves one d_ff-wide residual per layer (the MLP's
    pre-activation; the activation is recomputed from it): 8.74 GiB of
    temporaries; stacking the activation's internals again would take
    14.36.  No flash forward is recomputed in the backward."""
    model = build_model(get_config("gpt2-small"))
    step = rounds.make_train_step(model, smashed_compress="int8")
    text, ma = _compile(step, *_round_args(model, 5, 4, rep=one_chip,
                                           batch_sh=one_chip))
    for name in ("flash_attention_pallas", "flash_attention_bwd_pallas",
                 "roundtrip_pallas"):
        assert f"jit({name})" in text, name
    assert ma.temp_size_in_bytes <= 10 * 2 ** 30, \
        f"{ma.temp_size_in_bytes / 2 ** 30:.2f} GiB of temporaries"
    assert not [ln for ln in text.splitlines()
                if "rematted_computation" in ln
                and "flash_attention_pallas" in ln]


def test_sharded_round_compiles_for_four_v5e(topo, pallas):
    """8 clients over a (4, 1) data x model mesh: every Pallas call runs
    per client shard (the compiler cannot partition one)."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = build_model(get_config("gpt2-small"))
    step = rounds.make_train_step(
        model, policy=ShardingPolicy(mesh=mesh, client_mode=True),
        smashed_compress="int8")
    args = _round_args(
        model, 8, 2, rep=NamedSharding(mesh, P()),
        batch_sh=NamedSharding(mesh, P("data")),
        state_sh=lambda st: rules.shardings_for(rules.state_specs(st, mesh),
                                                mesh))
    text, _ = _compile(step, *args)
    assert "all-gather" not in text
