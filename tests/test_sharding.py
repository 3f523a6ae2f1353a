"""Sharding-rule unit tests + 1-device mesh execution of the sharded path.

Here we verify the *rules* and that the constrained code path runs on a
real (1,1) mesh; tests/test_chip_compile.py compiles the round for a
described four-chip mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.config import reduced
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models.common import ShardingPolicy
from repro.models.model import build_model
from repro.runtime import sharding as rules


def host_mesh():
    return make_host_mesh()


def test_fit_spec_divisibility():
    mesh = host_mesh()           # data=1, model=1 — everything divides
    assert rules.fit_spec((8, 4), ("data", "model"), mesh) == \
        P("data", "model")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


@settings(max_examples=20, deadline=None)
@given(dim=st.integers(1, 64), size=st.sampled_from([2, 4, 8, 16]))
def test_fit_spec_never_produces_nondivisible(dim, size):
    mesh = FakeMesh({"data": size, "model": 16})
    spec = rules.fit_spec((dim,), (("data", "model"),), mesh)
    ax = spec[0]
    if ax is not None:
        axes = ax if isinstance(ax, tuple) else (ax,)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        assert dim % prod == 0


def test_param_specs_cover_model_tree():
    arch = reduced(get_config("llama3-8b"))
    model = build_model(arch)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    mesh = FakeMesh({"data": 16, "model": 16, "pod": 2})
    specs = rules.param_specs(params, mesh)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(s) == len(p.shape) or len(s) <= len(p.shape)
        # every sharded dim is divisible
        for dim, ax in zip(p.shape, tuple(s) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            prod = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % prod == 0, f"{s} on {p.shape}"


def test_moe_expert_specs_ep_over_model():
    arch = reduced(get_config("kimi-k2-1t-a32b"), experts=8)
    model = build_model(arch)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    mesh = FakeMesh({"data": 2, "model": 4})
    specs = rules.param_specs(params, mesh)
    we_in = specs["dec"]["we_in"]
    assert we_in[1] == "model"           # experts EP-sharded
    assert we_in[2] is None              # d NOT sharded (no weight gathers)


def test_policy_constraints_run_on_mesh():
    """The constrained model path executes correctly on a real mesh."""
    arch = reduced(get_config("gpt2-small"), layers=2)
    model = build_model(arch)
    mesh = host_mesh()
    policy = ShardingPolicy(mesh=mesh)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    toks = jax.random.randint(key, (2, 16), 3, arch.model.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with mesh:
        loss_sharded, _ = jax.jit(
            lambda p, b: model.loss(p, None, b, policy=policy))(params,
                                                                batch)
    loss_plain, _ = model.loss(params, None, batch)
    np.testing.assert_allclose(float(loss_sharded), float(loss_plain),
                               rtol=1e-5)


def test_seq_shard_policy_matches_unsharded():
    arch = reduced(get_config("llama3-8b"), layers=2)
    model = build_model(arch)
    mesh = host_mesh()
    policy = ShardingPolicy(mesh=mesh, seq_shard=True)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    toks = jax.random.randint(key, (2, 32), 3, arch.model.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with mesh:
        l1, _ = jax.jit(lambda p, b: model.loss(p, None, b,
                                                policy=policy))(params,
                                                                batch)
    l0, _ = model.loss(params, None, batch)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)


def test_cache_specs_seq_sharded_when_heads_dont_divide():
    arch = reduced(get_config("llama3-8b"), layers=2)
    model = build_model(arch)
    cache = jax.eval_shape(
        lambda: model.init_cache((4,), 64, jnp.float32))
    mesh = FakeMesh({"data": 2, "model": 16, "pod": 1})
    specs = rules.cache_specs(cache, mesh)
    k_spec = specs["dec"]["k"]          # (L, B, S, KVH, hd), KVH=1or2
    assert k_spec[2] == "model" or k_spec[3] == "model"
