"""Fleet-scale population mode pins (ISSUE 7).

  * cohort gather/scatter round-trips; out-of-cohort slots bit-identical;
  * cohort-of-everyone (P == C) reproduces fleet mode bit-for-bit;
  * 1-edge hierarchical aggregation == flat, bitwise; E > 1 telescopes
    to the flat average (allclose) under uniform edge membership;
  * client-axis sharding specs put the cohort axis on the data mesh axis
    (with the fit_spec divisibility fallback), and the constrained path
    executes on a real (1, 1) host mesh with unchanged numerics;
  * the 1/K_i server-gradient normalization is bitwise off at K == 1 and
    actually changes server updates under heterogeneous budgets;
  * cohort-sampler RNG threads through checkpoint save/restore (resumed
    run bitwise == straight run; mismatched population raises loudly).
"""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import reduced
from repro.configs import get_config
from repro.core import aggregation, rounds
from repro.core.system import SplitFTSystem, SystemConfig
from repro.launch.mesh import make_host_mesh
from repro.models.common import NO_SHARDING, ShardingPolicy
from repro.models.model import build_model
from repro.runtime import sharding as rules
from repro.runtime.population import CohortSampler, PopulationStore


def small_arch(layers=4, population=0):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=32, batch=2)
    return arch.replace(data=dataclasses.replace(arch.data,
                                                 population=population))


SYS = dict(num_samples=80, eval_samples=16)


def tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def prepared_state(arch, n=3, seed=0):
    model = build_model(arch)
    state = rounds.init_state(model, jax.random.PRNGKey(seed),
                              num_clients=n)
    return model, rounds.prepare_state(state)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


# ---------------------------------------------------------------------------
# PopulationStore: gather/scatter round-trip, out-of-cohort isolation


def test_gather_identity_on_first_cohort():
    """gather of pids 0..C-1 from a fresh store IS the template state:
    fresh slots materialize from column pid % C of the initial state."""
    arch = small_arch()
    _, state = prepared_state(arch)
    store = PopulationStore(10, state, seed=0)
    got = store.gather(state, np.arange(3))
    assert tree_equal(got, state)


def test_scatter_gather_roundtrip():
    arch = small_arch()
    _, state = prepared_state(arch)
    store = PopulationStore(10, state, seed=0)
    pids = np.array([1, 4, 7])
    st = store.gather(state, pids)
    # mutate every per-client leaf, scatter, gather again
    st = jax.tree.map(lambda x: x + (1 if np.issubdtype(
        np.asarray(x).dtype, np.integer) else 0.5), st)
    store.scatter(st, pids, cursors=[3, 3, 3])
    back = store.gather(st, pids)    # global leaves pass through st
    assert tree_equal(back, st)
    assert list(store.cursors(pids)) == [3, 3, 3]


def test_scatter_leaves_out_of_cohort_slots_bit_identical():
    arch = small_arch()
    _, state = prepared_state(arch)
    store = PopulationStore(10, state, seed=0)
    outside = np.array([0, 5, 9])
    before = jax.tree.map(np.array, store.gather(state, outside))
    inside = np.array([2, 3, 6])
    st = store.gather(state, inside)
    st = jax.tree.map(lambda x: x * 0 + 7, st)
    store.scatter(st, inside)
    after = store.gather(state, outside)
    assert tree_equal(before, after)


def test_store_rejects_wrong_cohort_size():
    arch = small_arch()
    _, state = prepared_state(arch)
    store = PopulationStore(10, state, seed=0)
    with pytest.raises(ValueError, match="client axis"):
        store.gather(state, np.arange(5))


# ---------------------------------------------------------------------------
# CohortSampler: determinism, resume, loud mismatches


def test_sampler_deterministic_and_resumable():
    a = CohortSampler(100, 8, seed=3)
    b = CohortSampler(100, 8, seed=3)
    for _ in range(4):
        assert np.array_equal(a.sample(), b.sample())
    mid = a.state_dict()
    tail = [a.sample() for _ in range(3)]
    c = CohortSampler(100, 8, seed=0)      # different seed: state wins
    c.load_state_dict(mid)
    for want in tail:
        assert np.array_equal(c.sample(), want)


def test_sampler_full_population_is_arange_without_rng():
    s = CohortSampler(5, 5, seed=1)
    before = s.state_dict()
    assert np.array_equal(s.sample(), np.arange(5))
    assert s.state_dict() == before        # no RNG consumed


def test_sampler_mismatch_raises():
    s = CohortSampler(100, 8, seed=0)
    with pytest.raises(ValueError, match="population"):
        CohortSampler(200, 8, seed=0).load_state_dict(s.state_dict())
    with pytest.raises(ValueError, match="cohort"):
        CohortSampler(100, 4, seed=0).load_state_dict(s.state_dict())


def test_pid_keyed_jitter_survives_cohort_shuffle():
    """Regression (ISSUE 9): per-round jitter must be an attribute of
    the CLIENT (pid), not of the cohort slot it landed in.  A shuffled
    cohort of the same pids must charge each pid bitwise-identical
    phase times."""
    from repro.runtime.straggler import SpeedModel, population_speed_draws

    def model_for(pids, keyed=True):
        sm = SpeedModel(num_clients=len(pids), seed=0)
        sp, bw, js = population_speed_draws(pids, seed=0)
        sm.speed, sm.bandwidth = sp, bw
        if keyed:
            sm.jitter_seeds = np.asarray(js, np.int64)
        return sm

    def phases(sm):
        return sm.phase_times(cuts=[2] * sm.num_clients,
                              flops_per_layer=1e9,
                              smashed_bytes=1e6,
                              adapter_bytes=[1e5] * sm.num_clients,
                              round_idx=3)

    pids = [5, 6, 7]
    perm = [2, 0, 1]                       # slot order [7, 5, 6]
    a = phases(model_for(pids))
    b = phases(model_for([pids[j] for j in perm]))
    # b's slot k holds pid pids[perm[k]], which sits at slot perm[k]
    # in a -- every pid's (5,) phase column must match bitwise
    for k in range(3):
        np.testing.assert_array_equal(b[:, k], a[:, perm[k]])
    # the legacy positional draw does NOT have this property (the bug
    # this pins): without pid-keyed seeds the shuffled cohort reassigns
    # slot noise to different pids
    a_pos = phases(model_for(pids, keyed=False))
    b_pos = phases(model_for([pids[j] for j in perm], keyed=False))
    assert any(not np.array_equal(b_pos[:, k], a_pos[:, perm[k]])
               for k in range(3))


# ---------------------------------------------------------------------------
# cohort-of-everyone == fleet, bitwise


def test_population_equals_cohort_reproduces_fleet_bitwise():
    arch = small_arch()
    fleet = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    fleet.run(3, log_every=0)
    pop = SplitFTSystem(small_arch(population=arch.data.num_clients),
                        SystemConfig(**SYS), seed=0)
    pop.run(3, log_every=0)
    assert tree_equal(fleet.state, pop.state)
    assert [r["loss"] for r in fleet.history] == \
        [r["loss"] for r in pop.history]


def test_population_sampling_trains_distinct_pids():
    arch = small_arch(population=12)
    sys = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    sys.run(4, log_every=0)
    # 4 cohorts of 3 from 12 pids: more slots materialized than one cohort
    assert len(sys.store) > arch.data.num_clients
    assert np.isfinite(sys.history[-1]["loss"])


def test_population_async_runs():
    arch = small_arch(population=12)
    sys = SplitFTSystem(arch, SystemConfig(
        scheduler="async", buffer_size=2,
        straggler_sim=True, **SYS), seed=0)
    sys.run(3, log_every=0)
    assert len(sys.history) == 3
    assert np.isfinite(sys.history[-1]["loss"])


# ---------------------------------------------------------------------------
# hierarchical aggregation: 1 edge == flat bitwise, E > 1 telescopes


def _agg_inputs(seed=0, n=4):
    arch = small_arch()
    model, state = prepared_state(arch, n=n, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    cad = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, x.size),
                                    x.shape, x.dtype) if
        jnp.issubdtype(x.dtype, jnp.floating) else x,
        state["client_adapters"])
    return model, state, cad


def test_one_edge_hierarchical_is_flat_bitwise():
    model, state, cad = _agg_inputs()
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    cuts = state["cuts"]
    active = jnp.ones(4)
    flat = aggregation.fedavg(model, cad, cuts, w, active)
    one = aggregation.fedavg(model, cad, cuts, w, active,
                             edge_assign=jnp.zeros(4, jnp.int32),
                             num_edges=1)
    assert tree_equal(flat, one)


def test_multi_edge_hierarchical_telescopes_to_flat():
    """Two-tier FedAvg (clients->edge, edges->server) is algebraically
    the flat weighted mean whatever the grouping; pin it numerically."""
    model, state, cad = _agg_inputs()
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    cuts = state["cuts"]
    active = jnp.ones(4)
    flat = aggregation.fedavg(model, cad, cuts, w, active)
    for edges in (jnp.asarray([0, 1, 0, 1], jnp.int32),
                  jnp.asarray([0, 0, 1, 2], jnp.int32)):
        hier = aggregation.fedavg(model, cad, cuts, w, active,
                                  edge_assign=edges,
                                  num_edges=int(edges.max()) + 1)
        for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(hier)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def test_system_edge_groups_one_is_default_bitwise():
    arch = small_arch()
    base = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    base.run(2, log_every=0)
    one = SplitFTSystem(arch, SystemConfig(edge_groups=1, **SYS), seed=0)
    one.run(2, log_every=0)
    assert tree_equal(base.state, one.state)


def test_hierarchical_reduces_charged_server_phase_time():
    """With a finite server ingest link, >= 4 edge groups strictly cut
    the charged adapter-sync+ingest phase vs flat (the edges pre-reduce,
    so the server ingests E adapters instead of N)."""
    arch = small_arch(population=12)
    kw = dict(straggler_sim=True, scheduler="sync",
              server_ingest_bw=1e6, **SYS)
    flat = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
    flat.run(2, log_every=0)
    hier = SplitFTSystem(arch, SystemConfig(edge_groups=4, **kw), seed=0)
    hier.run(2, log_every=0)
    t_flat = flat.history[-1]["phase_times"][4].sum()
    t_hier = hier.history[-1]["phase_times"][4].sum()
    assert t_hier < t_flat


# ---------------------------------------------------------------------------
# client-axis sharding: specs + divisibility fallback + host-mesh parity


def test_state_specs_put_cohort_axis_on_data():
    arch = small_arch()
    _, state = prepared_state(arch, n=4)
    specs = rules.state_specs(state, FakeMesh({"data": 2, "model": 2}))
    assert specs["cuts"] == P("data")
    assert specs["round"] == P()           # global scalar replicates
    a_spec = jax.tree.leaves(
        specs["client_adapters"],
        is_leaf=lambda x: isinstance(x, P))[0]
    assert a_spec[1] == "data"             # (L, N, ...) leaf: axis 1


def test_state_specs_divisibility_fallback():
    arch = small_arch()
    _, state = prepared_state(arch, n=3)   # 3 does not divide data=2
    specs = rules.state_specs(state, FakeMesh({"data": 2, "model": 2}))
    assert specs["cuts"] == P(None)


def test_sharded_engine_matches_unsharded_on_host_mesh():
    arch = small_arch()
    plain = SplitFTSystem(arch, SystemConfig(**SYS), seed=0,
                          policy=NO_SHARDING)
    plain.run(2, log_every=0)
    mesh = make_host_mesh()
    pol = dataclasses.replace(ShardingPolicy(), mesh=mesh,
                              client_mode=True)
    sharded = SplitFTSystem(arch, SystemConfig(**SYS), seed=0,
                            policy=pol)
    sharded.run(2, log_every=0)
    for a, b in zip(jax.tree.leaves(plain.state),
                    jax.tree.leaves(sharded.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# 1/K_i server-gradient normalization (satellite bugfix)


def test_server_step_norm_is_bitwise_noop_at_k1():
    arch = small_arch()
    on = SplitFTSystem(arch, SystemConfig(server_step_norm=True, **SYS),
                       seed=0)
    on.run(2, log_every=0)
    off = SplitFTSystem(arch, SystemConfig(server_step_norm=False, **SYS),
                        seed=0)
    off.run(2, log_every=0)
    assert tree_equal(on.state, off.state)


def test_server_step_norm_changes_heterogeneous_local_steps():
    arch = small_arch()
    kw = dict(scheduler="local_steps", max_local_steps=3,
              straggler_sim=True, speed_sigma=0.8, **SYS)
    on = SplitFTSystem(arch, SystemConfig(server_step_norm=True, **kw),
                       seed=0)
    on.run(2, log_every=0)
    budgets = on.history[-1]["step_budgets"]
    assert budgets.min() != budgets.max()  # actually heterogeneous
    off = SplitFTSystem(arch, SystemConfig(server_step_norm=False, **kw),
                        seed=0)
    off.run(2, log_every=0)
    assert not tree_equal(on.state["server_adapters"],
                          off.state["server_adapters"])


# ---------------------------------------------------------------------------
# checkpoint: sampler RNG round-trips; mismatched population raises


def test_population_checkpoint_resume_bitwise():
    arch = small_arch(population=12)
    straight = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    straight.run(4, log_every=0)
    with tempfile.TemporaryDirectory() as td:
        kw = dict(checkpoint_dir=td, checkpoint_every=2, **SYS)
        first = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
        first.run(2, log_every=0)
        resumed = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
        assert resumed.restore()
        resumed.run(2, log_every=0)
        resumed._pop_scatter()
        assert tree_equal(straight.store.state_tree(),
                          resumed.store.state_tree())


def test_population_mismatch_raises_loudly():
    arch = small_arch()
    with tempfile.TemporaryDirectory() as td:
        kw = dict(checkpoint_dir=td, checkpoint_every=2, **SYS)
        SplitFTSystem(small_arch(population=12), SystemConfig(**kw),
                      seed=0).run(2, log_every=0)
        bad = SplitFTSystem(small_arch(population=24), SystemConfig(**kw),
                            seed=0)
        with pytest.raises(ValueError, match="population"):
            bad.restore()
        fleet = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
        with pytest.raises(ValueError, match="population"):
            fleet.restore()
