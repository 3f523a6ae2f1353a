"""Round-scheduler subsystem tests: policy plans, engine equivalence
(sync == local_steps at K_i = 1, bitwise), step-normalized FedAvg,
smashed-EF residuals, and checkpoint persistence of scheduler state."""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduced
from repro.configs import get_config
from repro.core import aggregation, lora as lora_lib, rounds, \
    scheduler as scheduler_lib
from repro.core.system import SplitFTSystem, SystemConfig
from repro.models.model import build_model
from repro.runtime.straggler import SpeedModel, local_step_budgets


def small_arch(layers=4, lr=3e-3, **split):
    """split: SplitConfig fields (the smashed compressor) to override."""
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=64, batch=4)
    return arch.replace(
        train=dataclasses.replace(arch.train, lr_client=lr, lr_server=lr),
        split=dataclasses.replace(arch.split, **split))


SYS = dict(num_samples=150, eval_samples=32)


def tiny_model(layers=4):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=32,
                   vocab=128, seq_len=16, batch=2)
    return build_model(arch)


# ---------------------------------------------------------------------------
# policy plans (host side)


def test_sync_plan_keeps_everyone_one_step():
    s = scheduler_lib.make_scheduler("sync")
    times = np.array([1.0, 2.0, 10.0])
    plan = s.plan(active=np.ones(3), times=times)
    assert plan.active.tolist() == [1, 1, 1]
    assert plan.step_budgets.tolist() == [1, 1, 1]
    # lockstep: the round costs the slowest client's step
    assert plan.sim_time == 10.0


def test_deadline_plan_drops_stragglers_and_ends_at_survivor():
    s = scheduler_lib.make_scheduler("deadline", deadline_frac=1.5)
    times = np.array([1.0, 2.0, 10.0])
    plan = s.plan(active=np.ones(3), times=times)
    assert plan.active.tolist() == [1, 1, 0]
    assert plan.step_budgets.tolist() == [1, 1, 0]
    assert plan.sim_time == 2.0           # last survivor, not the straggler
    assert plan.deadline == pytest.approx(3.0)


def test_local_steps_plan_speed_proportional():
    s = scheduler_lib.make_scheduler("local_steps", max_local_steps=4)
    times = np.array([1.0, 2.5, 10.0])
    plan = s.plan(active=np.ones(3), times=times)
    # K_i = clamp(floor(10 / t_i), 1, 4); nobody dropped
    assert plan.active.tolist() == [1, 1, 1]
    assert plan.step_budgets.tolist() == [4, 4, 1]
    # everyone finishes by the sync barrier
    assert plan.sim_time == 10.0
    assert (plan.step_budgets * times <= plan.sim_time + 1e-9).all()


def test_local_step_budgets_respects_membership_and_cap():
    times = np.array([1.0, 1.0, 8.0, 100.0])
    active = np.array([1.0, 0.0, 1.0, 1.0])
    k = local_step_budgets(times, max_steps=16, active=active)
    assert k[1] == 0                      # inactive -> no budget
    assert k[3] == 1                      # slowest active anchors at 1
    assert k[0] == 16                     # capped (100/1 > 16)
    assert k[2] == 12                     # floor(100/8)


def test_deadline_plan_ignores_inactive_clients():
    """Regression (ISSUE 5): departed clients' stale time estimates must
    not skew the deadline.  Here three fast leavers drag the full-fleet
    median to 5.5 (deadline 8.25) — under the old behaviour NO active
    client survives and the fallback resurrects an INACTIVE client,
    leaving the round empty after the active-mask intersection.  With
    the median over active clients only, the deadline is 16.5 and the
    two healthy survivors stay."""
    s = scheduler_lib.make_scheduler("deadline", deadline_frac=1.5)
    times = np.array([10.0, 11.0, 30.0, 1.0, 1.0, 1.0])
    active = np.array([1.0, 1.0, 1.0, 0.0, 0.0, 0.0])
    plan = s.plan(active=active, times=times)
    assert plan.active.tolist() == [1, 1, 0, 0, 0, 0]
    assert plan.deadline == pytest.approx(16.5)
    assert plan.sim_time == 11.0          # last active survivor


def test_deadline_survivors_active_unit():
    from repro.runtime.straggler import deadline_survivors
    t = np.array([4.0, 2.0, 100.0])
    # no mask -> whole fleet (legacy behaviour)
    m, d = deadline_survivors(t, deadline_frac=1.5)
    assert m.tolist() == [True, True, False]  # median 4 -> deadline 6
    assert d == pytest.approx(6.0)
    # the fallback keeps the fastest ACTIVE client, never a departed one
    m, d = deadline_survivors(t, deadline_frac=0.1,
                              active=np.array([1.0, 0.0, 1.0]))
    assert m.tolist() == [True, False, False]
    # an inactive client is never a survivor
    m, _ = deadline_survivors(t, deadline_frac=100.0,
                              active=np.array([0.0, 1.0, 1.0]))
    assert m.tolist() == [False, True, True]
    # empty pool -> nobody survives (no crash)
    m, d = deadline_survivors(t, active=np.zeros(3))
    assert not m.any() and d == 0.0


def test_deadline_fallback_tied_times_single_survivor():
    """Regression: when nobody makes the deadline and the fastest time
    is TIED, the fallback must keep exactly one survivor (the
    deterministic argmin) — a float-equality mask against the min would
    keep every tied client and the round's aggregate would depend on
    how ties happened to materialize."""
    from repro.runtime.straggler import deadline_survivors
    t = np.array([5.0, 5.0, 9.0])
    m, _ = deadline_survivors(t, deadline_frac=0.1)
    assert m.tolist() == [True, False, False]
    # ties among ACTIVE clients only: the inactive copy of the minimum
    # at slot 0 must never win
    m, _ = deadline_survivors(t, deadline_frac=0.01,
                              active=np.array([0.0, 1.0, 1.0]))
    assert m.tolist() == [False, True, False]
    # an all-tied fleet still yields exactly one survivor
    m, _ = deadline_survivors(np.full(4, 3.0), deadline_frac=0.0)
    assert m.tolist() == [True, False, False, False]


def test_unknown_scheduler_raises():
    with pytest.raises(ValueError):
        scheduler_lib.make_scheduler("gossip")
    with pytest.raises(ValueError):
        scheduler_lib.make_scheduler("local_steps", max_local_steps=0)


def test_deadline_without_speed_model_raises():
    s = scheduler_lib.make_scheduler("deadline")
    with pytest.raises(ValueError):
        s.plan(active=np.ones(3), times=None)


# ---------------------------------------------------------------------------
# engine equivalence: the K-step scan with all budgets == 1 is the sync
# step, bit for bit (under jit, the deployment configuration)


def test_local_steps_engine_k1_bit_identical_to_sync():
    model = tiny_model()
    arch = model.arch
    n = 3
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    v = arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (n, 2, 16), 3, v),
             "labels": jax.random.randint(key, (n, 2, 16), 3, v),
             "loss_mask": jnp.ones((n, 2, 16), jnp.float32)}
    w = jnp.ones(n) / n
    act = jnp.ones(n)
    lr = jnp.float32(1e-2)
    K = 3

    s_sync = rounds.init_state(model, key, num_clients=n)
    step_sync = rounds.make_train_step(model, jit=True)
    s_ls = rounds.with_step_budgets(
        rounds.init_state(model, key, num_clients=n))
    step_ls = rounds.make_train_step(model, max_local_steps=K, jit=True)

    for _ in range(3):
        batch_k = jax.tree.map(lambda t: jnp.stack([t] * K), batch)
        s_sync, m1 = step_sync(params, s_sync, batch, w, act, lr, lr)
        s_ls, mk = step_ls(params, s_ls, batch_k, w, act, lr, lr)

    assert int(s_ls["round"]) == int(s_sync["round"]) == 3
    np.testing.assert_array_equal(np.asarray(m1["total"]),
                                  np.asarray(mk["total"]))
    for k in ("client_adapters", "server_adapters", "opt_c", "opt_s"):
        for a, b in zip(jax.tree.leaves(s_sync[k]),
                        jax.tree.leaves(s_ls[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_local_steps_budgets_freeze_exhausted_clients():
    """A client with budget 1 must end the round with exactly its
    one-step adapters; a budget-K client must differ from them."""
    model = tiny_model()
    arch = model.arch
    n = 2
    key = jax.random.PRNGKey(1)
    params = model.init_params(key)
    v = arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (n, 2, 16), 3, v),
             "labels": jax.random.randint(key, (n, 2, 16), 3, v),
             "loss_mask": jnp.ones((n, 2, 16), jnp.float32)}
    w = jnp.ones(n) / n
    act = jnp.ones(n)
    lr = jnp.float32(1e-2)
    K = 3
    batch_k = jax.tree.map(lambda t: jnp.stack([t] * K), batch)

    # agg_every large so FedAvg does not mix the clients this round
    def run(budgets):
        state = rounds.with_step_budgets(
            rounds.init_state(model, key, num_clients=n))
        state["step_budgets"] = jnp.asarray(budgets, jnp.int32)
        step = rounds.make_train_step(model, max_local_steps=K,
                                      agg_every=100, jit=True)
        state, _ = step(params, state, batch_k, w, act, lr, lr)
        return state

    s_hetero = run([1, K])
    s_ones = run([1, 1])
    a_het = np.asarray(s_hetero["client_adapters"]["dec"]["q"]["A"])
    a_one = np.asarray(s_ones["client_adapters"]["dec"]["q"]["A"])
    # client 0 (budget 1) froze after step 1 in both runs
    np.testing.assert_array_equal(a_het[:, 0], a_one[:, 0])
    # client 1 kept stepping
    assert np.abs(a_het[:, 1] - a_one[:, 1]).max() > 0


# ---------------------------------------------------------------------------
# step-normalized FedAvg


def test_fedavg_steps_divide_weights():
    model = tiny_model()
    n = 3
    cad = lora_lib.init_adapters(model, jax.random.PRNGKey(0),
                                 num_clients=n)
    cuts = jnp.asarray([2, 2, 2])
    w = jnp.asarray([0.5, 0.3, 0.2])
    act = jnp.ones(n)
    steps = jnp.asarray([1.0, 2.0, 4.0])
    a = aggregation.fedavg(model, cad, cuts, w, act, steps=steps)
    b = aggregation.fedavg(model, cad, cuts, w / steps, act)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)
    # steps=None / all-ones is the unnormalized paper rule
    c = aggregation.fedavg(model, cad, cuts, w, act,
                           steps=jnp.ones(n))
    d = aggregation.fedavg(model, cad, cuts, w, act)
    for x, y in zip(jax.tree.leaves(c), jax.tree.leaves(d)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# system level: scheduler selection, legacy spelling, persistence


def test_straggler_sim_legacy_maps_to_deadline():
    sys_ = SplitFTSystem(small_arch(), SystemConfig(straggler_sim=True,
                                                    **SYS), seed=3)
    assert sys_.scheduler.name == "deadline"
    sys2 = SplitFTSystem(small_arch(), SystemConfig(straggler_sim=True,
                                                    scheduler="sync",
                                                    **SYS), seed=3)
    assert sys2.scheduler.name == "sync"          # explicit sync wins
    assert sys2.speed is not None                 # but still simulates


def test_local_steps_system_trains_and_records_budgets():
    cfg = SystemConfig(scheduler="local_steps", max_local_steps=4, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    hist = sys_.run(4, log_every=0)
    for h in hist:
        b = h["step_budgets"]
        assert b.max() <= 4 and b[h["active"] > 0].min() >= 1
        assert h["sim_time"] > 0
    assert hist[-1]["sim_clock"] == pytest.approx(
        sum(h["sim_time"] for h in hist))
    assert np.isfinite(hist[-1]["loss"])
    # fast clients ship more smashed bytes than slow ones
    assert np.sum(hist[-1]["comm"]) > 0


def test_local_steps_k1_system_matches_sync_bitwise():
    """max_local_steps=1 degenerates local_steps to the sync engine."""
    s_sync = SplitFTSystem(small_arch(), SystemConfig(**SYS), seed=0)
    s_sync.run(3, log_every=0)
    cfg = SystemConfig(scheduler="local_steps", max_local_steps=1, **SYS)
    s_ls = SplitFTSystem(small_arch(), cfg, seed=0)
    s_ls.run(3, log_every=0)
    a = np.asarray(s_sync.state["client_adapters"]["dec"]["q"]["A"])
    b = np.asarray(s_ls.state["client_adapters"]["dec"]["q"]["A"])
    np.testing.assert_array_equal(a, b)


def test_deadline_comm_record_skips_dropped_clients():
    """A dropped client transmits no smashed bytes and no b1 update; it
    still receives the b3 broadcast."""
    cfg = SystemConfig(straggler_sim=True, deadline_frac=1.2, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=3)
    hist = sys_.run(6, log_every=0)
    dropped = [h for h in hist if h["active"].sum() < 3]
    assert dropped
    h = dropped[0]
    i = int(np.argmin(h["active"]))
    j = int(np.argmax(h["active"]))
    assert h["comm_smashed"][i] == 0
    assert h["comm_smashed"][j] > 0
    assert 0 < h["comm"][i] < h["comm"][j]    # b3 broadcast only


def test_smashed_ef_requires_topk():
    cfg = SystemConfig(smashed_ef=True, **SYS)
    with pytest.raises(ValueError, match="topk"):
        SplitFTSystem(small_arch(smashed_compress="int8"), cfg, seed=0)


def test_restore_with_different_scheduler_raises():
    arch = small_arch()
    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(checkpoint_dir=d, checkpoint_every=2, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=0)
        s1.run(2, log_every=0)
        cfg2 = dataclasses.replace(cfg, scheduler="local_steps")
        s2 = SplitFTSystem(arch, cfg2, seed=0)
        with pytest.raises(ValueError, match="scheduler"):
            s2.restore()


def test_restore_with_different_state_template_raises():
    """Same scheduler, but the smashed-EF leaf vanished: restore must
    diagnose the template change, not silently restart from round 0."""
    arch = small_arch(smashed_compress="topk", smashed_topk_frac=0.25)
    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(checkpoint_dir=d, checkpoint_every=2, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=0)
        s1.run(2, log_every=0)
        arch2 = arch.replace(split=dataclasses.replace(
            arch.split, smashed_compress="none"))
        s2 = SplitFTSystem(arch2, cfg, seed=0)
        with pytest.raises(ValueError, match="template"):
            s2.restore()


def test_checkpoint_roundtrips_scheduler_state():
    """step budgets + smashed EF residuals survive save/restore exactly."""
    arch = small_arch(smashed_compress="topk", smashed_topk_frac=0.25)
    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(scheduler="local_steps", max_local_steps=3,
                           checkpoint_dir=d, checkpoint_every=2, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=0)
        s1.run(4, log_every=0)
        assert "step_budgets" in s1.state and "smashed_ef" in s1.state
        assert np.abs(np.asarray(s1.state["smashed_ef"])).max() > 0

        s2 = SplitFTSystem(arch, cfg, seed=0)
        assert s2.restore()
        assert int(s2.state["round"]) == 4
        np.testing.assert_array_equal(
            np.asarray(s1.state["step_budgets"]),
            np.asarray(s2.state["step_budgets"]))
        np.testing.assert_array_equal(
            np.asarray(s1.state["smashed_ef"]),
            np.asarray(s2.state["smashed_ef"]))
        assert s2.sim_clock == pytest.approx(s1.sim_clock)
        s2.run(2, log_every=0)            # continues fine


def test_per_client_adam_count_fixes_bias_correction():
    """ROADMAP bug: the inner scan shared one Adam step count across
    clients, so a budget-1 client's round-2 bias correction used the
    budget-K client's count.  With per-client counts
    (with_per_client_opt_steps) the budget-1 client must evolve exactly
    as in a run where EVERY budget is 1; with the legacy shared count it
    must not (the regression this test pins).  lr_s=0 freezes the shared
    server side and grad_clip=0 removes the cross-client clip coupling,
    so the budget-1 client's inputs are identical across runs."""
    arch = reduced(get_config("gpt2-small"), layers=4, d_model=32,
                   vocab=128, seq_len=16, batch=2)
    arch = arch.replace(train=dataclasses.replace(arch.train,
                                                  grad_clip=0.0))
    model = build_model(arch)
    n, K = 2, 3
    key = jax.random.PRNGKey(1)
    params = model.init_params(key)
    v = arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (n, 2, 16), 3, v),
             "labels": jax.random.randint(key, (n, 2, 16), 3, v),
             "loss_mask": jnp.ones((n, 2, 16), jnp.float32)}
    batch_k = jax.tree.map(lambda t: jnp.stack([t] * K), batch)
    w = jnp.ones(n) / n
    act = jnp.ones(n)
    lr_c, lr_s = jnp.float32(1e-2), jnp.float32(0.0)

    def run(budgets, per_client):
        state = rounds.with_step_budgets(
            rounds.init_state(model, key, num_clients=n))
        if per_client:
            state = rounds.with_per_client_opt_steps(state)
        state["step_budgets"] = jnp.asarray(budgets, jnp.int32)
        step = rounds.make_train_step(model, max_local_steps=K,
                                      agg_every=100, jit=True)
        for _ in range(2):
            state, _ = step(params, state, batch_k, w, act, lr_c, lr_s)
        return state

    def client0(state):
        return np.asarray(state["client_adapters"]["dec"]["q"]["A"])[:, 0]

    s_het = run([1, K], per_client=True)
    s_ones = run([1, 1], per_client=True)
    # fixed: the budget-1 client is exactly a K_i=1 independent run
    np.testing.assert_array_equal(client0(s_het), client0(s_ones))
    np.testing.assert_array_equal(
        np.asarray(s_het["opt_c"]["count"]), [2, 2 * K])
    # legacy shared count: client 0's round-2 step used count 4, not 2
    s_legacy = run([1, K], per_client=False)
    assert int(np.asarray(s_legacy["opt_c"]["count"])) == 2 * K
    assert np.abs(client0(s_legacy) - client0(s_ones)).max() > 0


# ---------------------------------------------------------------------------
# async (FedBuff) scheduler: system behavior, checkpointing, validation


def test_async_buffer_size_clamps_to_fleet():
    cfg = SystemConfig(scheduler="async", buffer_size=99, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    assert sys_.scheduler.buffer_size == 3          # num_clients
    assert "buffer_mask" in sys_.state
    assert "adapter_version" in sys_.state


def test_async_system_trains_and_records():
    cfg = SystemConfig(scheduler="async", buffer_size=2, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    hist = sys_.run(5, log_every=0)
    assert len(hist) == 5
    for h in hist:
        assert h["buffer_fill"] >= 2
        assert (h["staleness"] >= 0).all()
        assert h["round_steps"].sum() >= h["buffer_fill"]
        # buffered clients pay smashed + adapter bytes; in-flight pay none
        # at the boundary beyond their completed smashed exchanges
        assert np.sum(h["comm"]) > 0
    assert int(sys_.state["global_version"]) == 5
    assert np.isfinite(hist[-1]["loss"])


def test_async_checkpoint_roundtrip_mid_buffer():
    """Save with a PARTIALLY FULL buffer (between aggregations), restore
    into a fresh system, and the next aggregation must be bitwise
    identical to the uninterrupted run — buffer contents, per-client
    adapter versions and the event-queue clock all round-trip."""
    arch = small_arch()
    lr = jnp.float32(arch.train.lr_client)

    def ticks_until_agg(sys_):
        rec = None
        while rec is None:
            rec = sys_._async_tick(2, lr, lr)
        return rec

    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(scheduler="async", buffer_size=3,
                           checkpoint_dir=d, adaptive=False, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=3)
        s1.run(2, log_every=0)
        # tick manually until the buffer holds someone but has not flushed
        while float(np.asarray(s1.state["buffer_mask"]).sum()) == 0:
            assert s1._async_tick(2, lr, lr) is None
        assert 0 < float(np.asarray(s1.state["buffer_mask"]).sum()) < 3
        s1.save(42)

        s2 = SplitFTSystem(arch, cfg, seed=3)
        assert s2.restore()
        np.testing.assert_array_equal(
            np.asarray(s1.state["buffer_mask"]),
            np.asarray(s2.state["buffer_mask"]))
        np.testing.assert_array_equal(
            np.asarray(s1.state["adapter_version"]),
            np.asarray(s2.state["adapter_version"]))
        assert s2.scheduler.queue.now == s1.scheduler.queue.now
        assert s2.scheduler.queue.state_dict() == \
            s1.scheduler.queue.state_dict()

        rec1 = ticks_until_agg(s1)
        rec2 = ticks_until_agg(s2)
        assert rec1["loss"] == rec2["loss"]
        assert rec1["sim_clock"] == rec2["sim_clock"]
        np.testing.assert_array_equal(rec1["staleness"], rec2["staleness"])
        for a, b in zip(jax.tree.leaves(s1.state["client_adapters"]),
                        jax.tree.leaves(s2.state["client_adapters"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_async_against_sync_checkpoint_raises():
    """Resuming async from a sync checkpoint must fail loudly: the state
    templates differ (buffer/version leaves) and the saved scheduler name
    is the diagnosis restore() reports."""
    arch = small_arch()
    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(checkpoint_dir=d, checkpoint_every=2, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=0)
        s1.run(2, log_every=0)
        cfg2 = dataclasses.replace(cfg, scheduler="async")
        s2 = SplitFTSystem(arch, cfg2, seed=0)
        with pytest.raises(ValueError, match="scheduler"):
            s2.restore()


def test_async_engine_validation():
    model = tiny_model()
    with pytest.raises(ValueError, match="compress"):
        rounds.make_train_step(model, async_buffer=True, compress="topk")
    with pytest.raises(ValueError, match="compose"):
        rounds.make_train_step(model, async_buffer=True, max_local_steps=2)
    with pytest.raises(ValueError, match="agg_every"):
        rounds.make_train_step(model, async_buffer=True, agg_every=2)
    with pytest.raises(ValueError, match="buffer_size"):
        rounds.make_train_step(model, async_buffer=True, buffer_size=0)
    with pytest.raises(ValueError, match="buffer_size"):
        scheduler_lib.make_scheduler("async", buffer_size=0)
    with pytest.raises(NotImplementedError):
        scheduler_lib.make_scheduler("async").plan(active=np.ones(3))
    # an unfillable buffer fails at trace time, not by hanging
    key = jax.random.PRNGKey(0)
    state = rounds.with_per_client_opt_steps(rounds.with_async_buffer(
        rounds.init_state(model, key, num_clients=2)))
    step = rounds.make_train_step(model, async_buffer=True, buffer_size=5,
                                  jit=True)
    v = model.arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (2, 2, 16), 3, v),
             "labels": jax.random.randint(key, (2, 2, 16), 3, v),
             "loss_mask": jnp.ones((2, 2, 16), jnp.float32)}
    with pytest.raises(ValueError, match="never fill"):
        step(model.init_params(key), state, batch, jnp.ones(2) / 2,
             jnp.ones(2), jnp.float32(1e-2), jnp.float32(1e-2))


def test_async_shrunken_pool_raises_instead_of_hanging():
    cfg = SystemConfig(scheduler="async", buffer_size=3, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    sys_.pool.leave(0)
    with pytest.raises(RuntimeError, match="never fill"):
        sys_.run(1, log_every=0)


@pytest.mark.parametrize("overlap", [False, True])
def test_async_elastic_leave_drops_events_and_rejoin_reenters(overlap):
    """Regression (ISSUE 5): a client that leaves mid-flight must not
    keep ticking as a zombie — its pending events are dropped, it is
    never relaunched, and its launch counter freezes; on rejoin it
    re-enters at the current clock and contributes again.  The queue's
    client set tracks the active fleet throughout."""
    const = dict(speed_sigma=0.0, bw_sigma=0.0, jitter_sigma=0.0)
    cfg = SystemConfig(scheduler="async", buffer_size=2, adaptive=False,
                       overlap_comm=overlap, **const, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    sys_.run(2, log_every=0)
    sched = sys_.scheduler

    sys_.pool.leave(1)
    frozen = int(sched.launches[1])
    h = sys_.run(3, log_every=0)
    assert sched.queue.clients() == {0, 2}      # no zombie events
    assert int(sched.launches[1]) == frozen     # never relaunched
    for rec in h[-3:]:
        assert rec["round_steps"][1] == 0       # never contributed
        assert rec["active"][1] == 0.0

    sys_.pool.join(1)
    h = sys_.run(3, log_every=0)
    assert sched.queue.clients() == {0, 1, 2}   # re-entered at the clock
    assert int(sched.launches[1]) > frozen      # training again
    # with a constant-speed fleet the rejoiner lands in a flush again
    assert any(rec["round_steps"][1] > 0 for rec in h[-3:])
    clocks = [rec["sim_clock"] for rec in sys_.history]
    assert all(b >= a for a, b in zip(clocks, clocks[1:]))
    assert np.isfinite(h[-1]["loss"])


def test_async_flush_times_drawn_at_launch_indices():
    """Regression (ISSUE 5): the flush record's `round_time_sim` must be
    the serial step time each contributor experienced at ITS launch
    index — not a fresh full-fleet draw at the aggregation-round index,
    which no client's tick ever used.  With per-launch jitter the two
    disagree unless the record tracks actual launches."""
    from repro.runtime.straggler import serial_step_times

    cfg = SystemConfig(scheduler="async", buffer_size=2, adaptive=False,
                       jitter_sigma=0.3, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=3)
    hist = sys_.run(4, log_every=0)
    sched = sys_.scheduler
    cuts_np = np.asarray(sys_.state["cuts"])
    cb = sys_._cached_comm(cuts_np)
    # after the run, each client's recorded time equals the draw at the
    # launch index it last completed (launches[i] - 1)
    last = hist[-1]["round_time_sim"]
    for i in range(3):
        launch = int(sched.launches[i]) - 1
        if launch < 0:
            continue
        t_i = serial_step_times(
            sys_._cached_phases(launch, cuts_np, cb))[i]
        assert last[i] == t_i
    # and clients complete at DIFFERENT launch indices under async, so
    # a single aggregation-round draw could not have produced this
    assert len({int(k) for k in sched.launches}) > 1


def test_smashed_ef_frozen_for_inactive_clients():
    """A deadline-dropped client transmitted nothing this round: its
    accumulated EF residual must survive the round unchanged (both
    engines)."""
    model = tiny_model()
    arch = model.arch
    n = 2
    key = jax.random.PRNGKey(2)
    params = model.init_params(key)
    v = arch.model.vocab_size
    batch = {"tokens": jax.random.randint(key, (n, 2, 16), 3, v),
             "labels": jax.random.randint(key, (n, 2, 16), 3, v),
             "loss_mask": jnp.ones((n, 2, 16), jnp.float32)}
    w = jnp.ones(n) / n
    lr = jnp.float32(1e-2)

    def ef_after(active, local_steps):
        state = rounds.with_smashed_ef(
            rounds.init_state(model, key, num_clients=n), model)
        if local_steps:
            state = rounds.with_step_budgets(state)
            step = rounds.make_train_step(model, smashed_compress="topk",
                                          max_local_steps=2, jit=True)
            b = jax.tree.map(lambda t: jnp.stack([t] * 2), batch)
        else:
            step = rounds.make_train_step(model, smashed_compress="topk",
                                          jit=True)
            b = batch
        state, _ = step(params, state, b, w, jnp.asarray(active), lr, lr)
        return np.asarray(state["smashed_ef"])

    for local_steps in (False, True):
        ef = ef_after([1.0, 0.0], local_steps)
        assert np.abs(ef[0]).max() > 0          # active client accumulated
        np.testing.assert_array_equal(ef[1], 0)  # dropped client untouched


def test_smashed_ef_residual_updates_at_boundary():
    """Unit check of the stateful EF boundary: at the cut layer,
    y + residual' == x + residual (nothing lost), and only the cut
    client's rows change."""
    from repro.core import smashed

    c = smashed.make_compressor("topk", topk_frac=0.25)
    n, b, s, d = 2, 2, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (n, b, s, d))
    resid = jax.random.normal(jax.random.PRNGKey(1), (n, b, s, d)) * 0.1
    hook = smashed.make_boundary(c, jnp.asarray([2, 3]), residual=resid)
    assert hook.stateful
    carry = hook.init()
    y, carry = hook(x, carry, jnp.int32(1))   # cut-1 for client 0 only
    xn, yn, cn, rn = map(np.asarray, (x, y, carry, resid))
    # client 1 untouched at this layer
    np.testing.assert_array_equal(yn[1], xn[1])
    np.testing.assert_array_equal(cn[1], 0.0)
    # client 0: compressed message + residual' reconstructs x + residual
    np.testing.assert_allclose(yn[0] + cn[0], xn[0] + rn[0],
                               rtol=1e-5, atol=1e-6)
    # and the message really is sparse
    assert (yn[0] == 0).mean() > 0.5
