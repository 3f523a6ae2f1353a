"""Trace-driven heterogeneity pins (ISSUE 9).

  * constant trace == stationary SpeedModel, bitwise, under EVERY
    scheduler (losses, simulated clocks, adapter digests) — the
    backward-compatibility pin that transfers the whole scheduler-
    equivalence test family to trace mode;
  * trace replay is deterministic: same generator spec/seed (or same
    trace file) -> identical factors, in any query order;
  * checkpoint-resume mid-trace == straight run, bitwise (the trace
    cursor rides checkpoint metadata);
  * trace values are data: a churning trace never retraces the engine;
  * availability gates participation (barrier rounds mask, an
    all-unavailable window advances the clock to the next available
    instant) and actually reshapes the simulated clock.
"""

import dataclasses
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.config import reduced
from repro.configs import get_config
from repro.core.system import SplitFTSystem, SystemConfig
from repro.runtime import traces
from repro.runtime.straggler import SpeedModel


def small_arch(layers=4, lr=3e-3):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=32, batch=2)
    return arch.replace(train=dataclasses.replace(
        arch.train, lr_client=lr, lr_server=lr))


SYS = dict(num_samples=80, eval_samples=16)
CHURN = ("diurnal:amp=0.8,period=500,sigma=0.3,step=50"
         "+markov:p_down=0.1,p_up=0.5+cells:k=2+thermal:floor=0.5")


def adapter_digest(state):
    return tuple(np.asarray(leaf).tobytes()
                 for key in ("client_adapters", "server_adapters")
                 for leaf in jax.tree.leaves(state[key]))


# ---------------------------------------------------------------------------
# the backward-compatibility pin: constant trace == stationary, bitwise


SCHED_CONFIGS = {
    "sync": dict(scheduler="sync"),
    "deadline": dict(scheduler="deadline", deadline_frac=1.2),
    "local_steps": dict(scheduler="local_steps", max_local_steps=3),
    "async": dict(scheduler="async", buffer_size=2),
    "async_overlap": dict(scheduler="async", buffer_size=2,
                          overlap_comm=True),
}


@pytest.mark.parametrize("sched", sorted(SCHED_CONFIGS))
def test_constant_trace_is_stationary_clock_bitwise(sched):
    """trace factors of exactly 1.0 multiply through (x * 1.0 is IEEE
    identity) and max(t, next_available(t)) == t, so the whole run —
    losses, clocks, adapter trees — must be bit-identical to the
    stationary SpeedModel under every scheduler, jitter included."""
    kw = dict(straggler_sim=True, adaptive=False,
              **SCHED_CONFIGS[sched], **SYS)
    base = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hb = base.run(4, log_every=0)
    traced = SplitFTSystem(small_arch(),
                           SystemConfig(trace_gen="const", **kw), seed=0)
    ht = traced.run(4, log_every=0)
    assert isinstance(traced.speed.trace, traces.ConstantTrace)
    for a, b in zip(hb, ht):
        assert a["loss"] == b["loss"]
        assert a["sim_clock"] == b["sim_clock"]
        assert a["sim_time"] == b["sim_time"]
        np.testing.assert_array_equal(a["active"], b["active"])
        np.testing.assert_array_equal(a["round_time_sim"],
                                      b["round_time_sim"])
    assert adapter_digest(base.state) == adapter_digest(traced.state)


# ---------------------------------------------------------------------------
# replay determinism: pure functions of (pid, window), any query order


def test_generator_replay_deterministic_any_query_order():
    a = traces.make_trace_gen(CHURN, seed=7)
    b = traces.make_trace_gen(CHURN, seed=7)
    pids = [3, 11, 40]
    ts = [0.0, 260.0, 90.0, 1000.0, 260.0, 30.0]    # out of order, dup
    for t in ts:                       # a queries forward...
        a.sample(t, pids)
    for t in reversed(ts):             # ...b in the reverse order
        b.sample(t, pids)
    for t in ts:
        for x, y in zip(a.sample(t, pids), b.sample(t, pids)):
            np.testing.assert_array_equal(x, y)
    # a different seed actually changes the draw
    c = traces.make_trace_gen(CHURN, seed=8)
    assert not np.array_equal(a.sample(260.0, pids)[0],
                              c.sample(260.0, pids)[0])


def test_generator_series_keyed_by_pid_not_slot():
    """pid 11's series is the same whether it is queried alone, in a
    different cohort, or at a different slot position — the
    population_speed_draws pattern extended through time."""
    g = traces.make_trace_gen(CHURN, seed=3)
    solo = [g.sample(t, [11]) for t in (0.0, 260.0, 700.0)]
    h = traces.make_trace_gen(CHURN, seed=3)
    mixed = [h.sample(t, [40, 2, 11]) for t in (0.0, 260.0, 700.0)]
    for (ss, sb, sv), (ms, mb, mv) in zip(solo, mixed):
        assert ss[0] == ms[2] and sb[0] == mb[2] and sv[0] == mv[2]


def test_file_trace_replay_and_pid_wrap(tmp_path):
    path = os.path.join(tmp_path, "t.json")
    spec = {"step": 10.0,
            "speed": [[1.0, 0.5], [2.0, 0.25]],
            "bandwidth": [[1.0, 4.0], [0.5, 1.0]],
            "available": [[1, 1], [1, 0]]}
    with open(path, "w") as f:
        json.dump(spec, f)
    tr = traces.load_trace(path)
    sp, bw, av = tr.sample(0.0, [0, 1, 2])
    np.testing.assert_array_equal(sp, [1.0, 0.5, 1.0])   # pid 2 -> col 0
    np.testing.assert_array_equal(bw, [1.0, 4.0, 1.0])
    sp2, bw2, av2 = tr.sample(15.0, [0, 1])
    np.testing.assert_array_equal(sp2, [2.0, 0.25])
    np.testing.assert_array_equal(av2, [True, False])
    # rows wrap periodically past the end
    np.testing.assert_array_equal(tr.sample(25.0, [0])[0],
                                  tr.sample(5.0, [0])[0])
    # pid 1 is down in window 1: next_available skips to window 2
    assert tr.next_available(15.0, 1) == 20.0
    assert tr.next_available(15.0, 0) == 15.0
    # replay: a second load sees identical values
    tr2 = traces.load_trace(path)
    for t in (0.0, 15.0, 25.0):
        for x, y in zip(tr.sample(t, [0, 1, 5]), tr2.sample(t, [0, 1, 5])):
            np.testing.assert_array_equal(x, y)


def test_thermal_ramp_and_markov_reset():
    g = traces.make_trace_gen("thermal:floor=0.5,heat=100,step=10",
                              seed=0)
    # no markov: the device never rests, so the ramp runs from t=0 down
    # to the floor and stays there
    s0 = g.sample(0.0, [1])[0][0]
    s50 = g.sample(50.0, [1])[0][0]
    s500 = g.sample(500.0, [1])[0][0]
    assert s0 == 1.0 and s0 > s50 > s500 == 0.5


def test_markov_availability_churns_and_recovers():
    g = traces.make_trace_gen("markov:p_down=0.3,p_up=0.5,step=10",
                              seed=1)
    avail = [bool(g.sample(10.0 * k, [4])[2][0]) for k in range(200)]
    assert not all(avail) and any(avail)     # actually churns
    # next_available lands on an available window start
    t_down = 10.0 * avail.index(False)
    t_next = g.next_available(t_down, 4)
    assert t_next > t_down
    assert bool(g.sample(t_next, [4])[2][0])


def test_spec_parser_rejects_unknowns():
    with pytest.raises(ValueError, match="unknown trace component"):
        traces.make_trace_gen("lunar")
    with pytest.raises(ValueError, match="unknown knob"):
        traces.make_trace_gen("diurnal:volume=11")
    with pytest.raises(ValueError, match="compose"):
        traces.make_trace_gen("const+diurnal")
    with pytest.raises(ValueError, match="duplicate"):
        traces.make_trace_gen("markov+markov")
    with pytest.raises(ValueError, match="empty"):
        traces.make_trace_gen("  ")


def test_system_rejects_trace_and_trace_gen_together():
    with pytest.raises(ValueError, match="not.*both|not\\s+both"):
        SplitFTSystem(small_arch(),
                      SystemConfig(trace="x.json", trace_gen="const",
                                   **SYS), seed=0)


# ---------------------------------------------------------------------------
# the trace actually reshapes the simulated clock (not a silent no-op)


def test_trace_changes_clock_and_prices_controller_window():
    kw = dict(straggler_sim=True, adaptive=False, scheduler="sync", **SYS)
    base = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hb = base.run(3, log_every=0)
    traced = SplitFTSystem(
        small_arch(),
        SystemConfig(trace_gen="diurnal:amp=1.0,period=40,step=10",
                     **kw), seed=0)
    ht = traced.run(3, log_every=0)
    assert ht[-1]["sim_clock"] != hb[-1]["sim_clock"]
    # predict_round_times prices at the CURRENT trace window: advancing
    # the clock into another window moves the prediction
    cuts = np.asarray(traced.state["cuts"])
    p_now = traced.predict_round_times(3, cuts)
    traced.sim_clock += 20.0                   # half a diurnal period
    p_later = traced.predict_round_times(3, cuts)
    assert not np.array_equal(p_now, p_later)


def tiny_arch(**data):
    """4 layers at d_model 32, 3 clients: the smallest arch on which the
    schedulers' simulated clocks separate."""
    arch = reduced(get_config("gpt2-small"), layers=4, d_model=32,
                   vocab=256, seq_len=16, batch=2)
    return arch.replace(
        train=dataclasses.replace(arch.train, lr_client=3e-3,
                                  lr_server=3e-3),
        data=dataclasses.replace(arch.data, num_clients=3, **data))


def time_to_target(hist, target):
    """Simulated clock at the first round whose loss reaches target."""
    return next(h["sim_clock"] for h in hist if h["loss"] <= target)


@pytest.mark.parametrize("alpha", [100.0, 0.3])
@pytest.mark.parametrize("compress", ["none", "int8"])
def test_diurnal_async_beats_sync_time_to_target(alpha, compress):
    """Under a diurnal trace the sync barrier waits for whoever is in its
    trough; the async buffer flush does not.  The target is the weaker
    lane's best loss, so both lanes reach it; the deterministic clock
    (jitter_sigma=0) makes the comparison exact."""
    arch = tiny_arch(partition="dirichlet", alpha=alpha)
    arch = arch.replace(split=dataclasses.replace(
        arch.split, smashed_compress=compress))
    kw = dict(num_samples=48, eval_samples=16, straggler_sim=True,
              jitter_sigma=0.0,
              trace_gen="diurnal:amp=1.0,period=240,step=20")
    hist = {sched: SplitFTSystem(arch, SystemConfig(scheduler=sched, **kw,
                                                    **extra),
                                 seed=0).run(2, log_every=0)
            for sched, extra in (("sync", {}),
                                 ("async", dict(buffer_size=2)))}
    target = max(min(h["loss"] for h in hs) for hs in hist.values())
    assert time_to_target(hist["async"], target) \
        < time_to_target(hist["sync"], target)


def test_file_trace_availability_masks_barrier_round(tmp_path):
    """Client 0 is never available: every sync round runs without it,
    and an all-down first window makes the round WAIT (clock advances to
    the next available instant before pricing)."""
    path = os.path.join(tmp_path, "avail.json")
    with open(path, "w") as f:
        json.dump({"step": 1000.0,
                   "available": [[0, 0, 0], [0, 1, 1]]}, f)
    sys_ = SplitFTSystem(
        small_arch(),
        SystemConfig(trace=path, straggler_sim=True, adaptive=False,
                     scheduler="sync", **SYS), seed=0)
    h = sys_.run(2, log_every=0)
    # round 0 waited out the all-down window 0
    assert h[0]["sim_clock"] >= 1000.0
    np.testing.assert_array_equal(h[0]["active"], [0.0, 1.0, 1.0])
    np.testing.assert_array_equal(h[1]["active"], [0.0, 1.0, 1.0])


def test_async_defers_launch_to_next_available(tmp_path):
    path = os.path.join(tmp_path, "avail.json")
    # client 0 misses window 0; everyone is up afterwards
    with open(path, "w") as f:
        json.dump({"step": 100.0,
                   "available": [[0, 1, 1], [1, 1, 1]]}, f)
    # bw_mean makes one step ~30 simulated seconds, commensurate with
    # the 100 s availability window (the default ~ms steps would tick
    # thousands of times before client 0's deferred launch resolves)
    sys_ = SplitFTSystem(
        small_arch(),
        SystemConfig(trace=path, straggler_sim=True, adaptive=False,
                     scheduler="async", buffer_size=3, bw_mean=1e3,
                     **SYS), seed=0)
    h = sys_.run(2, log_every=0)
    assert all(np.isfinite(r["loss"]) for r in h)
    # client 0 could not launch before t=100, so the first flush (which
    # needs all 3 distinct clients) lands after its deferred completion
    assert h[0]["sim_clock"] > 100.0


# ---------------------------------------------------------------------------
# checkpoint-resume mid-trace == straight run, bitwise


@pytest.mark.parametrize("sched_kw", [dict(scheduler="sync"),
                                      dict(scheduler="async",
                                           buffer_size=2)],
                         ids=["sync", "async"])
def test_trace_checkpoint_resume_bitwise(sched_kw):
    arch = small_arch()
    kw = dict(trace_gen=CHURN, straggler_sim=True, adaptive=False,
              **sched_kw, **SYS)
    straight = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
    hs = straight.run(4, log_every=0)
    with tempfile.TemporaryDirectory() as td:
        ckw = dict(checkpoint_dir=td, checkpoint_every=2, **kw)
        first = SplitFTSystem(arch, SystemConfig(**ckw), seed=0)
        first.run(2, log_every=0)
        resumed = SplitFTSystem(arch, SystemConfig(**ckw), seed=0)
        assert resumed.restore()
        hr = resumed.run(2, log_every=0)
        for a, b in zip(hs[2:], hr):
            assert a["loss"] == b["loss"]
            assert a["sim_clock"] == b["sim_clock"]
            np.testing.assert_array_equal(a["active"], b["active"])
        assert adapter_digest(straight.state) \
            == adapter_digest(resumed.state)


def test_trace_cursor_roundtrips_through_state_dict():
    g = traces.make_trace_gen("markov:p_down=0.2,p_up=0.4,step=10",
                              seed=5)
    g.sample(500.0, [1, 2, 3])
    sd = g.state_dict()
    assert sd["markov"]                        # cursor actually advanced
    h = traces.make_trace_gen("markov:p_down=0.2,p_up=0.4,step=10",
                              seed=5)
    h.load_state_dict(json.loads(json.dumps(sd)))   # survives JSON
    for t in (500.0, 730.0, 40.0):
        np.testing.assert_array_equal(g.sample(t, [1, 2, 3])[2],
                                      h.sample(t, [1, 2, 3])[2])


# ---------------------------------------------------------------------------
# trace values are data: churning windows never retrace the engine


def test_trace_churn_never_retraces_engine():
    sys_ = SplitFTSystem(
        small_arch(),
        SystemConfig(trace_gen=CHURN, straggler_sim=True, adaptive=False,
                     scheduler="sync", **SYS), seed=0, jit=False)
    raw = sys_.train_step
    calls = {"n": 0}

    def counting(params, state, batch, w, a, lc, ls):
        calls["n"] += 1
        return raw(params, state, batch, w, a, lc, ls)

    sys_.train_step = jax.jit(counting)
    sys_.run(4, log_every=0)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# SpeedModel unit seams


def test_speed_model_trace_multiplies_base_draws():
    m = SpeedModel(3, seed=0, jitter_sigma=0.0)
    base = m.phase_times(cuts=[2, 2, 2], flops_per_layer=1e9,
                         smashed_bytes=1e6, adapter_bytes=[1e5] * 3)
    m.trace = traces.ConstantTrace(speed=2.0, bw=0.5)
    fast = m.phase_times(cuts=[2, 2, 2], flops_per_layer=1e9,
                         smashed_bytes=1e6, adapter_bytes=[1e5] * 3)
    np.testing.assert_allclose(fast[0], base[0] / 2.0)   # compute halves
    np.testing.assert_allclose(fast[1], base[1] * 2.0)   # wire doubles
    assert m.available_mask(0.0).all()
    assert m.next_available(1, 7.5) == 7.5
