"""Time-model layer pins (ISSUE 10).

  * the pricing refactor never moves the simulated clock: the default
    time source == explicit `analytic`, bitwise, under EVERY scheduler
    (losses, clocks, adapter digests), and turning telemetry ON
    (`measured`) is observation-passive — the charged clock is
    bit-identical with feedback enabled;
  * a well-specified `measured` pricer at jitter 0 prices bitwise like
    `analytic` (observed/base ratios are exactly 1.0), while a
    MIS-specified model (model_seed) is corrected to the true clock by
    ONE observation — the transfer property the controller relies on;
  * measured EWMA state is keyed by population id (survives cohort
    churn) and round-trips through checkpoint metadata;
  * `--record-trace` closes the loop: a recorded run replays through
    `--trace` onto the same simulated clock;
  * config-time loud guards: telemetry sources without timing hooks,
    trace pricing without a trace, continuous_topk without the co
    controller's topk bucket;
  * the continuous topk-fraction knob: a uniform traced fraction ==
    the static compressor bitwise, and co_adjust's fraction policy
    obeys the accuracy dead-band (double below, hold inside, halve
    above only past min_gain).
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
from repro.core import adaptive
from repro.core.system import SplitFTSystem, SystemConfig
from repro.runtime import timemodel
from repro.runtime.straggler import SpeedModel, population_speed_draws


def small_arch(layers=4, lr=3e-3):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=32, batch=2)
    return arch.replace(train=dataclasses.replace(
        arch.train, lr_client=lr, lr_server=lr))


SYS = dict(num_samples=80, eval_samples=16)

SCHED_CONFIGS = {
    "sync": dict(scheduler="sync"),
    "deadline": dict(scheduler="deadline", deadline_frac=1.2),
    "local_steps": dict(scheduler="local_steps", max_local_steps=3),
    "async": dict(scheduler="async", buffer_size=2),
    "async_overlap": dict(scheduler="async", buffer_size=2,
                          overlap_comm=True),
}

CO = dict(controller="co", rank_buckets=(2, 4),
          compressor_buckets=("none", "topk"))


def adapter_digest(state):
    return tuple(np.asarray(leaf).tobytes()
                 for key in ("client_adapters", "server_adapters")
                 for leaf in jax.tree.leaves(state[key]))


def assert_same_run(ha, hb):
    for a, b in zip(ha, hb):
        assert a["loss"] == b["loss"]
        assert a["sim_clock"] == b["sim_clock"]
        assert a["sim_time"] == b["sim_time"]
        np.testing.assert_array_equal(a["active"], b["active"])
        np.testing.assert_array_equal(a["round_time_sim"],
                                      b["round_time_sim"])


# ---------------------------------------------------------------------------
# the refactor pin: explicit analytic == the default source, bitwise,
# under every scheduler — and telemetry observation is passive


@pytest.mark.parametrize("sched", sorted(SCHED_CONFIGS))
def test_analytic_source_is_default_bitwise(sched):
    kw = dict(straggler_sim=True, adaptive=False,
              **SCHED_CONFIGS[sched], **SYS)
    base = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hb = base.run(4, log_every=0)
    assert base.time_source == "analytic"      # no trace -> analytic
    expl = SplitFTSystem(small_arch(),
                         SystemConfig(time_source="analytic", **kw),
                         seed=0)
    he = expl.run(4, log_every=0)
    assert_same_run(hb, he)
    assert adapter_digest(base.state) == adapter_digest(expl.state)


@pytest.mark.parametrize("sched", ["sync", "async"])
def test_measured_observation_is_passive_bitwise(sched):
    """time_source='measured' turns the telemetry feedback loop ON, but
    with the controller idle (adaptive=False) the charged clock must be
    bit-identical — observing never perturbs what it observes."""
    kw = dict(straggler_sim=True, adaptive=False,
              **SCHED_CONFIGS[sched], **SYS)
    base = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hb = base.run(4, log_every=0)
    meas = SplitFTSystem(small_arch(),
                         SystemConfig(time_source="measured", **kw),
                         seed=0)
    hm = meas.run(4, log_every=0)
    assert_same_run(hb, hm)
    assert adapter_digest(base.state) == adapter_digest(meas.state)
    assert meas.pricer.state_dict()["ratio"]   # it DID observe


def test_trace_source_explicit_matches_default():
    kw = dict(straggler_sim=True, adaptive=False, scheduler="sync",
              trace_gen="diurnal:amp=0.8,period=200,sigma=0.3,step=50",
              **SYS)
    base = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hb = base.run(3, log_every=0)
    assert base.time_source == "trace"         # trace installed -> trace
    expl = SplitFTSystem(small_arch(),
                         SystemConfig(time_source="trace", **kw), seed=0)
    he = expl.run(3, log_every=0)
    assert_same_run(hb, he)
    assert adapter_digest(base.state) == adapter_digest(expl.state)


def test_measured_well_specified_matches_analytic_bitwise():
    """With the model == the clock and jitter_sigma=0, every observed
    ratio is exactly 1.0 (IEEE x/x), so the measured co-controller run
    is bit-identical to the analytic one — the feedback loop costs
    nothing when the spec sheet is right."""
    kw = dict(straggler_sim=True, adaptive=True, jitter_sigma=0.0,
              scheduler="sync", **CO, **SYS)
    a = SplitFTSystem(small_arch(),
                      SystemConfig(time_source="analytic", **kw), seed=0)
    ha = a.run(4, log_every=0)
    m = SplitFTSystem(small_arch(),
                      SystemConfig(time_source="measured", **kw), seed=0)
    hm = m.run(4, log_every=0)
    assert_same_run(ha, hm)
    assert adapter_digest(a.state) == adapter_digest(m.state)
    for row in m.pricer.state_dict()["ratio"].values():
        assert row == [1.0] * 5


# ---------------------------------------------------------------------------
# the measured source corrects a mis-specified model


def _misspec_kw(**extra):
    return dict(straggler_sim=True, adaptive=False, scheduler="sync",
                jitter_sigma=0.0, model_seed=7, **extra, **SYS)


def test_measured_warm_start_prices_like_analytic():
    a = SplitFTSystem(small_arch(),
                      SystemConfig(time_source="analytic",
                                   **_misspec_kw()), seed=0)
    m = SplitFTSystem(small_arch(),
                      SystemConfig(time_source="measured",
                                   **_misspec_kw()), seed=0)
    cuts = np.asarray(a.state["cuts"])
    np.testing.assert_array_equal(m.predict_round_times(0, cuts),
                                  a.predict_round_times(0, cuts))
    # ...and the mis-specified belief really differs from the clock
    truth = SplitFTSystem(small_arch(),
                          SystemConfig(time_source="analytic",
                                       straggler_sim=True, adaptive=False,
                                       scheduler="sync", jitter_sigma=0.0,
                                       **SYS), seed=0)
    assert not np.array_equal(a.predict_round_times(0, cuts),
                              truth.predict_round_times(0, cuts))


def test_measured_one_observation_corrects_misspecified_model():
    """Phase times are linear in the per-client speed/bandwidth factors,
    so at jitter 0 a single observed round makes the measured
    predictions coincide with the TRUE clock even though the pricing
    model was drawn from a different seed — while analytic stays
    wrong forever."""
    m = SplitFTSystem(small_arch(),
                      SystemConfig(time_source="measured",
                                   **_misspec_kw()), seed=0)
    m.run(1, log_every=0)
    truth = SplitFTSystem(small_arch(),
                          SystemConfig(time_source="analytic",
                                       straggler_sim=True, adaptive=False,
                                       scheduler="sync", jitter_sigma=0.0,
                                       **SYS), seed=0)
    cuts = np.asarray(m.state["cuts"])
    np.testing.assert_allclose(m.predict_round_times(1, cuts),
                               truth.predict_round_times(1, cuts),
                               rtol=1e-12)
    # transfer: the correction learned at the CURRENT assignment prices
    # a *different* candidate assignment on the true clock too
    other = np.roll(cuts, 1)
    np.testing.assert_allclose(m.predict_round_times(1, other),
                               truth.predict_round_times(1, other),
                               rtol=1e-12)


def test_measured_checkpoint_resume_bitwise():
    """The EWMA state rides checkpoint metadata: resuming a measured
    co-controller run mid-stream continues the straight run bitwise."""
    arch = small_arch()
    kw = dict(time_source="measured", adaptive=True, **CO,
              straggler_sim=True, scheduler="sync", jitter_sigma=0.0,
              model_seed=7, **SYS)
    straight = SplitFTSystem(arch, SystemConfig(**kw), seed=0)
    hs = straight.run(4, log_every=0)
    with tempfile.TemporaryDirectory() as td:
        ckw = dict(checkpoint_dir=td, checkpoint_every=2, **kw)
        first = SplitFTSystem(arch, SystemConfig(**ckw), seed=0)
        first.run(2, log_every=0)
        resumed = SplitFTSystem(arch, SystemConfig(**ckw), seed=0)
        assert resumed.restore()
        assert resumed.pricer.state_dict() == first.pricer.state_dict()
        hr = resumed.run(2, log_every=0)
        assert_same_run(hs[2:], hr)
        assert adapter_digest(straight.state) \
            == adapter_digest(resumed.state)
        assert resumed.pricer.state_dict() \
            == straight.pricer.state_dict()


def test_measured_state_keyed_by_pid_across_cohort_churn():
    """Population mode: the EWMA ratios are keyed by population id, not
    cohort slot — each pid's learned ratio equals its own model/clock
    draw ratio no matter which slot (or round) it was observed in."""
    arch = small_arch()
    arch = arch.replace(data=dataclasses.replace(arch.data, population=12))
    sys_ = SplitFTSystem(arch, SystemConfig(
        straggler_sim=True, adaptive=False,
        scheduler="sync", time_source="measured", jitter_sigma=0.0,
        model_seed=7, **SYS), seed=0)
    sys_.run(4, log_every=0)
    ratio = sys_.pricer._ratio
    cohort = arch.data.num_clients
    assert len(ratio) > cohort                 # churn: > one cohort seen
    assert set(ratio) <= set(range(12))
    draw_kw = dict(speed_sigma=sys_.speed.speed_sigma,
                   bw_mean=sys_.speed.bw_mean,
                   bw_sigma=sys_.speed.bw_sigma)
    sp_c, bw_c, _ = population_speed_draws(np.arange(12), seed=0,
                                           **draw_kw)
    sp_m, bw_m, _ = population_speed_draws(np.arange(12), seed=7,
                                           **draw_kw)
    for pid, r in ratio.items():
        # duration = work / factor: compute row learns the speed ratio,
        # uplink row the bandwidth ratio, each keyed by the pid's draws
        np.testing.assert_allclose(r[0], sp_m[pid] / sp_c[pid],
                                   rtol=1e-12)
        np.testing.assert_allclose(r[1], bw_m[pid] / bw_c[pid],
                                   rtol=1e-12)
    # ...and the state survives a JSON round-trip losslessly
    sd = sys_.pricer.state_dict()
    clone = timemodel.MeasuredPricer(sys_.speed)
    clone.load_state_dict(json.loads(json.dumps(sd)))
    assert clone.state_dict() == sd


# ---------------------------------------------------------------------------
# record -> replay round-trip


def test_record_trace_replays_onto_same_clock(tmp_path):
    """--record-trace under a synthetic trace at jitter 0: replaying the
    dumped FileTrace reproduces the recorded run's simulated clock (the
    recorded factors are the generator's, recovered exactly)."""
    path = os.path.join(tmp_path, "rec.json")
    kw = dict(straggler_sim=True, adaptive=False, scheduler="sync",
              jitter_sigma=0.0, bw_mean=1e3, **SYS)
    rec = SplitFTSystem(small_arch(), SystemConfig(
        trace_gen="diurnal:amp=0.8,period=120,sigma=0.3,step=30",
        record_trace=path, **kw), seed=0)
    hr = rec.run(4, log_every=0)
    with open(path) as f:
        d = json.load(f)
    assert d["step"] == 30.0                   # the clock trace's window
    assert len(d["speed"]) >= 2                # the run crossed windows
    replay = SplitFTSystem(small_arch(), SystemConfig(trace=path, **kw),
                           seed=0)
    hp = replay.run(4, log_every=0)
    for a, b in zip(hr, hp):
        assert a["loss"] == b["loss"]
        np.testing.assert_allclose(b["sim_clock"], a["sim_clock"],
                                   rtol=1e-9)
        np.testing.assert_allclose(b["round_time_sim"],
                                   a["round_time_sim"], rtol=1e-9)


def test_recorder_empty_dump_is_loud():
    with pytest.raises(ValueError, match="nothing recorded"):
        timemodel.TraceRecorder(SpeedModel(2, seed=0)).to_trace_dict()


# ---------------------------------------------------------------------------
# config-time loud guards


def test_telemetry_without_timing_hooks_is_loud():
    arch = small_arch()
    with pytest.raises(ValueError, match="timing hooks"):
        SplitFTSystem(arch, SystemConfig(time_source="measured", **SYS))
    with pytest.raises(ValueError, match="record_trace"):
        SplitFTSystem(arch, SystemConfig(record_trace="x.json", **SYS))
    with pytest.raises(ValueError, match="model_seed"):
        SplitFTSystem(arch, SystemConfig(model_seed=3, **SYS))
    with pytest.raises(ValueError, match="no trace is installed"):
        SplitFTSystem(arch, SystemConfig(time_source="trace",
                                         straggler_sim=True, **SYS))
    with pytest.raises(ValueError, match="unknown time_source"):
        SplitFTSystem(arch, SystemConfig(time_source="psychic", **SYS))
    with pytest.raises(ValueError, match="ewma_alpha"):
        timemodel.MeasuredPricer(SpeedModel(3, seed=0), ewma_alpha=0.0)


def test_continuous_topk_guards():
    arch = small_arch()
    with pytest.raises(ValueError, match="co-controller"):
        SplitFTSystem(arch, SystemConfig(continuous_topk=True, **SYS))
    with pytest.raises(ValueError, match="topk"):
        SplitFTSystem(arch, SystemConfig(
            continuous_topk=True, controller="co", rank_buckets=(2, 4),
            compressor_buckets=("none", "int8"), **SYS))


# ---------------------------------------------------------------------------
# continuous topk fraction: engine parity + controller policy


def test_continuous_topk_uniform_equals_static_bitwise():
    """A traced per-client fraction equal everywhere to the static
    config fraction must reproduce the static compressor bitwise
    (floor(d * frac) == int(d * frac) and the k-th-largest-value
    threshold selects the same channels)."""
    kw = dict(straggler_sim=True, adaptive=False, scheduler="sync",
              **CO, **SYS)
    stat = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    hs = stat.run(3, log_every=0)
    cont = SplitFTSystem(small_arch(),
                         SystemConfig(continuous_topk=True, **kw),
                         seed=0)
    hc = cont.run(3, log_every=0)
    assert "topk_frac" not in stat.state
    np.testing.assert_array_equal(
        np.asarray(cont.state["topk_frac"]),
        np.full(small_arch().data.num_clients,
                np.float32(stat.smashed_topk_frac)))
    assert_same_run(hs, hc)
    assert adapter_digest(stat.state) == adapter_digest(cont.state)


def test_continuous_topk_adaptive_respects_bounds():
    kw = dict(straggler_sim=True, adaptive=True, scheduler="sync",
              continuous_topk=True, jitter_sigma=0.0, **CO, **SYS)
    sys_ = SplitFTSystem(small_arch(), SystemConfig(**kw), seed=0)
    h = sys_.run(4, log_every=0)
    f = np.asarray(sys_.state["topk_frac"], np.float64)
    assert np.all((f >= 0.01) & (f <= 1.0))
    assert np.isfinite(h[-1]["loss"])
    assert "topk_frac" in h[-1]                # the knob is logged


def _frac_args(n):
    split = small_arch(4).split
    return dict(split=split, num_layers=4, rank_buckets=(2, 4),
                num_compressors=2)


def test_co_adjust_frac_obeys_dead_band():
    """Below the band the fraction is forcibly doubled (quality
    recovery), inside it holds bitwise, above it halves only when the
    predicted saving clears min_gain."""
    cuts = np.array([3, 3, 3])
    rank = np.array([2, 2, 2])
    comp = np.array([1, 1, 1])
    accs = np.array([0.4, 0.6, 0.8])       # below / inside / above
    frac = np.array([0.3, 0.3, 0.4])

    def price(c, rk, ci, fr):              # wire cost grows with frac
        return 1.0 + np.asarray(fr, np.float64)

    nc, nr, ncp, nf, pred = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price, topk_frac=frac,
        dead_band=0.05, **_frac_args(3))
    assert nf[0] == pytest.approx(0.6)     # doubled
    assert nf[1] == 0.3                    # held, bitwise
    assert nf[2] == pytest.approx(0.2)     # halved: 25% saving > 5%
    np.testing.assert_allclose(pred, 1.0 + nf)
    # the in-band / above-band triples never moved (flat price)
    assert nc[1:].tolist() == [3, 3]
    assert nr[1:].tolist() == [2, 2]
    assert ncp[1:].tolist() == [1, 1]


def test_co_adjust_frac_clip_and_hysteresis():
    cuts = np.array([3, 3])
    rank = np.array([2, 2])
    comp = np.array([1, 1])
    accs = np.array([0.4, 0.8])            # below / above the band

    def price(c, rk, ci, fr):
        return 1.0 + np.asarray(fr, np.float64)

    _, _, _, nf, _ = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price,
        topk_frac=np.array([0.9, 0.4]), dead_band=0.05, min_gain=0.9,
        **_frac_args(2))
    assert nf[0] == 1.0                    # doubling clips at the bound
    assert nf[1] == 0.4                    # 25% saving < 90% hysteresis


def test_co_adjust_frac_pinned_when_price_is_flat():
    """A client whose compressor ignores the fraction prices identically
    at any value, so the hysteresis holds its fraction in place."""
    cuts = np.array([3, 3, 3])
    rank = np.array([2, 2, 2])
    comp = np.array([0, 0, 0])
    accs = np.array([0.85, 0.85, 0.95])    # client 2 above the band

    def price(c, rk, ci, fr):
        return np.ones(len(c), np.float64)

    _, _, _, nf, _ = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price,
        topk_frac=np.array([0.4, 0.4, 0.4]), dead_band=0.05,
        **_frac_args(3))
    np.testing.assert_array_equal(nf, [0.4, 0.4, 0.4])


def test_co_adjust_without_frac_keeps_four_tuple():
    cuts = np.array([3, 3])
    rank = np.array([2, 2])
    comp = np.array([1, 1])
    accs = np.array([0.5, 0.5])
    out = adaptive.co_adjust(
        cuts, rank, comp, accs,
        price=lambda c, rk, ci: np.ones(len(c), np.float64),
        **_frac_args(2))
    assert len(out) == 4
