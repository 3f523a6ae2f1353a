"""Co-controller tests: dead-band no-thrash, monotone response to speed,
heterogeneous-rank aggregation parity, predicted-vs-simulated makespan
consistency, and zero-recompile rank/compressor moves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduced
from repro.configs import get_config
from repro.core import adaptive, aggregation, lora as lora_lib, rounds
from repro.core.system import SplitFTSystem, SystemConfig
from repro.models.model import build_model


def small_model(layers=4):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=32,
                   vocab=128, seq_len=16, batch=2)
    return build_model(arch)


def small_arch(layers=6, lr=3e-3):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=64, batch=4)
    return arch.replace(train=dataclasses.replace(
        arch.train, lr_client=lr, lr_server=lr))


RANK_BUCKETS = (1, 2, 4)
N_COMP = 3


def linear_price(speeds, *, comp_cost=(3.0, 2.0, 1.0)):
    """Synthetic per-client price: compute scales with cut / speed, wire
    with rank and compressor aggressiveness — monotone in each knob."""

    def price(cuts, rank_cut, comp_idx):
        cuts = np.asarray(cuts, float)
        rank = np.asarray(rank_cut, float)
        cc = np.asarray([comp_cost[int(k)] for k in comp_idx], float)
        return cuts / np.asarray(speeds, float) + 0.1 * rank + 0.1 * cc

    return price


def co_args(n=3):
    split = small_arch(6).split
    return dict(split=split, num_layers=6, rank_buckets=RANK_BUCKETS,
                num_compressors=N_COMP)


# ---------------------------------------------------------------------------
# controller unit tests


def test_co_adjust_dead_band_no_thrash():
    """Inside the accuracy dead-band with no price change, the triple
    must not move — min_gain hysteresis holds it in place."""
    cuts = np.array([3, 3, 3])
    rank = np.array([2, 2, 2])
    comp = np.array([1, 1, 1])
    accs = np.array([0.5, 0.5, 0.5])       # everyone exactly at avg
    # slow compute -> the best possible (rank, comp) move saves ~3% of
    # the round, below the 5% min_gain threshold
    price = linear_price([0.5, 0.5, 0.5])
    for _ in range(5):
        cuts, rank, comp, _ = adaptive.co_adjust(
            cuts, rank, comp, accs, price=price, **co_args())
    assert cuts.tolist() == [3, 3, 3]
    assert rank.tolist() == [2, 2, 2]
    assert comp.tolist() == [1, 1, 1]


def test_co_adjust_moves_when_gain_is_large():
    """Inside the band, a (rank, compressor) move that cuts the predicted
    time well past min_gain is taken; the cut stays put."""
    cuts = np.array([3, 3, 3])
    rank = np.array([4, 4, 4])
    comp = np.array([0, 0, 0])
    accs = np.array([0.5, 0.5, 0.5])
    # wire dominates: dropping rank/comp saves >> min_gain
    price = linear_price([100.0, 100.0, 100.0],
                         comp_cost=(30.0, 2.0, 1.0))
    new_cuts, new_rank, new_comp, pred = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price, **co_args())
    assert new_cuts.tolist() == [3, 3, 3]          # in-band: cut frozen
    assert (new_rank < 4).all()
    assert (new_comp > 0).all()
    stay = price(cuts, rank, comp)
    assert (pred <= stay).all()


def test_co_adjust_quality_recovery_below_band():
    """A below-band client takes the forced quality move — cut down one
    bucket, rank up one bucket, compression one step weaker — even
    though it costs predicted time."""
    cuts = np.array([3, 3, 3])
    rank = np.array([2, 2, 2])
    comp = np.array([2, 2, 2])
    accs = np.array([0.1, 0.9, 0.9])
    price = linear_price([1.0, 1.0, 1.0])
    new_cuts, new_rank, new_comp, _ = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price, **co_args())
    assert new_cuts[0] < 3
    assert new_rank[0] == 4
    assert new_comp[0] == 1


def test_co_adjust_monotone_in_speed():
    """Slower client => never a smaller chosen predicted makespan (the
    argmin over pointwise-monotone candidates is monotone), and the
    chosen time never exceeds the stay-put time."""
    cuts = np.array([3, 3, 3])
    rank = np.array([4, 4, 4])
    comp = np.array([0, 0, 0])
    accs = np.array([0.5, 0.5, 0.5])
    prev = None
    for speed0 in (4.0, 2.0, 1.0, 0.5, 0.25):
        price = linear_price([speed0, 1.0, 1.0])
        _, _, _, pred = adaptive.co_adjust(
            cuts, rank, comp, accs, price=price, **co_args())
        stay = price(cuts, rank, comp)
        assert (pred <= stay + 1e-12).all()
        if prev is not None:
            assert pred[0] >= prev - 1e-12
        prev = pred[0]


def test_co_adjust_inactive_clients_frozen():
    cuts = np.array([3, 3, 3])
    rank = np.array([4, 4, 4])
    comp = np.array([0, 0, 0])
    accs = np.array([0.1, 0.5, 0.5])   # active clients sit at their avg
    price = linear_price([100.0, 100.0, 100.0],
                         comp_cost=(30.0, 2.0, 1.0))
    new_cuts, new_rank, new_comp, _ = adaptive.co_adjust(
        cuts, rank, comp, accs, price=price,
        active=np.array([0.0, 1.0, 1.0]), **co_args())
    assert (new_cuts[0], new_rank[0], new_comp[0]) == (3, 4, 0)
    assert new_comp[1] > 0 and new_comp[2] > 0


def test_adjust_cuts_straggler_median_over_active_only():
    """Regression for the all-clients median bug: a departed client's
    huge stale round time must not inflate the 1.5x-median threshold
    and hide a genuinely slow ACTIVE client."""
    split = small_arch(6).split
    cuts = np.array([3, 3, 3, 3])
    accs = np.array([0.9, 0.9, 0.9, 0.1])   # client 3 below average
    times = np.array([1.0, 1.0, 100.0, 1.6])  # client 2 left (stale time)
    active = np.array([1.0, 1.0, 0.0, 1.0])
    buckets = np.asarray(split.buckets(6))
    pos = int(np.argmin(np.abs(buckets - 3)))
    with_active = adaptive.adjust_cuts(cuts, accs, split, 6,
                                       round_times=times, active=active)
    # active median = 1.0 -> threshold 1.5 -> client 3 slow -> 2 buckets
    assert with_active[3] == buckets[max(pos - 2, 0)]
    without = adaptive.adjust_cuts(cuts, accs, split, 6,
                                   round_times=times)
    # all-clients median 1.3 -> threshold 1.95 -> only the 1-bucket drop
    assert without[3] == buckets[max(pos - 1, 0)]


# ---------------------------------------------------------------------------
# heterogeneous-rank aggregation


def test_fedavg_uniform_rank_matches_plain_bitwise():
    """Masked rank-r aggregation == plain aggregation bitwise when every
    client runs the same rank r on pre-masked adapters (the masked-slot
    generalization degenerates to the paper's rule)."""
    model = small_model()
    n, m = 3, model.num_flat_layers
    cuts = jnp.asarray([2, 2, 2])
    cad = lora_lib.init_adapters(model, jax.random.PRNGKey(0),
                                 num_clients=n)
    ranks = lora_lib.effective_ranks(m, cuts, model.arch.lora,
                                     r_cut=jnp.asarray([2, 2, 2]))
    masked = lora_lib.mask_adapters(model, cad, ranks)
    w = jnp.asarray([0.5, 0.3, 0.2])
    act = jnp.ones(n)
    plain = aggregation.fedavg(model, masked, cuts, w, act)
    hetero = aggregation.fedavg(model, masked, cuts, w, act, ranks=ranks)
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_leaves_with_path(plain),
            jax.tree_util.tree_leaves_with_path(hetero)):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_fedavg_hetero_rank_columns_average_owners_only():
    """Each rank column averages only the clients whose effective rank
    covers it; unowned columns coast on the plain layer average instead
    of zeroing (B=0 init would otherwise kill them permanently)."""
    model = small_model()
    n, m = 3, model.num_flat_layers
    r_max = model.arch.lora.r_others
    cuts = jnp.asarray([2, 2, 2])
    cad = lora_lib.init_adapters(model, jax.random.PRNGKey(1),
                                 num_clients=n)
    rank_cut = jnp.asarray([1, 2, 2])
    ranks = lora_lib.effective_ranks(m, cuts, model.arch.lora,
                                     r_cut=rank_cut)
    w = jnp.asarray([0.5, 0.3, 0.2])
    act = jnp.ones(n)
    plain = aggregation.fedavg(model, cad, cuts, w, act)
    hetero = aggregation.fedavg(model, cad, cuts, w, act, ranks=ranks)
    a = np.asarray(cad["dec"]["q"]["A"])        # (Lg, N, d, r)
    hp = np.asarray(hetero["dec"]["q"]["A"])
    wn = np.asarray(w)
    lcut = 1                                    # cut layer = cuts-1
    # column 0: all three clients cover it -> full weighted average
    np.testing.assert_allclose(
        hp[lcut, :, 0],
        np.einsum("n,nd->d", wn, a[lcut, :, :, 0]) / wn.sum(),
        rtol=1e-6)
    # column 1: only clients 1, 2 (rank 2) own it
    np.testing.assert_allclose(
        hp[lcut, :, 1],
        np.einsum("n,nd->d", wn[1:], a[lcut, 1:, :, 1]) / wn[1:].sum(),
        rtol=1e-6)
    # columns >= 2: unowned at the cut layer -> plain fallback, not zero
    pp = np.asarray(plain["dec"]["q"]["A"])
    np.testing.assert_array_equal(hp[lcut, :, 2:], pp[lcut, :, 2:])
    assert np.any(hp[lcut, :, 2:] != 0)
    # non-cut layers run at r_others everywhere -> identical to plain
    np.testing.assert_allclose(hp[0], pp[0], rtol=1e-6)
    assert r_max > 2        # the fallback columns actually exist


# ---------------------------------------------------------------------------
# engine: zero recompiles when the controller moves rank / compressor


def test_rank_and_compressor_moves_do_not_retrace():
    """The acceptance-criteria pin: changing per-client rank_cut,
    smashed_choice and cuts between rounds reuses ONE traced executable
    (policy is data, masks not recompiles)."""
    model = small_model()
    n = 3
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    state = rounds.init_state(model, key, num_clients=n)
    state = rounds.prepare_state(state, rank_cut=2, smashed_choice=0)
    traces = {"n": 0}
    raw = rounds.make_train_step(model,
                                 compressor_buckets=("none", "int8",
                                                     "topk"),
                                 jit=False)

    def counting(params, state, batch, w, a, lc, ls):
        traces["n"] += 1
        return raw(params, state, batch, w, a, lc, ls)

    step = jax.jit(counting)
    v = model.arch.model.vocab_size
    bk = jax.random.PRNGKey(7)
    batch = {"tokens": jax.random.randint(bk, (n, 2, 16), 3, v),
             "labels": jax.random.randint(bk, (n, 2, 16), 3, v),
             "loss_mask": jnp.ones((n, 2, 16), jnp.float32)}
    w = jnp.ones(n) / n
    act = jnp.ones(n)
    lr = jnp.float32(3e-3)
    assignments = [
        (jnp.asarray([2, 2, 2]), jnp.asarray([2, 2, 2]),
         jnp.asarray([0, 0, 0])),
        (jnp.asarray([1, 2, 3]), jnp.asarray([1, 4, 2]),
         jnp.asarray([1, 0, 2])),
        (jnp.asarray([3, 1, 2]), jnp.asarray([4, 4, 1]),
         jnp.asarray([2, 2, 1])),
    ]
    for cuts, rank, choice in assignments:
        state = dict(state, cuts=cuts.astype(jnp.int32),
                     rank_cut=rank.astype(jnp.int32),
                     smashed_choice=choice.astype(jnp.int32))
        state, metrics = step(params, state, batch, w, act, lr, lr)
        assert np.isfinite(float(metrics["total"]))
    assert traces["n"] == 1, \
        f"rank/compressor moves retraced the step {traces['n']}x"


# ---------------------------------------------------------------------------
# predicted vs simulated makespan (system level)


SYS = dict(num_samples=150, eval_samples=32)


def test_predicted_matches_simulated_makespan_zero_jitter():
    """With jitter_sigma=0 the co-controller's predicted per-client time
    for the assignment it just chose must equal the NEXT round's
    simulated serial step times exactly — prediction and simulation
    share comm.round_comm_bytes and SpeedModel.phase_times."""
    cfg = SystemConfig(controller="co", rank_buckets=(1, 2, 4),
                       compressor_buckets=("none", "int8", "topk"),
                       straggler_sim=True, jitter_sigma=0.0, **SYS)
    s = SplitFTSystem(small_arch(6), cfg, seed=0)
    hist = s.run(5, log_every=0)
    for prev, nxt in zip(hist[:-1], hist[1:]):
        assert "predicted_time" in prev
        np.testing.assert_array_equal(prev["predicted_time"],
                                      nxt["round_time_sim"])


def test_co_controller_trains_and_stays_in_buckets():
    cfg = SystemConfig(controller="co", rank_buckets=(1, 2, 4),
                       compressor_buckets=("none", "int8"),
                       straggler_sim=True, **SYS)
    arch = small_arch(6)
    s = SplitFTSystem(arch, cfg, seed=0)
    hist = s.run(6, log_every=0)
    buckets = set(arch.split.buckets(6))
    for h in hist:
        assert set(h["cuts"].tolist()) <= buckets
        assert set(h["rank_cut"].tolist()) <= {1, 2, 4}
        assert set(h["smashed_choice"].tolist()) <= {0, 1}
    assert np.isfinite(hist[-1]["loss"])


def test_co_controller_beats_accuracy_on_simulated_clock():
    """Same straggler fleet, same rounds, deterministic clock: the
    co-controller's (cut, rank, compressor) choices take less simulated
    time than the accuracy-only rule's, and reach the shared target loss
    (the weaker lane's final loss) no later.  It moves the rank and
    compressor, not just the cut."""
    arch = reduced(get_config("gpt2-small"), layers=4, d_model=32,
                   vocab=256, seq_len=16, batch=2)
    arch = arch.replace(
        train=dataclasses.replace(arch.train, lr_client=3e-3,
                                  lr_server=3e-3),
        data=dataclasses.replace(arch.data, num_clients=3,
                                 partition="iid"),
        split=dataclasses.replace(arch.split, cut_layer=1))
    kw = dict(num_samples=48, eval_samples=16, straggler_sim=True,
              jitter_sigma=0.0)
    hist = {
        c: SplitFTSystem(arch, SystemConfig(controller=c, **kw, **extra),
                         seed=0).run(4, log_every=0)
        for c, extra in (
            ("accuracy", {}),
            ("co", dict(rank_buckets=(1, 2, 4),
                        compressor_buckets=("none", "int8", "topk"))))}

    def time_to(hs, target):
        t = 0.0
        for h in hs:
            t += h["sim_time"]
            if h["loss"] <= target:
                break
        return t

    target = max(hs[-1]["loss"] for hs in hist.values())
    total = {c: sum(h["sim_time"] for h in hs) for c, hs in hist.items()}
    assert total["co"] < total["accuracy"]
    assert time_to(hist["co"], target) <= time_to(hist["accuracy"], target)
    last = hist["co"][-1]          # starts at (r_cut, "none" = choice 0)
    assert np.any(last["rank_cut"] != arch.lora.r_cut)
    assert np.any(last["smashed_choice"] != 0)


def test_co_controller_checkpoint_roundtrip(tmp_path):
    cfg = SystemConfig(controller="co", rank_buckets=(1, 2, 4),
                       compressor_buckets=("none", "int8"),
                       straggler_sim=True, checkpoint_dir=str(tmp_path),
                       checkpoint_every=2, **SYS)
    arch = small_arch(6)
    s1 = SplitFTSystem(arch, cfg, seed=0)
    s1.run(4, log_every=0)
    s2 = SplitFTSystem(arch, cfg, seed=0)
    assert s2.restore()
    np.testing.assert_array_equal(np.asarray(s2.state["rank_cut"]),
                                  np.asarray(s1.state["rank_cut"]))
    np.testing.assert_array_equal(np.asarray(s2.state["smashed_choice"]),
                                  np.asarray(s1.state["smashed_choice"]))
    s2.run(1, log_every=0)


def test_co_controller_rejects_smashed_ef():
    cfg = SystemConfig(controller="co", smashed_ef=True, **SYS)
    arch = small_arch()
    arch = arch.replace(split=dataclasses.replace(arch.split,
                                                  smashed_compress="topk"))
    with pytest.raises(ValueError, match="error feedback"):
        SplitFTSystem(arch, cfg, seed=0)


def test_co_controller_async_scheduler_composes():
    """The async event loop re-prices after C3 moves (cache keys include
    the rank/compressor policy) and keeps training."""
    cfg = SystemConfig(controller="co", rank_buckets=(1, 2, 4),
                       compressor_buckets=("none", "int8"),
                       scheduler="async", buffer_size=2,
                       straggler_sim=True, **SYS)
    s = SplitFTSystem(small_arch(6), cfg, seed=0)
    hist = s.run(4, log_every=0)
    assert len(hist) == 4
    assert np.isfinite(hist[-1]["loss"])
