"""End-to-end behaviour tests: the full SplitFT system (Algorithm 1),
fault tolerance, stragglers, elasticity, checkpoint/resume."""

import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, \
    save_checkpoint
from repro.config import DataConfig, SplitConfig, reduced
from repro.configs import get_config
from repro.core import rounds
from repro.core.system import SplitFTSystem, SystemConfig
from repro.models.model import build_model
from repro.runtime.straggler import SpeedModel, deadline_survivors


def small_arch(layers=4, lr=3e-3):
    arch = reduced(get_config("gpt2-small"), layers=layers, d_model=64,
                   vocab=512, seq_len=64, batch=4)
    return arch.replace(train=dataclasses.replace(
        arch.train, lr_client=lr, lr_server=lr))


SYS = dict(num_samples=150, eval_samples=32)


# ---------------------------------------------------------------------------
# one home per setting: the arch decides, SystemConfig drives the run


@pytest.mark.parametrize("a,b", [(SystemConfig, SplitConfig),
                                 (SystemConfig, DataConfig),
                                 (SplitConfig, DataConfig)],
                         ids=["system-split", "system-data", "split-data"])
def test_no_setting_has_two_homes(a, b):
    names = {f.name for f in dataclasses.fields(a)}
    assert not names & {f.name for f in dataclasses.fields(b)}


# the chip benchmark's train.paper flags
BENCH_FLAGS = ["--smashed-compress", "int8", "--scheduler", "sync",
          "--controller", "accuracy"]


@pytest.mark.parametrize("flags,want", [
    ([], dict(smashed="none", sched="sync", adaptive=True)),
    (BENCH_FLAGS + ["--adaptive"],
     dict(smashed="int8", sched="sync", adaptive=True)),
    (BENCH_FLAGS + ["--no-adaptive"],
     dict(smashed="int8", sched="sync", adaptive=False)),
    (BENCH_FLAGS + ["--adaptive", "--population", "6"],
     dict(smashed="int8", sched="sync", adaptive=True, population=6)),
    (["--smashed-compress", "int8", "--scheduler", "async", "--adaptive",
      "--buffer-size", "5"],
     dict(smashed="int8", sched="async", adaptive=True, buffer=3)),
    (BENCH_FLAGS + ["--adaptive", "--edge-groups", "2"],
     dict(smashed="int8", sched="sync", adaptive=True, edges=2)),
    (["--straggler-sim"], dict(smashed="none", sched="deadline",
                               adaptive=True)),
], ids=["defaults", "bench", "bench-no-adaptive", "population",
        "async-buffer-clamped", "edge-groups", "straggler-legacy"])
def test_build_system_maps_each_flag_to_its_home(tmp_path, flags, want):
    from repro.launch import train as ltrain
    args = ltrain.build_parser().parse_args(
        ["--arch", "gpt2-small", "--reduced", "--samples", "40",
         "--out", str(tmp_path)] + flags)
    s = ltrain.build_system(args)
    assert s.smashed_compress == s.arch.split.smashed_compress \
        == want["smashed"]
    assert s.comp_buckets == (want["smashed"],)
    assert s.scheduler.name == want["sched"]
    assert s.sys.controller == "accuracy"
    assert s.sys.adaptive is want["adaptive"]
    assert s.population == s.arch.data.population \
        == want.get("population", 0)
    assert s.num_edges == want.get("edges", 1)
    if "buffer" in want:             # clamped to the 3 reduced clients
        assert s.scheduler.buffer_size == want["buffer"]
    assert s.rank_buckets == (s.arch.lora.r_cut,)


def test_rounds_run_and_learn():
    sys_ = SplitFTSystem(small_arch(), SystemConfig(**SYS), seed=0)
    hist = sys_.run(25, log_every=0)
    assert len(hist) == 25
    early = np.mean([h["loss"] for h in hist[:5]])
    late = np.mean([h["loss"] for h in hist[-5:]])
    assert late < early, f"no learning: {early:.4f} -> {late:.4f}"
    # metrics well-formed
    assert hist[-1]["accuracy"].shape == (3,)
    assert np.isfinite(hist[-1]["loss"])


def test_adaptive_cuts_move_and_stay_in_buckets():
    arch = small_arch(6)
    sys_ = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    hist = sys_.run(10, log_every=0)
    buckets = set(arch.split.buckets(6))
    for h in hist:
        assert set(h["cuts"].tolist()) <= buckets
    # adaptive must actually adjust at least once at this heterogeneity
    all_cuts = {tuple(h["cuts"].tolist()) for h in hist}
    assert len(all_cuts) > 1


def test_fixed_split_baseline_keeps_cuts():
    arch = small_arch()
    sys_ = SplitFTSystem(arch, SystemConfig(adaptive=False, **SYS), seed=0)
    hist = sys_.run(5, log_every=0)
    for h in hist:
        assert h["cuts"].tolist() == [arch.split.cut_layer] * 3


@pytest.mark.parametrize("compress", ["topk", "int8"])
def test_compression_paths_train(compress):
    cfg = SystemConfig(compress=compress, topk_frac=0.25, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=0)
    hist = sys_.run(4, log_every=0)
    assert np.isfinite(hist[-1]["loss"])


def test_straggler_deadline_drops_slow_client():
    cfg = SystemConfig(straggler_sim=True, deadline_frac=1.2, **SYS)
    sys_ = SplitFTSystem(small_arch(), cfg, seed=3)
    hist = sys_.run(6, log_every=0)
    # with deadline 1.2x median and lognormal speeds, someone gets dropped
    dropped = any(h["active"].sum() < 3 for h in hist)
    assert dropped
    assert np.isfinite(hist[-1]["loss"])


def test_elastic_leave_join():
    sys_ = SplitFTSystem(small_arch(), SystemConfig(**SYS), seed=0)
    sys_.run(2, log_every=0)
    sys_.pool.leave(1)
    h = sys_.run(2, log_every=0)
    assert h[-1]["active"].tolist() == [1.0, 0.0, 1.0]
    sys_.pool.join(1)
    h = sys_.run(1, log_every=0)
    assert h[-1]["active"].tolist() == [1.0, 1.0, 1.0]


def test_checkpoint_resume_exact():
    arch = small_arch()
    with tempfile.TemporaryDirectory() as d:
        cfg = SystemConfig(checkpoint_dir=d, checkpoint_every=3, **SYS)
        s1 = SplitFTSystem(arch, cfg, seed=0)
        s1.run(6, log_every=0)
        cuts = np.asarray(s1.state["cuts"]).tolist()
        w = s1.c3_weights.copy()

        s2 = SplitFTSystem(arch, cfg, seed=0)
        assert s2.restore()
        assert int(s2.state["round"]) == 6
        assert np.asarray(s2.state["cuts"]).tolist() == cuts
        np.testing.assert_allclose(s2.c3_weights, w)
        # adapters restored bit-exact
        a1 = np.asarray(s1.state["client_adapters"]["dec"]["q"]["A"])
        a2 = np.asarray(s2.state["client_adapters"]["dec"]["q"]["A"])
        np.testing.assert_array_equal(a1, a2)
        s2.run(2, log_every=0)   # continues fine


def test_checkpoint_corruption_fallback():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3)
        tree = {"x": jnp.arange(4.0)}
        mgr.save(1, tree)
        mgr.save(2, jax.tree.map(lambda t: t + 1, tree))
        # corrupt the newest checkpoint
        with open(os.path.join(d, "ckpt_00000002.npz"), "wb") as f:
            f.write(b"garbage")
        got = mgr.restore_latest(tree)
        assert got is not None
        restored, _, step = got
        assert step == 1
        np.testing.assert_array_equal(restored["x"], np.arange(4.0))


def test_checkpoint_atomic_keep_last_k():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in range(5):
            mgr.save(s, {"x": jnp.full(3, float(s))})
        assert mgr.steps() == [3, 4]


def test_speed_model_deadline():
    sm = SpeedModel(8, seed=0)
    t = sm.round_times(cuts=[2] * 8, flops_per_layer=1e9,
                       smashed_bytes=1e6, adapter_bytes=[1e5] * 8)
    mask, deadline = deadline_survivors(t, deadline_frac=1.5)
    assert mask.any()
    assert (t[mask] <= deadline).all()


def test_serve_model_after_training():
    sys_ = SplitFTSystem(small_arch(), SystemConfig(**SYS), seed=0)
    sys_.run(3, log_every=0)
    params, adapters = sys_.serve_model()
    model = sys_.model
    cache = model.init_cache((2,), 32)
    toks = jnp.ones((2, 16), jnp.int32) * 5
    logits, cache = model.prefill(params, adapters, {"tokens": toks}, cache)
    assert logits.shape == (2, 1, sys_.arch.model.vocab_size)
    lg, cache = model.decode_step(params, adapters,
                                  jnp.ones((2, 1), jnp.int32), cache)
    assert bool(jnp.all(jnp.isfinite(lg)))


def test_train_step_interpret_matches_jnp_backward(monkeypatch):
    """End-to-end numerics guard for the kernel backward path: one full
    make_train_step round with every custom_vjp dispatched through the
    Pallas kernels (interpret mode) must match the jnp-oracle round within
    tolerance on gpt2_small.  Kernel backward changes can never silently
    shift round-engine numerics past this digest."""
    arch = small_arch()
    model = build_model(arch)
    n = 3
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    v = arch.model.vocab_size
    bk = jax.random.PRNGKey(7)
    batch = {"tokens": jax.random.randint(bk, (n, 4, 64), 3, v),
             "labels": jax.random.randint(bk, (n, 4, 64), 3, v),
             "loss_mask": jnp.ones((n, 4, 64), jnp.float32)}
    w = jnp.ones(n) / n
    act = jnp.ones(n)
    lr = jnp.float32(3e-3)

    def one_round(interpret: bool):
        if interpret:
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        state = rounds.init_state(model, key, num_clients=n)
        step = rounds.make_train_step(model, jit=True)
        state, metrics = step(params, state, batch, w, act, lr, lr)
        return state, metrics

    s_jnp, m_jnp = one_round(False)
    s_pls, m_pls = one_round(True)

    np.testing.assert_allclose(np.asarray(m_pls["total"]),
                               np.asarray(m_jnp["total"]),
                               rtol=1e-5, atol=1e-5)
    for part in ("client_adapters", "server_adapters"):
        for (pa, la), (pb, lb) in zip(
                jax.tree_util.tree_leaves_with_path(s_pls[part]),
                jax.tree_util.tree_leaves_with_path(s_jnp[part])):
            assert pa == pb
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=5e-4, atol=5e-5,
                err_msg=f"{part}{jax.tree_util.keystr(pa)}")


def test_noniid_partition_affects_client_data():
    arch = small_arch()
    arch = arch.replace(data=dataclasses.replace(
        arch.data, partition="dirichlet", alpha=0.1))
    sys_ = SplitFTSystem(arch, SystemConfig(**SYS), seed=0)
    sizes = [l.num_samples() for l in sys_.loaders]
    # highly skewed: clients differ in sample counts
    assert max(sizes) > min(sizes)
