"""The model families (chipbench/families/): the GPT family reads as the
harness read before it moved behind the seam, a model_type without a
family is refused by name, and a family that is only a file runs a cell.

data/gpt_golden.json was recorded by the harness before the move, on
the configuration files at full and at tiny size: the paths and shapes
of the base weights and adapters, the sums of the tiny weights drawn
from one seed, the `model_flops` counter at fixed row lengths, the flash
kernels' FLOPs and bytes at the cells' shapes, `round_mfu` and
`flash_roofline.train` on a made-up trace, and the `check` numbers of
the tiny training run."""

import io
import json
import pathlib
import sys
import types
from contextlib import redirect_stdout

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops, harness, run, trace, weights  # noqa: E402
from chipbench.reference import static_ranks               # noqa: E402
from chipbench.tests import tiny                            # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "gpt_golden.json").read_text())
LENS = {"full": [512, 300, 17, 0, 1, 257], "tiny": [64, 30, 5, 0, 1, 33]}
SEED = 2 ** 31 + 99
CELLS = {"gpt2s.train.paper": "gpt2-small",
         "neo125.train.paper": "gpt-neo-125m"}


def _cfg(name, size):
    if size == "tiny":
        return tiny.tiny_cfg(name)
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")


def _paths(tree, leaf):
    return {jax.tree_util.keystr(p): leaf(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _sums(x):
    x = np.asarray(x, np.float64)
    return [float(np.sum(x)), float(np.sum(np.abs(x)))]


def _made_up_trace(n_f, n_b):
    names = (["flash_attention_pallas.1"] * n_f
             + ["flash_attention_bwd_pallas.2"] * n_b)
    start = np.arange(len(names), dtype=np.float64) * 1e6
    dur = np.where(np.arange(len(names)) < n_f, 4.1e5, 9.7e5)
    return trace.Trace([{"names": names, "start": start, "dur": dur}],
                       [[]], [], (0.0, 1e9))


@pytest.mark.parametrize("name,size", [
    (n, s) for n in CELLS.values() for s in ("full", "tiny")])
def test_gpt_family_reads_as_before_the_move(name, size):
    want = GOLDEN[f"{name}/{size}"]
    cfg = _cfg(name, size)
    dims = harness.model_dims(cfg)
    lora = cfg["lora"]
    n, rows, s = (5, 20, 512) if size == "full" else (3, 6, 64)
    shape = lambda x: list(x.shape)                  # noqa: E731
    key = jax.random.PRNGKey(7)
    assert {p: list(v) for p, v in dims["family"].base_shapes(dims).items()
            } == want["base"]
    cad, sad = jax.eval_shape(
        lambda k: weights.make_train_adapters(dims, lora, n, k), key)
    assert _paths(cad, shape) == want["client_adapters"]
    assert _paths(sad, shape) == want["server_adapters"]
    pool = jax.eval_shape(lambda k: weights.make_pool(dims, lora, 4, k), key)
    assert _paths(pool, shape) == want["pool"]

    ranks = static_ranks(dims, lora)
    lens = LENS[size]
    assert flops.train_flops(dims, ranks, lens) == want["model_flops"]
    assert [flops.train_flops(dims, ranks, [x]) for x in lens] == \
        want["model_flops_rows"]
    (heads, _, qk, v), windows = next(iter(flops.attn_groups(dims).items()))
    args = (rows, s, heads, qk, v, flops.mean_kept_pairs(s, windows))
    assert list(flops.flash_fwd(*args)) == want["flash_fwd"]
    assert list(flops.flash_bwd(*args)) == want["flash_bwd"]
    rctx = {"trace": _made_up_trace(24, 12),
            "counters": {"rows": rows, "seq_len": s,
                         "model_flops": flops.train_flops(
                             dims, ranks, [s] * rows * 3)},
            "window_s": 0.75, "dims": dims, "chips": 1,
            "peaks": harness.peaks_for("TPU v5 lite")}
    for metric in ("flash_roofline.train", "round_mfu"):
        assert harness.metric_reader(metric)(rctx) == want[metric]

    if size == "tiny":
        k_base, k_ad = jax.random.split(
            jax.random.PRNGKey(harness.program_seed(SEED)))
        assert _paths(weights.make_base(dims, k_base), _sums) == \
            want["base_sums"]
        c, s_ = weights.make_train_adapters(dims, lora, n, k_ad)
        assert _paths({"cad": c, "sad": s_}, _sums) == want["adapter_sums"]
        assert _paths(weights.make_pool(dims, lora, 4, k_ad), _sums) == \
            want["pool_sums"]


def _check(workload, cfg, **overrides):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", "1", "--trace", "0"], require_tpu=False,
                      overrides=dict(overrides, cfg=cfg,
                                     traffic=tiny.tiny_train_traffic()))
    assert rc == 0
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True, res["check"]
    return {k: v["value"] for k, v in res["check"].items()}


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_training_checks_as_before_the_move(workload):
    got = _check(workload, tiny.tiny_cfg(CELLS[workload]))
    assert got == GOLDEN[f"check/{workload}"]


def test_unknown_family_is_named(capsys):
    with pytest.raises(harness.BenchError,
                       match="chipbench/families/no_such_model.py"):
        harness.load_family("no_such_model")
    cfg = dict(tiny.tiny_cfg(), model_type="no_such_model")
    rc = run.main(["--workload", "gpt2s.train.paper", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], require_tpu=False,
                  overrides={"cfg": cfg, "traffic": tiny.tiny_train_traffic()})
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "chipbench/families/no_such_model.py" in err


def test_family_that_is_only_a_file():
    """data/gpt2_copy.py wraps the GPT-2 family under model_type
    gpt2_copy; found on the test's search path, it runs the tiny
    training cell and checks as the GPT-2 family does."""
    cfg = dict(tiny.tiny_cfg(), model_type="gpt2_copy")
    with pytest.raises(harness.BenchError):
        harness.model_dims(cfg)
    fam = harness.model_dims(cfg, [DATA])["family"]
    assert isinstance(fam, types.ModuleType)
    assert pathlib.Path(fam.__file__).parent == DATA
    got = _check("gpt2s.train.paper", cfg, families=[DATA])
    assert got == GOLDEN["check/gpt2s.train.paper"]
