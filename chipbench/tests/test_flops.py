"""flops.py against counts made by hand."""

import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import flops, harness  # noqa: E402


def _dims(name):
    return harness.model_dims(harness.load_json(
        harness.HERE / "configs" / f"{name}.json"))


def test_gpt2_small_training_token():
    dims = _dims("gpt2-small")
    ranks = [16] + [8, 8] + [16] * 9              # cut 2: layers 1, 2 at r 8
    # per layer and token: q k v o (4 x 768^2) + mlp (2 x 768 x 3072)
    macs = 4 * 768 * 768 + 2 * 768 * 3072
    assert macs == 7_077_888
    base = 12 * 4 * macs                          # forward + input grad
    head = 4 * 768 * 50257
    lora = 12 * 4 * 768 * (10 * 16 + 2 * 8)
    per_token = base + head + lora
    assert per_token == 500_616_192
    pairs = 512 * 513 // 2                        # causal row of 512
    attn = 12 * 768 * 12 * pairs                  # 6 matmuls, 12 layers
    assert flops.train_flops(dims, ranks, [512]) == 512 * per_token + attn
    # about 0.53 GFLOP per token at seq 512
    assert abs((512 * per_token + attn) / 512 / 1e9 - 0.529) < 0.001


def test_gpt_neo_window_mask():
    assert flops.kept_keys(512, 256) == 256 * 257 // 2 + 256 * 256
    assert flops.kept_keys(100, 256) == 5050
    assert flops.kept_keys(512, 0) == 512 * 513 // 2
    dims = _dims("gpt-neo-125m")
    assert dims["windows"] == [0, 256] * 6
    mean = flops.mean_kept_pairs(512, dims["windows"])
    assert mean == (131_328 + 98_432) / 2


def test_pads_cost_nothing_and_rows_add():
    dims = _dims("gpt2-small")
    ranks = [16] * 12
    one = flops.train_flops(dims, ranks, [300])
    assert flops.train_flops(dims, ranks, [300, 0]) == one
    assert flops.train_flops(dims, ranks, [300, 300]) == 2 * one


def test_kernel_counts():
    # flash forward: 2 matmuls over kept pairs; bytes q k v o once
    f, b = flops.flash_fwd(20, 512, 12, 64, 131_328)
    assert f == 4 * 20 * 12 * 64 * 131_328
    assert b == 4 * 20 * 512 * 12 * 64 * 4 + 20 * 12 * 512 * 4
    fb, bb = flops.flash_bwd(20, 512, 12, 64, 131_328)
    assert fb == 10 * 20 * 12 * 64 * 131_328
    # decode attention over 300 positions: memory bound on a v5e
    fd, bd = flops.decode_attention(300, 12, 12, 64)
    assert fd == 4 * 12 * 64 * 300
    assert bd == 2 * 12 * 64 * 300 * 4 + 2 * 12 * 64 * 4
    _, which = flops.roofline_seconds(fd, bd, 197e12, 819e9)
    assert which == "memory"
