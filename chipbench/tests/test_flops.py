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
    windows = [lay["attn"]["window"] for lay in dims["layer"]]
    assert windows == [0, 256] * 6
    assert list(flops.attn_groups(dims).values()) == [windows]
    mean = flops.mean_kept_pairs(512, windows)
    assert mean == (131_328 + 98_432) / 2


def test_pads_cost_nothing_and_rows_add():
    dims = _dims("gpt2-small")
    ranks = [16] * 12
    one = flops.train_flops(dims, ranks, [300])
    assert flops.train_flops(dims, ranks, [300, 0]) == one
    assert flops.train_flops(dims, ranks, [300, 300]) == 2 * one


def test_kernel_counts():
    # flash forward: 2 matmuls over kept pairs; bytes q k v o once
    f, b = flops.flash_fwd(20, 512, 12, 64, 64, 131_328)
    assert f == 4 * 20 * 12 * 64 * 131_328
    assert b == 4 * 20 * 512 * 12 * 64 * 4 + 20 * 12 * 512 * 4
    fb, bb = flops.flash_bwd(20, 512, 12, 64, 64, 131_328)
    assert fb == 10 * 20 * 12 * 64 * 131_328
    # decode attention over 300 positions: memory bound on a v5e
    fd, bd = flops.decode_attention(300, 12, 12, 64, 64)
    assert fd == 4 * 12 * 64 * 300
    assert bd == 2 * 12 * 64 * 300 * 4 + 2 * 12 * 64 * 4
    _, which = flops.roofline_seconds(fd, bd, 197e12, 819e9)
    assert which == "memory"
    # a query/key head dim of 192 and a value head dim of 128, 16 heads:
    # forward q k^T 2 x 192 and p v 2 x 128 per pair; the backward
    # rebuilds the scores and takes dQ, dK over 192, dP, dV over 128
    f, b = flops.flash_fwd(4, 512, 16, 192, 128, 131_328)
    assert f == 4 * 16 * (2 * 192 + 2 * 128) * 131_328
    assert b == 4 * 512 * 16 * (192 + 192 + 128 + 128) * 4 + 4 * 16 * 512 * 4
    fb, bb = flops.flash_bwd(4, 512, 16, 192, 128, 131_328)
    assert fb == 4 * 16 * (3 * 2 * 192 + 2 * 2 * 128) * 131_328
    assert bb == 4 * 512 * 16 * (4 * 192 + 4 * 128) * 4 + 4 * 16 * 512 * 4


def test_layers_of_two_kinds():
    """A dense layer and a layer of routed experts (its `macs` the k
    active of E), each with its own adapter targets and head dims: every
    term is the layer's own."""
    def layer(macs, targets, qk, v, window):
        return {"macs": macs, "targets": targets,
                "attn": {"heads": 2, "kv_heads": 2, "qk_dim": qk, "v_dim": v,
                         "window": window}}
    dense = layer(1000, {"q": (8, 24), "o": (16, 8)}, 12, 8, 0)
    moe = layer(6 * 300 + 2 * 300, {"q": (8, 24)}, 12, 8, 4)
    dims = {"layer": [dense, moe], "head_macs": 8 * 100}
    lens = [6]
    per_token = (4 * (1000 + 2400) + 4 * 800
                 + 6 * (3 * (32 + 24) + 2 * 32))        # ranks 3 and 2
    # kept pairs of a row of 6: 21 causal, 18 within a window of 4
    assert flops.kept_keys(6, 4) == 1 + 2 + 3 + 4 + 4 + 4
    attn = 6 * 2 * (12 + 8) * (21 + 18)
    assert flops.train_flops(dims, [3, 2], lens) == 6 * per_token + attn
    assert flops.attn_groups(dims) == {(2, 2, 12, 8): [0, 4]}
