"""The trace reduction, on a small recorded profile (a 53 ms slice of a
gpt2-small training round on one v5e, trimmed to about 200 device ops),
on the modules and harness spans of a traced window of each training
cell (one v5e, seed 2718281828), and on a hand-made profile."""

import json
import pathlib
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import harness, programs, trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _planes(obj):
    """Profile-like planes (.name, .lines, .events) from plain lists."""
    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=float(start),
                                     duration_ns=float(dur), stats=[])
    return [types.SimpleNamespace(
        name=p["name"],
        lines=[types.SimpleNamespace(name=l["name"],
                                     events=[ev(*e) for e in l["events"]])
               for l in p["lines"]])
        for p in obj["planes"]]


def _recorded():
    with open(DATA / "train_round_trace.json") as f:
        return json.load(f)


def test_hand_made_profile():
    obj = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while.1", 0, 40], ["fusion.3", 0, 10], ["fusion.4", 5, 15],
                ["flash_attention_pallas.7", 30, 10]]},
            {"name": "XLA Modules", "events": [
                ["jit_round_step(1)", 0, 20],
                ["jit_c3_eval_step(2)", 30, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["bench.traced", 0, 50], ["bench.round", 0, 50],
                ["bench.eval_step", 20, 8]]}]}]}
    tr = trace.from_planes(_planes(obj))
    assert tr.window == (0.0, 50.0)
    assert tr.busy_s(0) == pytest.approx(40e-9)  # the while: [0, 40]
    tr.devices[0]["names"][0] = "fusion.9"       # without the container:
    tr.devices[0]["start"][0], tr.devices[0]["dur"][0] = 0.0, 1.0
    assert tr.busy_s(0) == pytest.approx(30e-9)  # [0, 20] and [30, 40]
    gaps = dict(tr.idle_gaps())
    assert gaps == pytest.approx({"bench.eval_step": 10e-9,
                                  "bench.round": 10e-9})
    assert tr.op_seconds(programs.is_flash_fwd) == pytest.approx((10e-9, 1))
    assert programs.split_step_modules(tr) == pytest.approx((20e-9, 10e-9))


def test_recorded_profile():
    obj = _recorded()
    tr = trace.from_planes(_planes(obj))
    lo, hi = tr.window
    assert hi - lo == 53e6
    ops = [e for p in obj["planes"] if p["name"].startswith("/device")
           for l in p["lines"] if l["name"] == "XLA Ops" for e in l["events"]]
    # busy: the union of op intervals, counted here on a 100 ns grid
    grid = np.zeros(int((hi - lo) / 100), bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    assert abs(tr.busy_s(0) - grid.sum() * 100e-9) < 2e-5
    idle = sum(t for _, t in tr.idle_gaps(100))
    assert idle + tr.busy_s(0) == pytest.approx(tr.window_s)
    # kernel time: the events of that kernel inside the window
    want = sum(d for n, s, d in ops if "flash_attention_pallas" in n
               and s >= lo and s + d <= hi) * 1e-9
    got, count = tr.op_seconds(programs.is_flash_fwd)
    assert got == pytest.approx(want) and count > 0
    kinds = [k for k, _ in tr.top_ops(10)]
    assert "while" not in kinds and kinds
    assert all(not k[-1].isdigit() or "." not in k for k in kinds)
    # recorded before the steps had their stable names, and no module
    # run lies whole inside the slice
    assert programs.split_step_modules(tr) == (None, None)


@pytest.mark.parametrize("name,rounds,want", [
    ("train_modules_gpt2s.json", 17, (2.5479870260000004, 1.22956157)),
    ("train_modules_neo125.json", 18, (2.3304427290000005, 1.323117253))])
def test_recorded_steps_are_found_by_name(name, rounds, want):
    """By name the same modules as by size, the rule before: of the step
    modules run whole inside the window, the one with more time."""
    with open(DATA / name) as f:
        tr = trace.from_planes(_planes(json.load(f)))
    got = programs.split_step_modules(tr)
    assert got == pytest.approx(want, rel=1e-12)
    tot = {}
    for n, s, d in tr.modules[0]:
        if n.split("(")[0].endswith("_step") and s >= tr.window[0] \
                and s + d <= tr.window[1]:
            tot[n] = tot.get(n, 0.0) + d * 1e-9
    assert tuple(sorted(tot.values(), reverse=True)) == \
        pytest.approx(got, rel=1e-12)
    ctx = {"trace": tr, "counters": {"rounds": rounds}}
    assert harness.metric_reader("round_device_ms.train")(ctx) == \
        pytest.approx(1e3 * got[0] / rounds)


def test_op_names():
    long = ("%flash_attention_pallas.7 = (f32[240,512,64]{2,1,0}) "
            "custom-call(s32[1]{0} %get-tuple-element.3113)")
    assert trace.short_name(long) == "flash_attention_pallas.7"
    assert trace.op_kind("flash_attention_pallas.7") == \
        "flash_attention_pallas"
    assert trace.op_kind("all-reduce-start") == "all-reduce-start"
    assert programs.is_flash_fwd("flash_attention_pallas.7")
    assert not programs.is_flash_fwd("flash_attention_bwd_pallas.20")
    assert programs.is_collective("all-reduce.3")
