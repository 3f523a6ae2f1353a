"""The readers of the program's own spans (chipbench/program_spans.py and
its five metrics) on a small hand-made record: two 50 ms rounds with the
program's span tree and known device idle (tests/data/
program_spans_trace.json, written by hand from the numbers below), then
on the real recorder of a tiny round on the CPU."""

import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, program_spans, programs, trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
NEW = ("round_host_ms.train", "c3_host_ms.train", "exposed_host_ms.train",
       "setup_host_s.train", "setup_compile_s.train")

# Per round (ms from the round's start): the device runs the round step
# over [7.5, 28.5], the C3 evaluation over [33.5, 44.5] and a small op
# over [3, 4].  Its idle pieces fall in these innermost spans:
IDLE_MS = {
    program_spans.OUTSIDE: 1 + 1,          # [0, 1] and [49, 50]
    "splitft.round.plan": 1,               # [1, 2]
    "splitft.round.batch": 1 + 2,          # [2, 3] and [4, 6]
    "splitft.round.dispatch": 1,           # [6, 7]
    "splitft.wait.round": 0.5 + 0.5,       # [7, 7.5] and [28.5, 29]
    "splitft.round.record": 1,             # [29, 30]
    "splitft.c3.batch": 2,                 # [30, 32]
    "splitft.c3.dispatch": 1,              # [32, 33]
    "splitft.wait.c3": 0.5 + 0.5,          # [33, 33.5] and [44.5, 45]
    "splitft.c3.rule": 2,                  # [45, 47]
    "splitft.c3": 1,                       # [47, 48]
    "splitft.round": 1,                    # [48, 49]
}
WANT = {
    # self time: round 1 (48 - 47 in children), plan 1, batch 4,
    # dispatch 1, record 1 (23 - 22 waiting)
    "round_host_ms.train": 8.0,
    # c3 1 (18 - 17 in children), batch 2, dispatch 1, rule 2
    "c3_host_ms.train": 6.0,
    # 17 ms idle a round, less 2 outside and 2 waiting
    "exposed_host_ms.train": 13.0,
    # self time: corpus 2.5 + 0.5, partition 0.1, loaders 0.3, init
    # 2.0 - 0.6 (its two compiles) + 0.1, engine 0.01
    "setup_host_s.train": 4.91,
    # init 0.1 + 0.5; the first round's dispatch 1.0 (a trace nested in
    # a trace counts once) + 0.5 + 8.0; the harness 1.0; not the compile
    # after the window opened
    "setup_compile_s.train": 11.1,
}


def _planes(obj):
    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=float(start),
                                     duration_ns=float(dur), stats=[])
    return [types.SimpleNamespace(
        name=p["name"],
        lines=[types.SimpleNamespace(name=l["name"],
                                     events=[ev(*e) for e in l["events"]])
               for l in p["lines"]])
        for p in obj["planes"]]


def _recorder(records, counters=None):
    return types.SimpleNamespace(
        records=lambda: [tuple(r) for r in records],
        counters=lambda: dict(counters or {}), MAX_RECORDS=1 << 16)


@pytest.fixture
def recorded(monkeypatch):
    with open(DATA / "program_spans_trace.json") as f:
        obj = json.load(f)
    monkeypatch.setitem(sys.modules, "repro.runtime.spans",
                        _recorder(obj["program_records"],
                                  {"compile.cache_hits": 3}))
    return {"trace": trace.from_planes(_planes(obj)),
            "spans": [tuple(s) for s in obj["harness_spans"]],
            "counters": obj["counters"]}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_the_hand_made_record(recorded, name):
    got = harness.metric_reader(name)(recorded)
    assert got == pytest.approx(WANT[name], rel=1e-9, abs=1e-9)


def test_idle_parts_add_up_to_the_window_idle(recorded):
    parts = program_spans.idle_by_span(recorded)
    rounds = recorded["counters"]["rounds"]
    per_round = {n: 1e3 * t / rounds for n, t in parts.items()}
    assert per_round == pytest.approx(IDLE_MS, abs=1e-6)
    tr = recorded["trace"]
    idle = tr.window_s - tr.busy_s(0)
    assert idle == pytest.approx(2 * 17e-3, abs=1e-12)
    assert sum(parts.values()) == pytest.approx(idle, abs=1e-12)
    share = harness.metric_reader("idle_share.train")(recorded)
    assert share == pytest.approx(100.0 * sum(parts.values())
                                  / tr.window_s)


def test_renamed_steps_still_split_by_module(recorded):
    tr = recorded["trace"]
    assert programs.split_step_modules(tr) == pytest.approx((42e-3, 22e-3))
    assert harness.metric_reader("round_device_ms.train")(recorded) == \
        pytest.approx(21.0)
    assert harness.metric_reader("c3_eval_ms.train")(recorded) == \
        pytest.approx(11.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_without_the_program_recorder(recorded, monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert harness.metric_reader(name)(recorded) is None


def test_full_buffer_reads_nothing(recorded, monkeypatch):
    full = _recorder([["s", 0.0, 1.0, {"id": i, "parent": None}]
                      for i in range(8)])
    full.MAX_RECORDS = 8
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", full)
    for name in NEW:
        assert harness.metric_reader(name)(recorded) is None


def test_real_recorder_on_a_tiny_round():
    """The program's recorder, two rounds of the tiny system inside the
    harness's spans, a device that did nothing: all the window is idle,
    so the exposed host time is the non-wait program spans' self time,
    which round and C3 self time cover; the steps' modules carry their
    stable names."""
    import jax

    from repro.config import reduced
    from repro.configs import get_config
    from repro.core.system import SplitFTSystem, SystemConfig

    arch = reduced(get_config("gpt2-small"), layers=2, d_model=64,
                   vocab=512, seq_len=32, batch=2)
    system = SplitFTSystem(arch, SystemConfig(num_samples=40,
                                              eval_samples=16), seed=0)
    seen = []
    inner = system.train_step

    def train_step(*args):
        seen.append(args)
        return inner(*args)

    system.train_step = train_step
    system.run(1, log_every=0)
    hs = harness.Spans()
    with hs.span("bench.window"):
        with hs.span("bench.traced"):
            system.run(2, log_every=0)
    (_, t0, t1, _), = [s for s in hs.records if s[0] == "bench.traced"]
    empty = {"names": [], "start": np.zeros(0), "dur": np.zeros(0)}
    ctx = {"trace": trace.Trace([empty], [[]], [],
                                (5e6, 5e6 + (t1 - t0) * 1e9)),
           "spans": hs.records, "counters": {"rounds": 2}}
    got = {n: harness.metric_reader(n)(ctx) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["exposed_host_ms.train"] == pytest.approx(
        got["round_host_ms.train"] + got["c3_host_ms.train"], rel=1e-6)
    parts = program_spans.idle_by_span(ctx)
    assert sum(parts.values()) == pytest.approx(ctx["trace"].window_s)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          seen[0])
    text = inner.lower(*shapes).as_text()
    assert f"@{programs.ROUND_MODULE}" in text
    assert f"@{programs.C3_MODULE}" in system.eval_step.lower(
        *shapes[:4]).as_text()
