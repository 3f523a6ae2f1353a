"""The program's span and counter recorder (repro.runtime.spans) and the
span tree of one barrier round: nesting, round ids, self time, the
bounded buffer, compile events, the profiler's host plane, and that the
spans change no result."""

import contextlib
import glob
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduced
from repro.configs import get_config
from repro.core import system as system_lib
from repro.core.system import SplitFTSystem, SystemConfig
from repro.runtime import spans

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench.program_spans import self_seconds  # noqa: E402


@pytest.fixture(autouse=True)
def fresh():
    spans.reset()
    yield
    spans.reset()


def by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_nesting_parent_and_round_ids():
    with spans.span("outer", round=3):
        with spans.span("inner"):
            pass
        with spans.span("other", rid=5):
            with spans.span("leaf"):
                pass
    with spans.span("after"):
        pass
    recs = by_name(spans.records())
    outer, inner = recs["outer"][0], recs["inner"][0]
    other, leaf = recs["other"][0], recs["leaf"][0]
    assert outer.extra["parent"] is None
    assert inner.extra["parent"] == outer.extra["id"]
    assert other.extra["parent"] == outer.extra["id"]
    assert leaf.extra["parent"] == other.extra["id"]
    assert recs["after"][0].extra["parent"] is None
    # a span inherits its parent's ids: all spans of round 3 carry it
    assert {r.extra.get("round") for r in (outer, inner, other, leaf)} == {3}
    assert leaf.extra["rid"] == 5 and "round" not in recs["after"][0].extra
    for r in spans.records():
        assert r.start <= r.end
    assert outer.start <= inner.start <= inner.end <= other.start
    assert leaf.end <= other.end <= outer.end
    # records unpack as the benchmark harness's host spans do
    name, t0, t1, extra = inner
    assert (name, t0, t1) == ("inner", inner.start, inner.end)


def test_self_time_leaves_out_children():
    import time
    with spans.span("parent"):
        time.sleep(0.02)
        with spans.span("child"):
            time.sleep(0.03)
    recs = spans.records()
    parent = by_name(recs)["parent"][0]
    child = by_name(recs)["child"][0]
    assert child.end - child.start >= 0.03
    # the benchmark's reader of self time, on the recorder's records
    st = self_seconds(recs, lambda name: True)
    assert st["parent"] == pytest.approx((parent.end - parent.start)
                                         - (child.end - child.start))
    assert st["parent"] >= 0.02
    assert st["child"] == pytest.approx(child.end - child.start)


def test_buffer_stays_bounded():
    n = spans.MAX_RECORDS + 10
    for i in range(n):
        with spans.span("s", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.MAX_RECORDS
    assert recs[0].extra["i"] == 10 and recs[-1].extra["i"] == n - 1


def test_counters():
    spans.count("x")
    spans.count("x", 4)
    assert spans.counters()["x"] == 5
    spans.reset()
    assert spans.counters() == {}


def test_recompile_shows_under_the_span_that_caused_it():
    def doubled_for_spans_test(x):
        return x * 2

    f = jax.jit(doubled_for_spans_test)
    with spans.span("first", round=1):
        f(jnp.ones(3))
    spans.reset()
    with spans.span("steady", round=2):
        f(jnp.ones(3))                 # cached: nothing compiles
    assert not [r for r in spans.records()
                if r.name.startswith("compile.")]
    with spans.span("again", round=3):
        f(jnp.ones(4))                 # a new shape recompiles
    recs = spans.records()
    again = by_name(recs)["again"][0]
    comp = [r for r in recs if r.name.startswith("compile.")
            and "doubled_for_spans_test" in r.extra["fun_name"]]
    names = {r.name for r in comp}
    assert {"compile.jaxpr_trace", "compile.backend_compile"} <= names
    for r in comp:
        assert r.extra["parent"] == again.extra["id"]
        assert r.extra["round"] == 3
        assert again.start <= r.start <= r.end <= again.end


# ---------------------------------------------------------------------------
# the barrier round


def tiny_system(**kw):
    arch = reduced(get_config("gpt2-small"), layers=2, d_model=64,
                   vocab=512, seq_len=32, batch=2)
    return SplitFTSystem(arch, SystemConfig(num_samples=40, eval_samples=16,
                                            **kw), seed=0)


ROUND_TREE = {
    ("splitft.round", None),
    ("splitft.round.plan", "splitft.round"),
    ("splitft.round.batch", "splitft.round"),
    ("splitft.round.dispatch", "splitft.round"),
    ("splitft.round.record", "splitft.round"),
    ("splitft.wait.round", "splitft.round.record"),
}
C3_TREE = {
    ("splitft.c3", "splitft.round"),
    ("splitft.c3.batch", "splitft.c3"),
    ("splitft.c3.dispatch", "splitft.c3"),
    ("splitft.wait.c3", "splitft.c3"),
    ("splitft.c3.rule", "splitft.c3"),
}
SETUP = {"splitft.setup.corpus", "splitft.setup.partition",
         "splitft.setup.loaders", "splitft.setup.init",
         "splitft.setup.engine"}


def tree(recs, round_idx):
    """(name, parent name) of the program spans of one round, exactly
    one each."""
    names = {r.extra["id"]: r.name for r in recs}
    mine = [r for r in recs if r.extra.get("round") == round_idx
            and not r.name.startswith("compile.")]
    pairs = [(r.name, names.get(r.extra["parent"])) for r in mine]
    assert len(pairs) == len(set(pairs)), pairs
    return set(pairs)


def test_setup_spans():
    tiny_system()
    recs = spans.records()
    assert {r.name for r in recs if r.name.startswith("splitft.")} == SETUP
    for r in recs:
        if r.name.startswith("splitft."):
            assert r.extra["parent"] is None


def test_barrier_round_span_tree():
    s = tiny_system(adjust_every=2)
    spans.reset()
    s.run(2, log_every=0)
    recs = spans.records()
    assert tree(recs, 0) == ROUND_TREE             # round 0: no C3
    assert tree(recs, 1) == ROUND_TREE | C3_TREE   # round 1: C3 runs
    # the first call of each step compiles under its dispatch span
    names = {r.extra["id"]: r.name for r in recs}
    comp = {(r.extra["fun_name"], names[r.extra["parent"]])
            for r in recs if r.name == "compile.backend_compile"}
    assert ("jit(round_step)", "splitft.round.dispatch") in comp
    assert ("jit(c3_eval_step)", "splitft.c3.dispatch") in comp


def test_spans_reach_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    s = tiny_system()
    s.run(1, log_every=0)                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.run(1, log_every=0)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    host = set()
    for p in ProfileData.from_file(path).planes:
        if p.name.startswith("/host:"):
            host |= {e.name for line in p.lines for e in line.events}
    want = {n for n, _ in ROUND_TREE | C3_TREE}
    assert want <= host
    # the two jitted steps carry stable names
    assert {"PjitFunction(round_step)",
            "PjitFunction(c3_eval_step)"} <= host


def test_history_and_state_unchanged_by_spans(monkeypatch):
    def run():
        s = tiny_system()
        hist = s.run(3, log_every=0)
        return hist, jax.tree.map(np.asarray, s.state)

    with_spans = run()
    monkeypatch.setattr(system_lib, "span",
                        lambda name, **ids: contextlib.nullcontext())
    spans.reset()
    without = run()
    assert not spans.records() or all(
        r.name.startswith("compile.") for r in spans.records())
    for a, b in zip(with_spans[0], without[0]):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)
    jax.tree.map(np.testing.assert_array_equal, with_spans[1], without[1])
