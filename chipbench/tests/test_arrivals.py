"""The open-loop generator: seeded, the same work for every seed, and
latencies stamped from the scheduled arrival."""

import math
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import arrivals, harness  # noqa: E402

TR = harness.load_json(harness.HERE / "traffic" / "serve.steady.json")


def _key(d):
    return (d.rid, d.arrival, d.adapter, d.max_new, d.tokens.tobytes())


def test_same_seed_same_requests():
    a = arrivals.schedule(TR, 2 ** 31 + 12345, 20, 50257)
    b = arrivals.schedule(TR, 2 ** 31 + 12345, 20, 50257)
    assert [_key(x) for x in a] == [_key(x) for x in b]


def test_every_seed_gets_the_same_work():
    a = arrivals.schedule(TR, 1, 20, 50257)
    b = arrivals.schedule(TR, 2, 20, 50257)
    assert len(a) == len(b) == round(TR["rate_per_s"] * 20)
    # the same schedule: arrivals and sizes in the same order
    for f in (lambda d: d.arrival, lambda d: len(d.tokens),
              lambda d: d.max_new):
        assert list(map(f, a)) == list(map(f, b))
    # the seed draws the content: token ids and the adapters' order
    assert sorted(d.adapter for d in a) == sorted(d.adapter for d in b)
    assert [d.adapter for d in a] != [d.adapter for d in b]
    assert all(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    for s in (a, b):
        t = [d.arrival for d in s]
        assert t == sorted(t) and 0 <= t[0] and t[-1] < 20
        assert all(TR["prompt"]["min"] <= len(d.tokens) <= TR["prompt"]["max"]
                   for d in s)
        assert all(len(d.tokens) + d.max_new <= TR["max_len"] for d in s)


def test_lognormal_median_and_zipf_skew():
    lens = arrivals.lognormal_set(TR["prompt"], 1001)
    assert np.median(lens) == TR["prompt"]["median"]
    ids = arrivals.zipf_set(64, 1.0, 10_000)
    counts = np.bincount(ids, minlength=64)
    assert counts[0] > 10 * counts[63] and counts.sum() == 10_000


def test_latencies_run_from_the_scheduled_arrival():
    due = [arrivals.Due(0, 1.0, 0, np.zeros(4, np.int32), 3),
           arrivals.Due(1, 2.0, 0, np.zeros(4, np.int32), 2)]
    # request 0 was admitted late: its wait counts in its first token
    emitted = {0: [1.5, 1.5, 1.75], 1: [2.25]}
    ttft, tbt, done = arrivals.latencies(due, emitted)
    assert ttft[0] == 0.5 and math.isinf(ttft[1])
    assert tbt == [0.0, 0.25] and done == [0]
