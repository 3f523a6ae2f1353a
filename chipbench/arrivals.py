"""The open-loop request generator that every serving traffic file
drives.

Every seed gets the same work in the same order: one schedule, drawn
from the traffic file's own `schedule_seed`.  The number of requests due
in the window is rate x seconds; their arrival times are the order
statistics of that many uniform draws (a Poisson process conditioned on
its count); the prompt and output lengths are one fixed set, made from
the quantiles of the traffic's distributions, dealt out to the arrivals
in the schedule's order.  The run's seed draws what the requests hold:
the prompts' token ids and which adapter each request asks for (the
Zipf set of adapters, in a seeded order).  Near the knee a queue turns
a reordering of sizes into a different run, so sizes stay where the
schedule puts them.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Due:
    rid: int
    arrival: float          # seconds after the window opens
    adapter: int
    tokens: np.ndarray      # prompt ids
    max_new: int


def lognormal_set(spec, n):
    """n lengths at the quantiles (i + 1/2) / n of the lognormal, clipped."""
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])
    vals = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def zipf_set(p, s, n):
    """n adapter ids at the quantiles of a Zipf(s) law over p adapters."""
    w = 1.0 / np.arange(1, p + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    q = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, q), p - 1).astype(np.int64)


def due_count(tr, seconds):
    return max(1, int(round(tr["rate_per_s"] * seconds)))


def schedule(tr, seed, seconds, vocab):
    """The requests due in [0, seconds), sorted by arrival."""
    n = due_count(tr, seconds)
    fixed = np.random.default_rng(tr["schedule_seed"])
    arrivals = np.sort(fixed.uniform(0.0, seconds, n))
    plen = fixed.permutation(lognormal_set(tr["prompt"], n))
    outl = fixed.permutation(lognormal_set(tr["output"], n))
    rng = np.random.default_rng(seed)
    ads = rng.permutation(zipf_set(tr["adapters"], tr["adapter_zipf"], n))
    return [Due(rid=i, arrival=float(arrivals[i]), adapter=int(ads[i]),
                tokens=rng.integers(3, vocab, size=int(plen[i]),
                                    dtype=np.int64).astype(np.int32),
                max_new=int(outl[i]))
            for i in range(n)]


def buckets_used(tr, seconds, buckets):
    """The prefill buckets that this traffic's prompts fall into."""
    lens = lognormal_set(tr["prompt"], due_count(tr, seconds))
    used = set()
    for n in lens:
        used.add(next(b for b in buckets if b >= n))
    return sorted(used)


def latencies(due, emitted):
    """(time to first token per due request, every gap between tokens,
    rids done).  emitted: rid -> host times of its tokens, on the clock
    of the scheduled arrivals.  A request that never finished counts
    an infinite time to first token."""
    ttft, tbt, done = [], [], []
    for d in due:
        ts = emitted.get(d.rid, [])
        if len(ts) < d.max_new:
            ttft.append(math.inf)
            continue
        done.append(d.rid)
        ttft.append(ts[0] - d.arrival)
        tbt.extend(np.diff(ts).tolist())
    return ttft, tbt, done
