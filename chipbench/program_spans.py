"""The program's own host spans (`repro.runtime.spans`), reduced for the
per-layer readers.

The program records (name, start, end, extra) on `time.perf_counter()`,
the clock of the harness's spans; `extra` holds the span's `id` and its
`parent`'s.  One interval sits in both the harness's records and the
profiler trace: `bench.traced`, which is `trace.window` in trace
nanoseconds.  Its two ends put program spans on the device trace's
clock; the mapping is good to a few microseconds, and the idle gaps it
splits are milliseconds long.

On a tree whose program has no recorder every function here returns
None, and so does every reader built on them.
"""

from __future__ import annotations

import collections
import importlib

import numpy as np

from chipbench import trace as trace_lib
from chipbench.harness import log

WINDOW = "bench.window"
TRACED = "bench.traced"
OUTSIDE = "outside the program"


def recorder():
    """The program's span module, or None where it has none."""
    try:
        return importlib.import_module("repro.runtime.spans")
    except ImportError:
        return None


def program_records():
    """All records the program kept, or None (no recorder, or a buffer
    that has overflowed and so lost the oldest)."""
    mod = recorder()
    if mod is None:
        return None
    recs = list(mod.records())
    if len(recs) >= mod.MAX_RECORDS:
        log(f"program spans: {len(recs)} records fill the buffer; the "
            "oldest are lost, no span metric is read")
        return None
    return recs


def harness_span(ctx, name):
    """(start, end) of the last harness span `name`, or None."""
    found = [s for s in ctx["spans"] if s[0] == name]
    return (found[-1][1], found[-1][2]) if found else None


def is_wait(name):
    """A span in which the host waits on the device."""
    return ".wait." in name


def union_length(intervals):
    """Length of the union of (start, end) intervals."""
    iv = list(intervals)
    return trace_lib.union_length([a for a, _ in iv], [b for _, b in iv],
                                  -np.inf, np.inf)


def traced_records(ctx):
    """Program records that lie inside the traced segment, or None."""
    recs = program_records()
    seg = harness_span(ctx, TRACED)
    if recs is None or seg is None:
        return None
    return [r for r in recs if r[1] >= seg[0] and r[2] <= seg[1]]


def self_seconds(recs, select):
    """{name: host self time} over the records whose name `select`
    accepts: each span's duration minus the part of it that its child
    spans cover."""
    kids = collections.defaultdict(lambda: ([], []))
    for _, t0, t1, extra in recs:
        starts, ends = kids[extra.get("parent")]
        starts.append(t0)
        ends.append(t1)
    parts = collections.Counter()
    for name, t0, t1, extra in recs:
        if select(name):
            starts, ends = kids.get(extra["id"], ((), ()))
            parts[name] += (t1 - t0) - trace_lib.union_length(
                starts, ends, t0, t1)
    return parts


def self_ms_per_round(ctx, metric, select):
    """Host self time per round, in ms, of the traced spans `select`
    accepts; the parts go to standard error."""
    recs = traced_records(ctx)
    rounds = ctx["counters"].get("rounds")
    if recs is None or not rounds:
        return None
    parts = self_seconds(recs, select)
    if not parts:
        return None
    log(f"{metric}: host self time per round over {rounds} rounds: "
        + ", ".join(f"{n} {1e3 * t / rounds:.3f} ms"
                    for n, t in parts.most_common()))
    return 1e3 * sum(parts.values()) / rounds


def idle_by_span(ctx):
    """Device 0's idle time in the traced window, in seconds, by the
    innermost program span open at each idle instant (OUTSIDE where
    none is), on the trace's clock; None without trace or spans."""
    tr = ctx["trace"]
    recs = program_records()
    seg = harness_span(ctx, TRACED)
    if recs is None or seg is None or not tr.devices or tr.window_s <= 0:
        return None
    lo, hi = tr.window
    if seg[1] <= seg[0]:
        return None
    scale = (hi - lo) / (seg[1] - seg[0])      # trace ns per host second
    log(f"program spans: bench.traced is {hi - lo:.0f} ns on the trace "
        f"and {1e9 * (seg[1] - seg[0]):.0f} ns on the host clock")
    names, starts, ends = [], [], []
    for name, t0, t1, _ in recs:
        a = max(lo + (t0 - seg[0]) * scale, lo)
        b = min(lo + (t1 - seg[0]) * scale, hi)
        if b > a:
            names.append(name)
            starts.append(a)
            ends.append(b)
    # the window cut at every span boundary, each piece owned by the
    # innermost span open over it: spans painted in order of start,
    # longest first, so a later or shorter span paints over its outer
    cuts = np.unique(np.concatenate([[lo, hi], starts, ends]))
    owner = np.full(len(cuts) - 1, -1)
    for k in sorted(range(len(names)), key=lambda k: (starts[k], -ends[k])):
        i, j = np.searchsorted(cuts, [starts[k], ends[k]])
        owner[i:j] = k
    d = tr.devices[0]
    gaps = trace_lib.complement(d["start"], d["start"] + d["dur"], lo, hi)
    parts = collections.Counter()
    if not gaps:
        return parts
    # the idle gaps cut once more at the span boundaries
    ga, gb = np.asarray(gaps).T
    pts = np.unique(np.concatenate([cuts, ga, gb]))
    mid = 0.5 * (pts[:-1] + pts[1:])
    g = np.searchsorted(ga, mid, side="right") - 1
    idle = (g >= 0) & (mid < gb[np.maximum(g, 0)])
    own = owner[np.searchsorted(cuts, mid, side="right") - 1][idle]
    per = np.bincount(own + 1, weights=np.diff(pts)[idle],
                      minlength=len(names) + 1)
    for k in np.flatnonzero(per):
        parts[names[k - 1] if k > 0 else OUTSIDE] += per[k] * 1e-9
    return parts


def setup_records(ctx):
    """Program records that ended before the measured window opened, with
    a map from span id to name; None where either is missing."""
    recs = program_records()
    win = harness_span(ctx, WINDOW)
    if recs is None or win is None:
        return None
    names = {extra["id"]: name for name, _, _, extra in recs}
    return [r for r in recs if r[2] <= win[0]], names
