"""Reduction of one profiler trace to what the per-layer readers need.

`load(trace_dir)` reads the `.xplane.pb` the JAX profiler wrote and keeps
three things, all on the trace's own clock in nanoseconds:

* device ops: per device, every event of its "XLA Ops" line (the HLO
  instruction's name, e.g. `flash_attention_pallas.7`, start, duration);
* device modules: every event of its "XLA Modules" line (one per run of a
  compiled program);
* host spans: the harness's own `bench.*` TraceAnnotations.

The window is the harness's `bench.traced` span: the part of the
measured window that the profiler saw.  Busy time is the union
of the op intervals inside it; idle gaps are the rest, each put down to
the innermost harness span open at its middle.
"""

from __future__ import annotations

import collections
import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


class Trace:
    def __init__(self, devices, modules, spans, window):
        self.devices = devices        # [{"names": [...], "start", "dur", "module": [...]}]
        self.modules = modules        # [[(name, start, dur)]] per device
        self.spans = spans            # [(name, start, dur)]
        self.window = window          # (start_ns, end_ns)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, dev):
        d = self.devices[dev]
        return union_length(d["start"], d["start"] + d["dur"],
                            *self.window) * 1e-9

    def mean_busy_s(self):
        return float(np.mean([self.busy_s(i)
                              for i in range(len(self.devices))]))

    def op_seconds(self, match, dev=None):
        """(total seconds, event count) of ops whose name satisfies match,
        inside the window, averaged over devices (or on one)."""
        devs = range(len(self.devices)) if dev is None else [dev]
        tot, cnt = [], []
        for i in devs:
            d = self.devices[i]
            sel = np.array([match(n) for n in d["names"]], bool) \
                if d["names"] else np.zeros(0, bool)
            sel &= self._inside(d["start"], d["dur"])
            tot.append(float(d["dur"][sel].sum()) * 1e-9)
            cnt.append(int(sel.sum()))
        return float(np.mean(tot)), float(np.mean(cnt))

    def module_seconds(self, match, dev=None):
        devs = range(len(self.modules)) if dev is None else [dev]
        tot, cnt = [], []
        for i in devs:
            t = c = 0
            for name, s, du in self.modules[i]:
                if match(name) and s >= self.window[0] and \
                        s + du <= self.window[1]:
                    t += du
                    c += 1
            tot.append(t * 1e-9)
            cnt.append(c)
        return float(np.mean(tot)), float(np.mean(cnt))

    def _inside(self, start, dur):
        return (start >= self.window[0]) & (start + dur <= self.window[1])

    def top_ops(self, k=10):
        """The k kinds of device op that took most time in the window
        (mean over devices); ops that contain others are left out."""
        tot = collections.Counter()
        for d in self.devices:
            sel = self._inside(d["start"], d["dur"])
            for n, du in zip(np.asarray(d["names"], object)[sel],
                             d["dur"][sel]):
                kind = op_kind(n)
                if kind not in CONTAINERS:
                    tot[kind] += float(du) * 1e-9
        n_dev = max(len(self.devices), 1)
        return [[name, t / n_dev] for name, t in tot.most_common(k)]

    def idle_gaps(self, k=10):
        """Idle time on device 0 inside the window, by the host span that
        was open at the middle of each gap; the k largest totals."""
        d = self.devices[0]
        gaps = complement(d["start"], d["start"] + d["dur"], *self.window)
        spans = sorted(self.spans, key=lambda s: s[2])   # innermost first
        tot = collections.Counter()
        for a, b in gaps:
            mid = 0.5 * (a + b)
            name = next((n for n, s, du in spans if s <= mid <= s + du
                         and n != WINDOW_SPAN), "outside harness spans")
            tot[name] += (b - a) * 1e-9
        return [[n, t] for n, t in tot.most_common(k)]


def merged(starts, ends, lo, hi):
    """Sorted, merged intervals clipped to [lo, hi]."""
    if len(starts) == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s = np.clip(np.asarray(starts, np.float64)[order], lo, hi)
    e = np.clip(np.asarray(ends, np.float64)[order], lo, hi)
    out = []
    cs, ce = s[0], e[0]
    for a, b in zip(s[1:], e[1:]):
        if a <= ce:
            ce = max(ce, b)
        else:
            out.append((cs, ce))
            cs, ce = a, b
    out.append((cs, ce))
    return [(a, b) for a, b in out if b > a]


def union_length(starts, ends, lo, hi):
    return float(sum(b - a for a, b in merged(starts, ends, lo, hi)))


def complement(starts, ends, lo, hi):
    gaps, cur = [], lo
    for a, b in merged(starts, ends, lo, hi):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(name):
    """'%flash_attention_pallas.7 = (...) custom-call(...)' ->
    'flash_attention_pallas.7'."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(short):
    """'flash_attention_pallas.7' -> 'flash_attention_pallas'."""
    head, _, tail = short.rpartition(".")
    return head if head and tail.isdigit() else short


# ops that contain other ops on the same line (their time is their body's)
CONTAINERS = ("while", "conditional", "call")


def load(trace_dir, n_devices=None):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return from_planes(pd.planes, n_devices)


def from_planes(planes, n_devices=None):
    """Build a Trace from profiler planes (ProfileData planes, or any
    objects with .name, .lines; lines with .name, .events; events with
    .name, .start_ns, .duration_ns, .stats)."""
    devices, modules, spans = [], [], []
    for p in planes:
        if p.name.startswith("/device:") and "TPU" in p.name \
                and "NON_CORE" not in p.name:
            names, st, du, mlist = [], [], [], []
            for line in p.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        names.append(short_name(e.name))
                        st.append(e.start_ns)
                        du.append(e.duration_ns)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        mlist.append((e.name, e.start_ns, e.duration_ns))
            devices.append({"names": names,
                            "start": np.asarray(st, np.float64),
                            "dur": np.asarray(du, np.float64),
                            "plane": p.name})
            modules.append(mlist)
        elif p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    order = sorted(range(len(devices)), key=lambda i: devices[i]["plane"])
    devices = [devices[i] for i in order]
    modules = [modules[i] for i in order]
    if n_devices is not None:
        devices, modules = devices[:n_devices], modules[:n_devices]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        window = (win[-1][1], win[-1][1] + win[-1][2])
    elif not any(len(d["start"]) for d in devices):
        window = (0.0, 0.0)
    else:
        lo = min(float(d["start"].min()) for d in devices if len(d["start"]))
        hi = max(float((d["start"] + d["dur"]).max()) for d in devices
                 if len(d["start"]))
        window = (lo, hi)
    return Trace(devices, modules, spans, window)
