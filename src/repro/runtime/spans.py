"""Host spans and counters of the program, on the profiler's clock.

`span(name, **ids)` times a block with `time.perf_counter()` and keeps
one record per span: (name, start, end, extra), where `extra` holds the
span's `ids`, its own `id` and the `id` of the span that was open when
it began (`parent`, None at the top).  A span inherits the ids of its
parent, so every span of one round carries that round's `round=r`.
Each span also enters a `jax.profiler.TraceAnnotation` of its name, so
while a profiler trace is being taken it lands on the trace's host plane,
on the same clock as the device lines; with no trace running that costs
well under a microsecond.  The profiler trace is the only export.

On import one listener of `jax.monitoring` turns JAX's compile events
(tracing to a jaxpr, lowering to MLIR, the backend compile, which
already includes a persistent-cache read) into `compile.*` records that
end when the event fires, with the innermost open span as parent and
JAX's `fun_name` among their ids; persistent-cache hits are counted
under `compile.cache_hits`.  A recompile in a steady loop then shows as
a `compile.*` record under the span that triggered it.

Records live in a bounded deque (`MAX_RECORDS`), so a long job cannot
grow them without limit; `records()`, `counters()` and `reset()` read
and clear them.  Spans nest on one stack: the program records them from
the thread that drives it.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

MAX_RECORDS = 1 << 16

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "compile.jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "compile.backend_compile",
}
CACHE_HITS_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_HITS = "compile.cache_hits"


class Record(NamedTuple):
    """One span, laid out as the benchmark harness's host spans are:
    (name, start_s, end_s, extra) on `time.perf_counter()`."""
    name: str
    start: float
    end: float
    extra: Dict[str, Any]        # ids, plus "id" and "parent"


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_counters: collections.Counter = collections.Counter()
_open: List["_Span"] = []        # open spans, innermost last
_next_id = itertools.count()


class _Span:
    __slots__ = ("name", "ids", "id", "parent", "start", "_ann")

    def __init__(self, name: str, ids: Dict[str, Any]):
        self.name = name
        self.ids = ids

    def __enter__(self):
        outer = _open[-1] if _open else None
        if outer is not None and outer.ids:
            self.ids = {**outer.ids, **self.ids}
        self.parent = outer.id if outer is not None else None
        self.id = next(_next_id)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        _open.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _open.pop()
        self._ann.__exit__(*exc)
        _records.append(Record(self.name, self.start, end,
                               dict(self.ids, id=self.id,
                                    parent=self.parent)))
        return False


def span(name: str, **ids) -> _Span:
    """Context manager: one record for the block it wraps."""
    return _Span(name, ids)


def count(name: str, n: int = 1):
    """Add n to the per-name total `name`."""
    _counters[name] += n


def records() -> List[Record]:
    """The records kept, oldest first (a copy)."""
    return list(_records)


def counters() -> Dict[str, int]:
    return dict(_counters)


def reset():
    """Drop every record and counter (open spans stay open)."""
    _records.clear()
    _counters.clear()


def _on_duration(event: str, duration: float, **kwargs):
    name = COMPILE_EVENTS.get(event)
    if name is None:
        return
    end = time.perf_counter()
    outer: Optional[_Span] = _open[-1] if _open else None
    extra = dict(outer.ids) if outer is not None else {}
    extra.update(fun_name=kwargs.get("fun_name"), id=next(_next_id),
                 parent=outer.id if outer is not None else None)
    _records.append(Record(name, end - duration, end, extra))


def _on_event(event: str, **kwargs):
    if event == CACHE_HITS_EVENT:
        count(CACHE_HITS)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
