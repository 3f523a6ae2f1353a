"""What every cell shares: the cell's files and model family found by
name, the device guard, the compile cache, host spans, and the per-layer
metric readers."""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, bench=None):
    """(workload entry, config file, traffic file, BENCHMARK.json)."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, traffic, bench


def load_family(model_type, path=()):
    """The family module of `model_type` (chipbench/families/__init__.py
    says what it gives): <model_type>.py in the directories of `path`
    (tests only), then in chipbench/families/."""
    tried = [pathlib.Path(d) / f"{model_type}.py"
             for d in (*path, HERE / "families")]
    for f in tried:
        if f.exists():
            return _load_module(f, f"chipbench_family_{model_type}")
    raise BenchError(f"no family for model_type {model_type!r}: "
                     f"{', '.join(_shown(f) for f in tried)}")


def model_dims(cfg, path=()):
    """The family's sizes of the configuration file, with the family
    module itself under "family"."""
    fam = load_family(cfg["model_type"], path)
    return dict(fam.dims(cfg), family=fam)


def check_program_arch(dims, lora, arch):
    """Raise unless the program's registry entry has the file's sizes."""
    want = dict(dims["family"].program_sizes(arch),
                r_others=arch.lora.r_others, r_cut=arch.lora.r_cut,
                alpha=arch.lora.alpha, cut_layer=arch.split.cut_layer)
    have = dict(dims, **{k: lora[k] for k in
                         ("r_others", "r_cut", "alpha", "cut_layer")})
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad:
        raise BenchError(f"configuration file and program disagree "
                         f"(file, program): {bad}")


def program_seed(seed):
    """--seed may exceed 32 bits; the program's PRNG keys take 31."""
    return int(seed) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# device


def device_info(chips, *, require_tpu=True):
    """The device JAX sees; a BenchError where it is not the chip the cell
    asks for (never a fall back to the CPU)."""
    if require_tpu and os.environ.get("REPRO_PALLAS_INTERPRET"):
        raise BenchError("REPRO_PALLAS_INTERPRET is set: the kernels would "
                         "run in interpret mode, not on the chip")
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {dev.platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peaks_for(kind, *, require=True):
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        if require:
            raise BenchError(f"device kind {kind!r} is not in peaks.json")
        return None
    return table[kind]


def memory_peak_bytes(chips):
    """The peak on the fullest chip: what the allocator held at its peak,
    in use or reserved for a program's temporaries."""
    import jax
    best = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        best = max(best, st.get("peak_bytes_in_use", 0),
                   st.get("peak_bytes_reserved", 0))
    return best or None


def use_compile_cache():
    """JAX's persistent cache at <checkout>/.jax_cache, or where
    JAX_COMPILATION_CACHE_DIR points (then JAX reads it itself)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# host spans


class Spans:
    """Host spans kept in memory, and mirrored into the profiler's trace
    as TraceAnnotations while a trace is being taken."""

    def __init__(self, annotate=False):
        self.annotate = annotate
        self.records = []          # (name, start_s, end_s, extra)

    def span(self, name, **extra):
        return _Span(self, name, extra)


class _Span:
    def __init__(self, owner, name, extra):
        self.owner, self.name, self.extra = owner, name, extra
        self.ann = None

    def __enter__(self):
        if self.owner.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.records.append((self.name, self.t0, t1, self.extra))
        return False


class TracedSegment:
    """The part of the measured window that the profiler sees: from
    `start_s` after the window opens, for `seconds` (the traffic file's
    "trace" entry).  A long trace stalls the host while the profiler
    drains its buffers, so the segment is kept short.  Drivers call
    `poll(elapsed)` where starting or stopping is clean and `end()` when
    the window closes; t0 and t1 are its host times."""

    def __init__(self, ctx):
        spec = ctx["traffic"].get("trace", {"start_s": 0.0,
                                             "seconds": ctx["seconds"]})
        self.ctx = ctx
        self.start_s = min(float(spec["start_s"]), ctx["seconds"] / 2)
        self.seconds = min(float(spec["seconds"]),
                           max(ctx["seconds"] - self.start_s, 0.0))
        self.enabled = bool(ctx["trace"])
        self.on = False
        self.t0 = self.t1 = None
        self.dir = None
        self._span = None

    def poll(self, elapsed):
        if not self.enabled:
            return
        if self.t0 is None and elapsed >= self.start_s:
            self.dir = self.ctx["start_trace"]()
            self._span = self.ctx["spans"].span("bench.traced").__enter__()
            self.t0, self.on = time.perf_counter(), True
        elif self.on and elapsed >= self.start_s + self.seconds:
            self.end()

    def end(self):
        if self.on:
            self._span.__exit__(None, None, None)
            self.t1, self.on = time.perf_counter(), False
            self.ctx["stop_trace"]()

    def covers(self, t):
        """Whether host time t lies in the traced segment."""
        return self.t0 is not None and self.t1 is not None and \
            self.t0 <= t <= self.t1

    @property
    def length(self):
        return (self.t1 - self.t0) if self.t1 is not None else 0.0


# ---------------------------------------------------------------------------
# per-layer metric readers


def metric_reader(name):
    """chipbench/metrics/<name>.py's `read(ctx)`."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader for per-layer metric {name!r}: "
                         f"{_shown(path)}")
    return _load_module(
        path, f"chipbench_metric_{name.replace('.', '_')}").read


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shown(path):
    path = pathlib.Path(path).resolve()
    return str(path.relative_to(ROOT)) if path.is_relative_to(ROOT) \
        else str(path)


def metrics_for(cell, bench, kind):
    """The metric entries of `kind` ('end_to_end' or 'per_layer') that
    this cell reports."""
    out = []
    for m in bench[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        out.append(m)
    return out


def log(*args):
    print(*args, file=sys.stderr, flush=True)
