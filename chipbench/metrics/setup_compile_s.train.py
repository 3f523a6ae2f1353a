"""Seconds spent compiling before the measured window opened: the union
of the `compile.*` spans the program's listener recorded from JAX's
compile events (tracing, lowering, backend compile with its
persistent-cache read), the harness's compiles included, since both are
part of `setup_s`.  Standard error gets the parts by event and by the
program span that triggered them (round step, C3 step, init, or outside
the program), and the persistent-cache hits."""

import collections

from chipbench import program_spans
from chipbench.harness import log


def read(ctx):
    found = program_spans.setup_records(ctx)
    if found is None:
        return None
    recs, names = found
    by_parent = collections.defaultdict(list)
    by_kind = collections.defaultdict(list)
    for name, t0, t1, extra in recs:
        if name.startswith("compile."):
            parent = names.get(extra["parent"], program_spans.OUTSIDE)
            by_parent[parent].append((t0, t1))
            by_kind[name].append((t0, t1))
    if not by_kind:
        return None
    hits = program_spans.recorder().counters().get("compile.cache_hits", 0)

    def show(groups):
        tot = {k: program_spans.union_length(v) for k, v in groups.items()}
        return ", ".join(f"{k} {t:.3f} s ({len(groups[k])})" for k, t in
                         sorted(tot.items(), key=lambda kv: -kv[1]))

    log(f"setup_compile_s.train: by event: {show(by_kind)}; by span: "
        f"{show(by_parent)}; persistent-cache hits (whole run) {hits}")
    return program_spans.union_length(
        iv for v in by_kind.values() for iv in v)
