"""Host self time of the program's set-up spans (`splitft.setup.*`:
corpus and tokenization, partition, loaders, parameter and state init,
building the jitted steps) from process start to the opening of the
measured window.  Self time leaves out the child spans, so the compiles
nested in set-up count in `setup_compile_s.train` alone and the two
split `setup_s` without overlap.  Standard error gets the parts by span
name."""

from chipbench import program_spans
from chipbench.harness import log


def _setup(name):
    return name.startswith("splitft.setup.")


def read(ctx):
    found = program_spans.setup_records(ctx)
    if found is None:
        return None
    parts = program_spans.self_seconds(found[0], _setup)
    if not parts:
        return None
    log("setup_host_s.train: host self time: " + ", ".join(
        f"{n} {t:.3f} s" for n, t in parts.most_common()))
    return sum(parts.values())
