"""Host self time per round of the program's round-engine spans
(`splitft.round` and `splitft.round.*`: plan, batch assembly, dispatch,
record) inside the traced segment.  Self time is a span's duration
minus what its child spans cover, so the host's waits on the device
(`splitft.wait.*`) and the C3 subtree (`splitft.c3`) are left out."""

from chipbench import program_spans


def _round(name):
    return name == "splitft.round" or name.startswith("splitft.round.")


def read(ctx):
    return program_spans.self_ms_per_round(ctx, "round_host_ms.train",
                                           _round)
