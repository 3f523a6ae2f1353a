"""Host self time per round of the program's C3 spans (`splitft.c3` and
`splitft.c3.*`: evaluation batch, dispatch, the rule with its new cuts)
inside the traced segment; the wait for the evaluation's accuracies
(`splitft.wait.c3`) is left out."""

from chipbench import program_spans


def _c3(name):
    return name == "splitft.c3" or name.startswith("splitft.c3.")


def read(ctx):
    return program_spans.self_ms_per_round(ctx, "c3_host_ms.train", _c3)
