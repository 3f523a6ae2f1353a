"""Device time per prefill call in the serving window: the runs of the
engine's jitted prefill (`_prefill_raw`) module, over their count."""

from chipbench import programs


def read(ctx):
    t, n = ctx["trace"].module_seconds(
        programs.module_named(programs.PREFILL_MODULE))
    return 1e3 * t / n if n else None
