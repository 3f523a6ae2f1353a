"""Host-clock time of a `step()` that admitted nothing (a decode tick
alone), mean over the window's such steps."""


def read(ctx):
    s = ctx["counters"].get("idle_steps_s")
    return 1e3 * sum(s) / len(s) if s else None
