"""Device time of the C3 evaluation step (`eval_step`) per round: the
runs of the smaller `jit_step` module inside the window."""

from chipbench.programs import split_step_modules


def read(ctx):
    _, evals = split_step_modules(ctx["trace"])
    rounds = ctx["counters"].get("rounds")
    if evals is None or not rounds:
        return None
    return 1e3 * evals / rounds
