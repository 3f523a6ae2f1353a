"""Device time of the C3 evaluation step per round: the runs of its XLA
module, `jit_c3_eval_step`, inside the window, over the rounds."""

from chipbench.programs import split_step_modules


def read(ctx):
    _, evals = split_step_modules(ctx["trace"])
    rounds = ctx["counters"].get("rounds")
    if evals is None or not rounds:
        return None
    return 1e3 * evals / rounds
