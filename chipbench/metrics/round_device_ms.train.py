"""Device time of the jitted round step per round: the runs of its XLA
module, `jit_round_step`, inside the window, over the rounds."""

from chipbench.programs import split_step_modules


def read(ctx):
    train, _ = split_step_modules(ctx["trace"])
    rounds = ctx["counters"].get("rounds")
    if train is None or not rounds:
        return None
    return 1e3 * train / rounds
