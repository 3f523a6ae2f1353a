"""Device time of the collective ops (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) per round, mean over the
chips: the FedAvg and resharding exchanges of the sharded round."""

from chipbench import programs


def read(ctx):
    t, n = ctx["trace"].op_seconds(programs.is_collective)
    rounds = ctx["counters"].get("rounds")
    if not n or not rounds:
        return None
    return 1e3 * t / rounds
