"""The whole round's share of the chip's bf16 peak: model FLOPs of the
real tokens trained in the window (chipbench/flops.train_flops), over
window x chips x peak."""


def read(ctx):
    fl = ctx["counters"].get("model_flops")
    if not fl:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * fl / (ctx["window_s"] * ctx["chips"] * peak)
