"""The serving window's share of the chip's bf16 peak: model FLOPs of
every prompt prefilled and token decoded in the window
(chipbench/flops.prefill_flops and decode_flops), over window x chips x
peak."""


def read(ctx):
    fl = ctx["counters"].get("model_flops")
    if not fl:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * fl / (ctx["window_s"] * ctx["chips"] * peak)
