"""The paged decode-attention kernel's share of its roofline in the
serving window: for every token decoded in the window, max(FLOPs / peak,
bytes / HBM bandwidth) of attending over its cached positions in every
layer (chipbench/flops.decode_attention at each of the family's attention
shapes; idle slots count nothing), summed, over the kernel's device
time."""

from chipbench import flops, programs
from chipbench.harness import log


def read(ctx):
    tr, c, dims, pk = ctx["trace"], ctx["counters"], ctx["dims"], \
        ctx["peaks"]
    t, n = tr.op_seconds(programs.is_kernel(programs.DECODE_PAGED))
    if n == 0 or t <= 0 or not c.get("attended"):
        return None
    need, bound = 0.0, {}
    for att in c["attended"]:
        for (heads, kv, qk, v), windows in flops.attn_groups(dims).items():
            fl, by = flops.decode_attention(att, heads, kv, qk, v)
            s, which = flops.roofline_seconds(fl, by, pk["bf16_flops_per_s"],
                                              pk["hbm_bytes_per_s"])
            need += s * len(windows)
            bound[which] = bound.get(which, 0) + 1
    log(f"decode_attn_roofline.serve: {n:.0f} kernel calls, {t:.4f} s; "
        f"bound by {bound}")
    return 100.0 * need / t
