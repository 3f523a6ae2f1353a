"""The flash kernels' share of their roofline in the training window:
sum over the forward and backward calls of max(FLOPs / peak, bytes /
HBM bandwidth), over the sum of their device time.  FLOPs and bytes come
from chipbench/flops.py for the cell's shapes (every call of a round
runs at rows = clients x batch, length seq_len; each forward and backward
runs every layer once, so a call takes each of the family's attention
shapes in the share of the layers that have it, with the mask's kept
pairs averaged over those layers).  The bound that applies is printed on
standard error."""

from chipbench import flops, programs
from chipbench.harness import log


def read(ctx):
    tr, c, dims, pk = ctx["trace"], ctx["counters"], ctx["dims"], \
        ctx["peaks"]
    t_f, n_f = tr.op_seconds(programs.is_flash_fwd)
    t_b, n_b = tr.op_seconds(programs.is_flash_bwd)
    if n_f + n_b == 0 or t_f + t_b <= 0:
        return None
    rows = c["rows"] // ctx["chips"]             # per chip's call
    need, bound = 0.0, {}
    for (heads, _, qk, v), windows in flops.attn_groups(dims).items():
        pairs = flops.mean_kept_pairs(c["seq_len"], windows)
        args = (rows, c["seq_len"], heads, qk, v, pairs)
        share = len(windows) / len(dims["layer"])
        for (fl, by), n in ((flops.flash_fwd(*args), n_f),
                            (flops.flash_bwd(*args), n_b)):
            t, which = flops.roofline_seconds(fl, by, pk["bf16_flops_per_s"],
                                              pk["hbm_bytes_per_s"])
            need += n * share * t
            bound[which] = bound.get(which, 0) + n * share
    log(f"flash_roofline.train: {n_f:.0f} forward and {n_b:.0f} backward "
        f"calls, {t_f + t_b:.4f} s; bound by {bound}")
    return 100.0 * need / (t_f + t_b)
