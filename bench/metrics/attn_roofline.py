"""Share of the attention kernels' roofline, in percent: the least time
the chip needs for the attention work the schedule requires (live slices,
real sequence length, causal as the lower triangle), the larger of FLOPs
over peak FLOP/s and bytes over peak HBM bandwidth, over the kernels'
device time. Work and time are per step and per chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    devs = tr["devices"].values()
    kernel_s = sum(d["kernel_s"] for d in devs) / len(devs) / ctx["steps"]
    if kernel_s <= 0:
        return None
    pk, n = ctx["peaks"], ctx["n_chips"]
    least = max(ctx["attn_flops"] / n / pk["flops"],
                ctx["attn_bytes"] / n / pk["hbm_bw"])
    return 100.0 * least / kernel_s
