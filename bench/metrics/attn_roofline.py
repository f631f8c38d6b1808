"""Share of the attention kernels' roofline, in percent: the least time
the chip needs for the attention work the schedule requires (live slices,
real sequence length, causal as the lower triangle), the larger of FLOPs
over peak FLOP/s and bytes over peak HBM bandwidth, over the attention
kernels' device time (``attn_kernel_ms``). Work and time are per step and
per chip."""
from bench.metrics.attn_kernel_ms import read as attn_kernel_ms


def read(ctx):
    ms = attn_kernel_ms(ctx)
    if ms is None:
        return None
    pk, n = ctx["peaks"], ctx["n_chips"]
    least = max(ctx["attn_flops"] / n / pk["flops"],
                ctx["attn_bytes"] / n / pk["hbm_bw"])
    return 100.0 * least / (ms * 1e-3)
