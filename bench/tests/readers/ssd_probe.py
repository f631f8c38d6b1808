"""A reader written as a later kernel's metrics will be: one file that
takes one named kernel's device milliseconds per step and
``flops.required_ssd``'s counts from the context, and gives a share of
that kernel's roofline in percent. In the test the recorded trace's
backward attention kernel stands in for an SSD kernel."""
KERNEL = "transpose_jvp_jit__gated_attention_impl___"


def read(ctx):
    ms = ctx["kernel_ms"].get(KERNEL)
    if not ms or not ctx["ssd_flops"]:
        return None
    pk, n = ctx["peaks"], ctx["n_chips"]
    least = max(ctx["ssd_flops"] / n / pk["flops"],
                ctx["ssd_bytes"] / n / pk["hbm_bw"])
    return 100.0 * least / (ms * 1e-3)
