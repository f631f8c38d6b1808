"""Model FLOP utilization, in percent: the FLOPs the schedule requires per
step (``flops.required_step_flops``) times the steps, over the time they
took, the chips and each chip's peak. Steps and time are those of the
window's untraced part (a traced run's, after its traced span), since the
profiler slows the host work between steps; nothing where that part holds
no step."""


def read(ctx):
    if ctx["untraced_steps"] <= 0:
        return None
    used = ctx["step_flops"] * ctx["untraced_steps"]
    return 100.0 * used / (ctx["untraced_s"] * ctx["n_chips"]
                           * ctx["peaks"]["flops"])
