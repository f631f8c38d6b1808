"""Share of a step in which no operation runs on the device, in percent:
one less the device's busy time per step, from the trace (averaged over
the chips), over the wall time per step of the window's untraced part.
The profiler slows the host work between steps but not the device's, so
the trace's own span would overstate the idle share. Nothing without a
trace or an untraced step."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["untraced_steps"] <= 0:
        return None
    devs = tr["devices"].values()
    busy_per_step = sum(d["busy_s"] for d in devs) / len(devs) / ctx["steps"]
    step_s = ctx["untraced_s"] / ctx["untraced_steps"]
    return 100.0 * (1.0 - busy_per_step / step_s)
