"""Device milliseconds per step of the Pallas kernels (the gated attention
kernels, the only Pallas kernels these configurations run), averaged over
the chips; nothing where the trace holds no kernel."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    devs = tr["devices"].values()
    per_chip = sum(d["kernel_s"] for d in devs) / len(devs)
    if per_chip <= 0:
        return None
    return per_chip / ctx["steps"] * 1e3
