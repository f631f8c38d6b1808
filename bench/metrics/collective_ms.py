"""Device milliseconds per step of all-gather, reduce-scatter, all-reduce
and collective-permute ops on the chip that spends most on them; nothing
where the trace holds none (one chip)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    worst = max(d["collective_s"] for d in tr["devices"].values())
    if worst <= 0:
        return None
    return worst / ctx["steps"] * 1e3
