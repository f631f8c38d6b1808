"""Milliseconds per step of collective time during which no other op runs,
on the chip that spends most on collectives; nothing where the trace
holds no collective."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busiest = max(tr["devices"].values(), key=lambda d: d["collective_s"])
    if busiest["collective_s"] <= 0:
        return None
    return busiest["exposed_collective_s"] / ctx["steps"] * 1e3
