"""Seconds from the first request for a batch to the opening of the
window: scoring, knapsack, compilation or cache loads, and the warm steps
(the generator's timestamps)."""


def read(ctx):
    return ctx["warmup_s"]
