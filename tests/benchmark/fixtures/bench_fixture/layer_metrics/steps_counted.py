"""A per-layer metric a fixture adds as a file: the steps the window counted."""


def read(ctx):
    return float(ctx["counters"]["steps"])
