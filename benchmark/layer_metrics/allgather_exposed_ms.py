"""Time per step inside `all-gather` operations on device 0 with no
non-collective operation running there: the parameter gather's part of
`collective_exposed_ms`. Nothing to read on one chip."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    if r is None or ctx["counters"]["chips"] < 2:
        return None
    return 1e3 * r["allgather_exposed_s"]
