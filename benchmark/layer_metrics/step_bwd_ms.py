"""Device time of one step inside backward operations: those autodiff
scoped `transpose(jvp(<scope>))`."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    return None if r is None else 1e3 * r["phase_s"]["backward"]
