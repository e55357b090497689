"""Device time of one step inside the optimizer update: operations under
the step's `update` scope, collectives excluded (they are the
collectives layer's)."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    return None if r is None else 1e3 * r["phase_s"]["update"]
