"""Device time of one step inside the `norm` and `max_pooling` units,
forward and backward. A fusion that spans both counts once: the time is
the union of the operations' intervals."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    return None if r is None else 1e3 * r["norm_pool_s"]
