"""Device time of one step inside forward operations: those whose scope
path names a program scope (`L<nn>.<type>`, `input_normalize`,
`cast_params`, `loss`) with no `transpose(` and no `update` in it."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    return None if r is None else 1e3 * r["phase_s"]["forward"]
