"""Device time of one step inside the expert layers (`.../moe`): the
router, the sort of the held (token, slot) pairs, the grouped products,
the weighted scatter back and the shared expert, forward, backward and
recomputed. The grouped products are placed here BY NAME: the TPU
compiler rewrites a `lax.ragged_dot` into custom calls whose metadata is
`op_name="ragged-dot-none"` and nothing else (`xing4_scopes._placed`), so
`step_unscoped_share` counts them as work without a scope."""

from benchmark import xing4_scopes as X

PART = X.component("moe")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
