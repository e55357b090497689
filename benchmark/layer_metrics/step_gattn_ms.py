"""Device time of one step inside gated full attention (`.../attn`): the
query, gate, key and value projections, the QK-norms, the rotary part, the
core (the `veles_flash_*` kernels or the blocked XLA form), the output
gate and the output projection, forward, backward and recomputed.
(`step_attn_ms` reads latent attention's scope, `.../mla`.)"""

from benchmark import xing4_scopes as X

PART = X.component("attn")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
