"""Device time of one step inside latent attention (`.../mla`): the
down- and up-projections with their norms, the rotary part, scores,
softmax and the output projection, forward, backward and recomputed."""

from benchmark import xing4_scopes as X

PART = X.component("mla")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
