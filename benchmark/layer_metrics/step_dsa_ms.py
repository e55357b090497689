"""Device time of one step inside the indexed attention (`.../dsa`): the
projections with their norms and rotary embedding, the indexer, the
selection, scores, softmax, values, the output projection and the index
loss, forward, backward and recomputed."""

from benchmark import xing4_scopes as X

PART = X.component("dsa")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
