"""Device time of one step in what the selection costs beside the
attention it saves: the indexer's projections and index scores
(`.../dsa/indexer`), the search for every query's threshold
(`.../select`) and the index loss (`.../index_loss`), forward, backward
and recomputed."""

from benchmark import xing4_scopes as X

PART = X.component("indexer", "select", "index_loss")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
