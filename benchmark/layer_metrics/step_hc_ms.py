"""Device time of one step inside the hyper-connections (`.../hc_pre`
and `.../hc_post`): the three maps, the Sinkhorn iterations and the
mixing of the residual streams, forward, backward and recomputed."""

from benchmark import xing4_scopes as X

PART = X.component("hc_pre", "hc_post")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
