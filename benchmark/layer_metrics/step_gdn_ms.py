"""Device time of one step inside the Gated DeltaNet layers (`.../gdn`):
the projections, the convolution with the gates and the normalisations,
the chunked scan and the gated norm with the output projection, forward,
backward and recomputed."""

from benchmark import xing4_scopes as X

PART = X.component("gdn")


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    return None if s is None else 1e3 * s
