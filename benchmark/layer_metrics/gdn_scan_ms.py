"""Device time of one step inside the Gated DeltaNet layers' chunked scan
(`.../gdn/scan`): the products inside the chunks, the inverse, the chain
along the sequence and its backward, the chunks' outputs; forward,
backward and recomputed."""

from benchmark import qwen3next_scopes as Q


def read(ctx):
    s = Q.scan_seconds(ctx)
    return None if s is None else 1e3 * s
