"""95th percentile of the per-batch `loader_block_s` of the batches the
window consumed, in milliseconds."""

import numpy as np


def read(ctx):
    waits = ctx["counters"].get("feed_block_ms")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 95))
