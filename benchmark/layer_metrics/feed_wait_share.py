"""Share of the window the loop spent blocked in the feed: the growth of
`feed.stats()`'s `loader_block_s` + `put_block_s` over the window, over
the window. Nothing to read where the traffic has no feed."""


def read(ctx):
    c = ctx["counters"]
    if "feed_wait_s" not in c:
        return None
    return 100.0 * c["feed_wait_s"] / c["window_s"]
