"""Seconds the loop spent inside the feed's put call, over the batches
the feed produced (`veles_feed_put_seconds_total` over
`veles_feed_batches_total`). Counted from process start, set-up's eleven
batches among some 240."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.registry_ratio(
        "veles_feed_put_seconds_total", ("veles_feed_batches_total",), 1e3)
