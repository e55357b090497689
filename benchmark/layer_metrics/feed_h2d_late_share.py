"""Share of the batches the loop popped whose arrays had not yet reached
the device (`veles_feed_h2d_late_total` over late + ready; `is_ready()`,
asked without blocking). Counted from process start, set-up's eleven
batches among some 240."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.registry_ratio(
        "veles_feed_h2d_late_total",
        ("veles_feed_h2d_late_total", "veles_feed_h2d_ready_total"), 100.0)
