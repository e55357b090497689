"""Seconds the loader's produce threads spent producing, over the batches
they produced (`veles_loader_produce_seconds_total` over
`veles_loader_batches_produced_total`): one batch's gather on one
worker. Counted from process start, set-up's eleven batches among some
240."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.registry_ratio(
        "veles_loader_produce_seconds_total",
        ("veles_loader_batches_produced_total",), 1e3)
