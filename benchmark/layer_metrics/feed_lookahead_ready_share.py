"""Share of the loader's fills whose lookahead future was done when the
loop asked for it (`veles_loader_lookahead_ready_total` over ready +
waited). Counted from process start, set-up's eleven batches among some
240 (the first fill of a run has no lookahead and counts as waited)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.registry_ratio(
        "veles_loader_lookahead_ready_total",
        ("veles_loader_lookahead_ready_total",
         "veles_loader_lookahead_waited_total"), 100.0)
