"""Slots routed to the experts held here over all slots routed, in the
window, mean over the expert layers (`veles_moe_held_slots_total` over
`veles_moe_slots_total`): held / experts x 100 at balance, 12.5 for 8 of
64."""

from benchmark import xing4_scopes as X


def read(ctx):
    layers = X.moe_counters()
    if not layers or not all(c["slots"] for c in layers.values()):
        return None
    return 100.0 * sum(c["held"] / c["slots"]
                       for c in layers.values()) / len(layers)
