"""The fullest held expert's slots over the even share of the slots
(slots / experts), mean over the window's steps, of the worst expert
layer (`veles_moe_fullest_held_slots_total`): 1 at perfect balance; the
grouped products wait for the fullest group where experts lie on
different chips."""

from benchmark import xing4_scopes as X


def read(ctx):
    layers = X.moe_counters()
    if not layers or not all(c["slots"] for c in layers.values()):
        return None
    _held, experts = X.held_experts_of(ctx)
    return max(c["fullest"] / (c["slots"] / experts)
               for c in layers.values())
