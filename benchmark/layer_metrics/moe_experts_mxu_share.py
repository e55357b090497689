"""Share of the chip's bf16 peak that the grouped products of the held
experts reach: the operations of the three products for the slots the
program COUNTED in the window (`veles_moe_held_slots_total` a step;
the REQUIRED operations, as `step_mxu_share` counts them: the forward
and the backward's two products, 3 forwards' worth,
`xing4_ops_count.grouped_flops`; what `jax.checkpoint` and the expert
path's own backward recompute is time and no work) over the device time
of the operations under `.../moe/experts` x `peaks.json`. The scope also
holds the sort, the gathers and the masks: it is the expert path's
share of the peak whatever lowers its products; compute bounds them."""

from benchmark import ops_count, xing4_ops_count
from benchmark import xing4_scopes as X

PART = X.component("experts")
#: forward, and the backward's products by the input and by the weight
PASSES = 3


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    layers = X.moe_counters()
    if not s or not layers or not all(c["steps"] for c in layers.values()):
        return None
    slots_per_step = sum(c["held"] / c["steps"] for c in layers.values())
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        xing4_ops_count.grouped_flops(ctx["cell"]["config_data"],
                                      slots_per_step, PASSES),
        s, peak["bf16_flops_per_s"])
