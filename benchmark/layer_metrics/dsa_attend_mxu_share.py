"""Share of the chip's bf16 peak that the main attention reaches: the
REQUIRED operations of scores and values over the pairs the program
COUNTED selected in the window (`veles_dsa_pairs_selected_total` a step;
4 x heads x head size a pair, forward and the backward's two products: 3
forwards' worth, `keye2_ops_count.attend_flops`; what is recomputed, and
every score of a pair that was not selected, is time and no work) over
the device time of the operations under `.../attend` x `peaks.json`. The
scope also holds the softmax, the masks and the output projection: it is
the attention's share of the peak whatever lowers it, and it cannot pass
100."""

from benchmark import keye2_ops_count, ops_count
from benchmark import keye2_scopes as K
from benchmark import xing4_scopes as X

PART = X.component("attend")
#: forward, and the backward's products by either operand
PASSES = 3


def read(ctx):
    s = X.scope_seconds(ctx, PART)
    layers = K.dsa_counters()
    if not s or not layers or not all(c["steps"] for c in layers.values()):
        return None
    pairs_per_step = sum(c["selected"] / c["steps"]
                         for c in layers.values())
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        keye2_ops_count.attend_flops(ctx["cell"]["config_data"],
                                     pairs_per_step, PASSES),
        s, peak["bf16_flops_per_s"])
