"""Share of the chip's bf16 peak that `veles_gmm` reaches (the held experts' products by rows (forward, recomputed, and the rows' gradient);
`keye2_scopes.grouped_roofline`): its products over the slots the
program counted held, over its device time. Compute bounds it; the rows of
a tile that are no work keep it under 100."""

from benchmark import keye2_scopes as K


def read(ctx):
    return K.grouped_roofline(ctx, "veles_gmm")
