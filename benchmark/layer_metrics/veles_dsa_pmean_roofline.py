"""Share of the chip's bf16 peak that `veles_dsa_pmean` reaches (the mean-head probabilities the index loss reads: scores again, from the saved logsumexps, forward and for the gradient;
`keye2_scopes.kernel_roofline`): the operations it executes, masked pairs
among them, over its device time. Compute bounds it; it cannot pass 100."""

from benchmark import keye2_scopes as K


def read(ctx):
    return K.kernel_roofline(ctx, "veles_dsa_pmean")
