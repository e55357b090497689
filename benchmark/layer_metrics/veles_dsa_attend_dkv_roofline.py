"""Share of the chip's bf16 peak that `veles_dsa_attend_dkv` reaches (the keys' and values' gradients: scores, P^T dO, dO V^T, dS^T Q;
`keye2_scopes.kernel_roofline`): the operations it executes, masked pairs
among them, over its device time. Compute bounds it; it cannot pass 100."""

from benchmark import keye2_scopes as K


def read(ctx):
    return K.kernel_roofline(ctx, "veles_dsa_attend_dkv")
