"""Share of the chip's bf16 peak that `veles_dsa_index_fwd` reaches (the indexer's scores of every causal tile, all index heads in VMEM: for the selection, for the index loss and again for its gradient;
`keye2_index_count.index_kernel_roofline`): the operations it executes
over its device time. Compute bounds it, at a contraction of 64 that
half-fills the array: near 50 at best; it cannot pass 100."""

from benchmark import keye2_index_count as K


def read(ctx):
    return K.index_kernel_roofline(ctx, "veles_dsa_index_fwd")
