"""Share of the chip's bf16 peak that `veles_flash_fwd` reaches (the forward of latent attention's core: scores and values over every pair of the tiles it visits;
`xing4_flash_count.flash_kernel_roofline`): the operations its calls
execute, pairs above the diagonal inside a visited tile among them, over
their device time, the calls counted from the trace. Compute bounds it;
it cannot pass 100."""

from benchmark import xing4_flash_count as F


def read(ctx):
    return F.flash_kernel_roofline(ctx, "veles_flash_fwd")
