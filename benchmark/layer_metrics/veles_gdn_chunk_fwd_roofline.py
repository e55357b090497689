"""Share of the chip's roofline that `veles_gdn_chunk_fwd` reaches (the forward of the chunked Gated DeltaNet's operand stage: the decay matrix, the chunk's inverse and what the chain and the outputs read of them, every (C, C) matrix in VMEM;
`qwen3next_gdn_count.gdn_kernel_roofline`): the least time the chip's
peaks allow for the stage's work (its interface's bytes, unpadded, at the
HBM rate: longer than its matrix operations at the bf16 peak) over the
kernel's own device time, the calls counted from the trace. Small
dependent products bound the kernel, not bandwidth; it cannot pass 100."""

from benchmark import qwen3next_gdn_count as G


def read(ctx):
    return G.gdn_kernel_roofline(ctx, "veles_gdn_chunk_fwd")
