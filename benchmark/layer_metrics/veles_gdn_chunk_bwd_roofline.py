"""Share of the chip's roofline that `veles_gdn_chunk_bwd` reaches (the backward of the chunked Gated DeltaNet's operand stage: the inverse and the decay matrix formed again, d A = -T^T (d T) T^T inside VMEM, six cotangents turned into five gradients;
`qwen3next_gdn_count.gdn_kernel_roofline`): the least time the chip's
peaks allow for the stage's work (inputs, cotangents and gradients,
unpadded, at the HBM rate: longer than its matrix operations at the bf16
peak) over the kernel's own device time, the calls counted from the trace.
Small dependent products bound the kernel; it cannot pass 100."""

from benchmark import qwen3next_gdn_count as G


def read(ctx):
    return G.gdn_kernel_roofline(ctx, "veles_gdn_chunk_bwd")
