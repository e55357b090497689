"""Share of the chip's memory roofline that `veles_seg_sum` reaches (the
held experts' combine and, in the backward, the transpose of the rows'
gather: every token's sum of its held rows of the sorted buffer;
`moe_seg_sum_count.seg_sum_roofline`): the bytes the sum cannot avoid (the
held rows the program counted, read; a row a token, written) at the HBM
rate over the kernel's own device time, the calls counted from the trace.
Nothing where the step gathers a row a (token, slot) pair instead; it
cannot pass 100."""

from benchmark import moe_seg_sum_count as S


def read(ctx):
    return S.seg_sum_roofline(ctx)
