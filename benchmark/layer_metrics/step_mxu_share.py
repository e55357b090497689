"""Share of the chip's bf16 peak that the step's conv and matmul work
needs: operations of one step on one chip (ops_count, from the
configuration's shapes) over the step's device time x peaks.json."""

from benchmark import ops_count


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        ctx["counters"]["flops_per_step"], t["step_device_s"],
        peak["bf16_flops_per_s"])
