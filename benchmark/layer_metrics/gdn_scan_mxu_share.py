"""Share of the chip's bf16 peak that the Gated DeltaNet layers' scan
reaches: the REQUIRED operations of the recurrence (6 dk dv a token and
value head, forward and the backward's two: 3 forwards' worth,
`qwen3next_ops_count.gdn_scan_flops`; what the chunked form does beside
them, and what is recomputed, is time and no work) over the device time of
the operations under `.../gdn/scan` x `peaks.json`. Little arithmetic in a
long chain: latency bounds it, and it cannot pass 100."""

from benchmark import ops_count, qwen3next_ops_count
from benchmark import qwen3next_scopes as Q


def read(ctx):
    s = Q.scan_seconds(ctx)
    if not s:
        return None
    cfg = ctx["cell"]["config_data"]
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        qwen3next_ops_count.gdn_scan_flops(cfg, cfg["batch_per_chip"]),
        s, peak["bf16_flops_per_s"])
