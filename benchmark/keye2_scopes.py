"""What the `keye2_lm` family's per-layer metrics read beside
`xing4_scopes.py`'s scopes: the indexed attention's pair counters and
the kernels' own device time.

The blocks name their parts beneath the unit's scope
(`L02.hc_block/dsa/indexer`, `veles_tpu/znicz/lm.py`; a block of queries
is the body of a `lax.map`, so a part's path runs `dsa/while/body/
closed_call/attend/...`, and `xing4_scopes.component` matches whole
components wherever they stand).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from benchmark import keye2_ops_count, ops_count
from benchmark import trace_reduce as T


def dsa_counters() -> Optional[Dict[str, Dict[str, float]]]:
    """{layer: {steps, causal, selected, scored}} of the window, (query,
    key) pairs from the program's `veles_dsa_*` counters; None where the
    program has none (a program from before them, or without such
    attention)."""
    try:
        from veles_tpu.telemetry import metrics
        values = metrics.family_values
    except (ImportError, AttributeError):
        return None
    out: Dict[str, Dict[str, float]] = {}
    for key, name in (("steps", "veles_dsa_steps_total"),
                      ("causal", "veles_dsa_pairs_causal_total"),
                      ("selected", "veles_dsa_pairs_selected_total"),
                      ("scored", "veles_dsa_pairs_scored_total")):
        family = values(name)
        if not family:
            return None
        for (layer,), v in family.items():
            out.setdefault(layer, {})[key] = v
    return out


def kernel_seconds(ctx, kernel: str) -> Optional[float]:
    """Device time a step of the traced run spent in the operations of the
    kernel named `kernel` on device 0. Nothing to read where the step runs
    no such kernel (another lowering, off a TPU, a run that was not
    traced)."""
    if ctx.get("trace") is None:
        return None
    from veles_tpu.caches import cache_path
    trace_dir = os.path.join(
        cache_path("benchmark", ctx["cell"]["name"]), "trace")
    try:
        rows = T.events_of(T.find_xplane(trace_dir))["devices"].get(0)
    except FileNotFoundError:
        return None
    base = rows and T.reduce_device(rows[T.OPS_LINE], rows[T.MODULES_LINE])
    if not base:
        return None
    # a trace names an operation by its HLO line, which starts with the
    # kernel's fixed name
    return sum(s for name, s in base["by_op"].items()
               if name.startswith("%" + kernel + ".")
               or name.startswith("%" + kernel + " ")) / base["steps"] \
        or None


def kernel_roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the chip's bf16 peak one `veles_dsa_*` kernel reaches:
    the operations it executes in a step (`keye2_ops_count.
    dsa_kernel_flops`) over its device time x `peaks.json`."""
    kernel_s = kernel_seconds(ctx, kernel)
    if not kernel_s:
        return None
    cfg = ctx["cell"]["config_data"]
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        keye2_ops_count.dsa_kernel_flops(cfg, kernel, cfg["batch_per_chip"]),
        kernel_s, peak["bf16_flops_per_s"])


def grouped_roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the chip's bf16 peak a grouped-product kernel of the held
    experts reaches (`veles_gmm`, `veles_tgmm`): its calls' products over
    the slots the program COUNTED held, a step of the window
    (`keye2_ops_count.grouped_kernel_flops`; the rows of a tile that are
    another group's or nobody's are time and no work, so it cannot pass
    100), over its device time x `peaks.json`. The counters are the
    window's, the time the traced steps': a held share that drifts inside
    the window moves the reading by as much."""
    from benchmark import xing4_scopes as X
    kernel_s = kernel_seconds(ctx, kernel)
    layers = X.moe_counters()
    if not kernel_s or not layers or not all(
            c["steps"] for c in layers.values()):
        return None
    held = sum(c["held"] / c["steps"] for c in layers.values())
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        keye2_ops_count.grouped_kernel_flops(
            ctx["cell"]["config_data"], kernel, held),
        kernel_s, peak["bf16_flops_per_s"])
