"""What the held experts' combine (`veles_seg_sum`, ISSUE 43: every token's
sum of its held rows of a sorted buffer, forward as the expert layer's
combine and backward as the transpose of the rows' gather) has to move in
one call on a sparse-expert language-model configuration, from its file
alone, how often the traced steps called it, and the share of the chip's
memory roofline that is over the kernel's own device time. Nothing here
imports the program.

The work is the SUM's, whatever implements it: the rows that are held, of
the model's width in the compute dtype, read once; a row a token written.
A form that gathers a row a (token, slot) pair, held or not, moves k times
the tokens' rows and earns no more; one that permutes the rows first pays
for the permutation outside the kernel's time and inside the step's. The
held rows are the ones the program COUNTED in the window
(`veles_moe_held_slots_total`, `moe_held_slot_share`'s counter: a layer
and step's mean), the calls a step are COUNTED from the trace's events,
never held as a constant: two a layer with experts today (8, 12 and 10 in
the three cells), more where a skewed router sends a layer to the
whole-buffer branch's windows, fewer where a later change saves the
backward's. HBM bounds the sum (one one-hot product a row and token tile:
0.3 ms of the matrix unit a call at `qwen3next_ep16.seq8k`'s sizes beside
0.27 ms of HBM time), and both sides of the share count each byte once, so
it cannot pass 100.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

from benchmark import ops_count
from benchmark import trace_reduce as T

KERNEL = "veles_seg_sum"


def tokens(cfg: Dict[str, Any]) -> int:
    """Tokens of one step on one chip: a call sums into every one."""
    return cfg["batch_per_chip"] * cfg["seq_len"]


def held_rows_at_balance(cfg: Dict[str, Any]) -> float:
    """(token, slot) pairs a layer holds here where the router sends every
    expert the same load: what the counters read near, by hand."""
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    pub = cfg.get("published", {})
    experts = pub.get("n_routed_experts", pub.get("num_experts", held))
    return tokens(cfg) * cfg["num_experts_per_tok"] * held / experts


def call_bytes(cfg: Dict[str, Any], held_rows: float) -> Tuple[float, float]:
    """(bytes read, bytes written) one call cannot avoid: the held rows,
    a row a token."""
    row = cfg["hidden_size"] * ops_count.ITEMSIZE[cfg["compute_dtype"]]
    return held_rows * row, tokens(cfg) * row


def call_seconds_at_peak(cfg: Dict[str, Any], held_rows: float,
                         peak: Dict[str, float]) -> float:
    """The least time the chip could take over one call's work."""
    return sum(call_bytes(cfg, held_rows)) / peak["hbm_bytes_per_s"]


@functools.lru_cache(maxsize=2)
def _kernel_events(path: str) -> Optional[Tuple[int, float, int]]:
    """(events, seconds, whole steps) of the `veles_seg_sum` operations
    inside the traced window of device 0."""
    rows = T.events_of(path)["devices"].get(0)
    base = rows and T.reduce_device(rows[T.OPS_LINE], rows[T.MODULES_LINE])
    if not base:
        return None
    lo, hi = base["window"]
    # a trace names an operation by its HLO line, which starts with the
    # kernel's fixed name and the instruction's number
    spans = [b - a for name, a, b in rows[T.OPS_LINE]
             if name[1:].split(" ")[0].split(".")[0] == KERNEL
             and name.startswith("%") and a >= lo and b <= hi]
    return len(spans), sum(spans), base["steps"]


def kernel_calls(ctx) -> Optional[Tuple[float, float]]:
    """(calls, seconds) of the kernel a step of the traced run on device
    0. Nothing to read where the step runs no such kernel (a program from
    before it, the gather form, a run that was not traced)."""
    if ctx.get("trace") is None:
        return None
    from veles_tpu.caches import cache_path
    trace_dir = os.path.join(
        cache_path("benchmark", ctx["cell"]["name"]), "trace")
    try:
        found = _kernel_events(T.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    if not found or not found[0]:
        return None
    n, s, steps = found
    return n / steps, s / steps


def held_rows_counted() -> Optional[float]:
    """Held (token, slot) pairs a layer and step of the window, mean over
    the expert layers, from the program's `veles_moe_*` counters."""
    from benchmark import xing4_scopes as X
    layers = X.moe_counters()
    if not layers or not all(c.get("steps") for c in layers.values()):
        return None
    return sum(c["held"] / c["steps"] for c in layers.values()) / len(layers)


def seg_sum_roofline(ctx) -> Optional[float]:
    """Share of the chip's memory roofline the combine reaches: the least
    time HBM allows for the sums of its calls of a step, over their device
    time. None where the trace holds no such call: a step that gathers the
    slots shows as a missing roofline. Says the count on the run's output
    (the result line comes after it)."""
    read, held = kernel_calls(ctx), held_rows_counted()
    if not read or not read[1] or held is None:
        return None
    calls, seconds = read
    cfg = ctx["cell"]["config_data"]
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    print(f"seg_sum: {calls:.2f} calls a step, {1e3 * seconds:.3f} ms a "
          f"step, {held:.0f} held rows a call", flush=True)
    return 100.0 * calls * call_seconds_at_peak(cfg, held, peak) / seconds
