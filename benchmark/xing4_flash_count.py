"""Operations the three flash kernels of latent attention's core
(`veles_flash_fwd`, `veles_flash_dq`, `veles_flash_dkv`; ISSUE 38) EXECUTE
in one call on a `xing4_lm` configuration, from its file and the kernels'
tile sizes alone, how often the traced steps called them, and the share of
the chip's peak that is over a kernel's own device time. Nothing here
imports the program.

A call covers every (sequence, head) of the chip and visits every
(queries, keys) tile that holds a causal pair (20 of the 32 tiles of 512 x
1,024 at 4,096 tokens); over a tile the forward forms the scores (a
product of 2 x the key's width a pair) and the values' sum (2 x the
value's width), dQ the scores, the probabilities' cotangent and the
queries' gradient, dK/dV the scores, the values' gradient, the
probabilities' cotangent and the keys' gradient: 2, 3 and 4 products.
What is REQUIRED of the core is `xing4_ops_count`'s (causal pairs, three
forwards' worth); these are the kernels' own work over their own time.

The calls a step are COUNTED from the trace's events, not held as a
constant: a later change of what a block's `jax.checkpoint` saves (a
forward recomputed in the backward pass) changes the calls and the time
together, and the share stays under 100.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

from benchmark import keye2_ops_count, ops_count, xing4_ops_count
from benchmark import trace_reduce as T

#: queries and keys a grid step holds at most (`pallas_kernels.
#: _FLASH_BLK_Q`, `_FLASH_BLK_K`; shrunk to divide the sequence as
#: `keye2_ops_count._fit` shrinks: a test holds these to the program's)
FLASH_BLOCKS = (512, 1024)
#: products a (head, query, key) pair of a visited tile, by kernel: (of 2 x
#: the key's width, of 2 x the value's width) operations
FLASH_KERNEL_PRODUCTS = {"veles_flash_fwd": (1, 1), "veles_flash_dq": (2, 1),
                         "veles_flash_dkv": (2, 2)}


def pairs_visited(seq: int) -> int:
    """Pairs of the tiles a kernel visits over one causal sequence."""
    bq, bk = (keye2_ops_count._fit(seq, b) for b in FLASH_BLOCKS)
    return sum(bq * bk * ((i * bq + bq - 1) // bk + 1)
               for i in range(seq // bq))


def flash_call_flops(cfg: Dict[str, Any], kernel: str, batch: int) -> float:
    """Operations ONE call of `kernel` executes on `batch` sequences."""
    d = xing4_ops_count.dims(cfg)
    of_key, of_value = FLASH_KERNEL_PRODUCTS[kernel]
    return float(batch * d["heads"] * pairs_visited(d["seq"]) * 2
                 * (of_key * (d["nope"] + d["rope"]) + of_value * d["v"]))


@functools.lru_cache(maxsize=2)
def _kernel_events(path: str) -> Optional[Tuple[Dict[str, Tuple[int, float]],
                                                int]]:
    """({kernel: (events, seconds) inside the traced window of device 0},
    whole steps in the window) for every `veles_flash_*` operation."""
    rows = T.events_of(path)["devices"].get(0)
    base = rows and T.reduce_device(rows[T.OPS_LINE], rows[T.MODULES_LINE])
    if not base:
        return None
    lo, hi = base["window"]
    found: Dict[str, Tuple[int, float]] = {}
    for name, a, b in rows[T.OPS_LINE]:
        # a trace names an operation by its HLO line, which starts with
        # the kernel's fixed name and the instruction's number
        if not name.startswith("%veles_flash_") or a < lo or b > hi:
            continue
        kernel = name[1:].split(" ")[0].split(".")[0]
        n, s = found.get(kernel, (0, 0.0))
        found[kernel] = (n + 1, s + b - a)
    return found, base["steps"]


def kernel_calls(ctx, kernel: str) -> Optional[Tuple[float, float]]:
    """(calls, seconds) of `kernel` a step of the traced run on device 0.
    Nothing to read where the step runs no such kernel (a program from
    before them, the XLA form, a run that was not traced)."""
    if ctx.get("trace") is None:
        return None
    from veles_tpu.caches import cache_path
    trace_dir = os.path.join(
        cache_path("benchmark", ctx["cell"]["name"]), "trace")
    try:
        found = _kernel_events(T.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    if not found or kernel not in found[0]:
        return None
    (n, s), steps = found[0][kernel], found[1]
    return n / steps, s / steps


def flash_kernel_roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the chip's bf16 peak `kernel` reaches: the operations its
    calls of a step execute over their device time x `peaks.json`.
    Compute bounds the kernels."""
    read = kernel_calls(ctx, kernel)
    if not read or not read[1]:
        return None
    calls, seconds = read
    cfg = ctx["cell"]["config_data"]
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        calls * flash_call_flops(cfg, kernel, cfg["batch_per_chip"]),
        seconds, peak["bf16_flops_per_s"])
