"""What the two kernels of the chunked Gated DeltaNet's operand stage
(`veles_gdn_chunk_fwd`, `veles_gdn_chunk_bwd`; ISSUE 42) have to move and
to multiply in one call on a `qwen3next_lm` configuration, from its file
alone, how often the traced steps called them, and the share of the chip's
roofline that is over a kernel's own device time. Nothing here imports the
program.

The work is the STAGE's, whatever implements it. A call covers one group
of sequences (`scan_groups`) of one linear layer: every (chunk, value head)
of them, a CHUNK-HEAD. Its interface, unpadded: forward q, k, v in the
compute dtype and the cumulative log-decay and beta in float32 in, w, u0,
kd, qg, the chunk's square `attn` and the scalar `last` out; backward the
same inputs and the six cotangents in, the five gradients out. Its matrix
work a chunk-head: the ten C^3 products of the chunk's inverse and the
products of C x C by the head's width (K K^T, Q K^T, T by the two operand
blocks forward; those formed again, the two cotangents of T's products
either way round, d A = -T^T (d T) T^T and the four products that reach q
and k backward). The roofline's time is the longer of the bytes at
`peaks.json`'s HBM rate and the operations at its bf16 peak: HBM's at the
cell's sizes (1.23 against 0.39 ms a forward call), so the share cannot
pass 100.

The calls a step are COUNTED from the trace's events, never held as a
constant: the change that brought the kernels itself changed how often the
stage runs (its own `jax.checkpoint` went), and a later one that keeps the
operands through a group's checkpoint would change calls and time
together.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

from benchmark import ops_count, qwen3next_ops_count
from benchmark import trace_reduce as T

KERNELS = ("veles_gdn_chunk_fwd", "veles_gdn_chunk_bwd")
#: rows of the diagonal blocks the inverse starts from
#: (`linear_attention.INVERSE_BLOCK`: a test holds it to the program's)
INVERSE_BLOCK = 16


def chunk_heads(cfg: Dict[str, Any]) -> int:
    """Chunk-heads ONE call covers: a group's sequences, whole."""
    d = qwen3next_ops_count.dims(cfg)
    chunk = cfg["chunk"]
    return (cfg["batch_per_chip"] // cfg.get("scan_groups", 1)
            * -(-d["seq"] // chunk) * d["value_heads"])


def interface_bytes(cfg: Dict[str, Any], kernel: str) -> int:
    """Bytes a chunk-head's stage cannot avoid moving, by kernel."""
    d = qwen3next_ops_count.dims(cfg)
    c, dk, dv = cfg["chunk"], d["dk"], d["dv"]
    op = ops_count.ITEMSIZE[cfg["compute_dtype"]]
    inputs = op * c * (2 * dk + dv) + 4 * 2 * c         # q, k, v; gamma, beta
    # w, kd, qg of the keys' width, u0 of the values', attn, last
    results = op * c * (3 * dk + dv) + op * c * c + 4
    if kernel == "veles_gdn_chunk_fwd":
        return inputs + results
    if kernel == "veles_gdn_chunk_bwd":
        return inputs + results + inputs    # the cotangents, the gradients
    raise KeyError(kernel)


def inverse_products(chunk: int) -> int:
    """C^3 products of (I + A)^-1 by substitution over blocks of
    INVERSE_BLOCK rows: two a doubling, of the powers inside a block and of
    the blocks put together (ten at 64)."""
    return 2 * ((chunk - 1).bit_length() - 1)


def matrix_flops(cfg: Dict[str, Any], kernel: str) -> int:
    """Matrix operations of a chunk-head's stage, by kernel."""
    d = qwen3next_ops_count.dims(cfg)
    c, dk, dv = cfg["chunk"], d["dk"], d["dv"]
    square, by_k, by_v = 2 * c ** 3, 2 * c * c * dk, 2 * c * c * dv
    formed = inverse_products(c) * square + 2 * by_k     # T; K K^T, Q K^T
    if kernel == "veles_gdn_chunk_fwd":
        return formed + by_k + by_v                      # T kb, T vb
    if kernel == "veles_gdn_chunk_bwd":
        # d T from both products, d kb and d vb; d A; N k, N^T k, P k, P^T q
        return formed + 2 * (by_k + by_v) + 2 * square + 4 * by_k
    raise KeyError(kernel)


def call_seconds_at_peak(cfg: Dict[str, Any], kernel: str,
                         peak: Dict[str, float]) -> float:
    """The least time the chip could take over one call's work."""
    n = chunk_heads(cfg)
    return max(n * interface_bytes(cfg, kernel) / peak["hbm_bytes_per_s"],
               n * matrix_flops(cfg, kernel) / peak["bf16_flops_per_s"])


@functools.lru_cache(maxsize=2)
def _kernel_events(path: str) -> Optional[Tuple[Dict[str, Tuple[int, float]],
                                                int]]:
    """({kernel: (events, seconds) inside the traced window of device 0},
    whole steps in the window) for every `veles_gdn_chunk_*` operation."""
    rows = T.events_of(path)["devices"].get(0)
    base = rows and T.reduce_device(rows[T.OPS_LINE], rows[T.MODULES_LINE])
    if not base:
        return None
    lo, hi = base["window"]
    found: Dict[str, Tuple[int, float]] = {}
    for name, a, b in rows[T.OPS_LINE]:
        # a trace names an operation by its HLO line, which starts with
        # the kernel's fixed name and the instruction's number
        if not name.startswith("%veles_gdn_chunk_") or a < lo or b > hi:
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


def gdn_kernel_roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the chip's roofline `kernel` reaches: the least time the
    chip's peaks allow for the work of its calls of a step, over their
    device time. None where the trace holds no such call: a step that fell
    to the XLA form on the chip shows as a missing roofline."""
    read = kernel_calls(ctx, kernel)
    if not read or not read[1]:
        return None
    calls, seconds = read
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return 100.0 * calls * call_seconds_at_peak(
        ctx["cell"]["config_data"], kernel, peak) / seconds
