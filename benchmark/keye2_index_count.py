"""Operations the indexer's two kernels (`veles_dsa_index_fwd`,
`veles_dsa_index_bwd`; ISSUE 36) EXECUTE in one step of a `keye2_lm`
configuration, from its file and the kernels' tile sizes alone, and the
share of the chip's peak that is over a kernel's own device time.
Nothing here imports the program.

A kernel is called a block of `query_block` queries at a time, a block of
band b against the keys up to the band's end, and visits every (queries,
keys) tile that holds a causal pair; over a tile it forms each index
head's scores (one product of 2 x `indexer_head_dim` operations a pair),
the backward two more products a head (the queries' and the keys'
gradient). The forward is called three times a block, layer and step: for
the selection, for the index loss and, recomputed, for the loss's
gradient; the backward once. What is REQUIRED of the indexer is
`keye2_ops_count.train_flops_per_step`'s (causal pairs, three forwards'
worth): these are the kernels' own work, over their own time. A
contraction of 64 half-fills a v5e's 128-deep array, which bounds both
near 50 %.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import keye2_ops_count, keye2_scopes, ops_count

#: queries and keys a grid step holds at most (`pallas_kernels.
#: _DSA_INDEX_BLK_Q`, `_DSA_INDEX_BLK_K`; shrunk to divide a block of
#: queries and a band's keys as `keye2_ops_count._fit` shrinks: a test
#: holds these to the program's)
INDEX_BLOCKS = (512, 512)
#: products of 2 x heads x width operations a pair, by kernel
INDEX_KERNEL_PRODUCTS = {"veles_dsa_index_fwd": 1, "veles_dsa_index_bwd": 3}
#: calls a block of queries, layer and step
INDEX_KERNEL_CALLS = {"veles_dsa_index_fwd": 3, "veles_dsa_index_bwd": 1}


def pairs_visited(seq: int, bands: int, block: int) -> int:
    """Pairs of the tiles a kernel visits over one sequence walked in
    `bands` bands of queries, a block of `block` queries a call, band b
    against the keys up to its end."""
    per = seq // bands
    total = 0
    for b in range(bands):
        hi = (b + 1) * per
        bq = keye2_ops_count._fit(min(block, per), INDEX_BLOCKS[0])
        bk = keye2_ops_count._fit(hi, INDEX_BLOCKS[1])
        for q0 in range(b * per, hi, bq):
            total += bq * bk * (min((q0 + bq - 1) // bk, hi // bk - 1) + 1)
    return total


def index_kernel_flops(cfg: Dict[str, Any], kernel: str, batch: int) -> float:
    """Operations `kernel` executes in one step on `batch` sequences."""
    d = keye2_ops_count.dims(cfg)
    return float(batch * d["layers"] * INDEX_KERNEL_CALLS[kernel]
                 * INDEX_KERNEL_PRODUCTS[kernel] * 2 * d["index_heads"]
                 * d["index_dim"]
                 * pairs_visited(d["seq"], cfg.get("key_bands", 4),
                                 cfg.get("query_block", 256)))


def index_kernel_roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the chip's bf16 peak `kernel` reaches: the operations it
    executes in a step over its device time x `peaks.json`. Nothing to
    read where the traced step runs no such kernel (a program from before
    them, another lowering, a run that was not traced)."""
    kernel_s = keye2_scopes.kernel_seconds(ctx, kernel)
    if not kernel_s:
        return None
    cfg = ctx["cell"]["config_data"]
    peak = ops_count.peak_for(ctx["peaks"], ctx["device_kind"])
    return ops_count.mxu_share_percent(
        index_kernel_flops(cfg, kernel, cfg["batch_per_chip"]), kernel_s,
        peak["bf16_flops_per_s"])
