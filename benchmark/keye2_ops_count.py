"""Shapes, parameters and matrix operations of a `keye2_lm` configuration,
from its file alone. Nothing here imports the program.

A configuration file (`configs/keye2_ep8.json`) keeps the published
`config.json`'s keys; those it lists under `reduced` give what THIS chip
holds (`num_hidden_layers`, `num_experts` and `num_local_experts`,
`vocab_size`), `published` what the model has. The router keeps the
published number of outputs; attention and the indexer are whole.

REQUIRED operations (what `step_mxu_share` and `dsa_attend_mxu_share`
divide by time) are what the mathematics needs, whatever implements it:
the main attention over the SELECTED (query, key) pairs, the indexer over
the CAUSAL pairs (every one has to be scored before any can be left out).
A program that scores every causal pair and masks does more and is
credited with no more; one that stops scoring unselected keys gains time
and no work, so no share can pass 100 %.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

Shapes = Dict[str, Tuple[int, ...]]


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    pub, sa = cfg.get("published", {}), cfg["sa_config"]
    return {
        "c": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "expert_width": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"],
        "held_first": cfg.get("held_experts_first", 0),
        "experts": pub.get("num_experts", cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"], "seq": cfg["seq_len"],
    }


def block_shapes(d: Dict[str, int]) -> Shapes:
    c, h, kv, hd = d["c"], d["heads"], d["kv_heads"], d["d"]
    hi, di, w, e = (d["index_heads"], d["index_dim"], d["expert_width"],
                    d["held"])
    return {"attn_norm": (c,), "attn_w_q": (c, h * hd),
            "attn_w_k": (c, kv * hd), "attn_w_v": (c, kv * hd),
            "attn_q_norm": (hd,), "attn_k_norm": (hd,),
            "attn_w_o": (h * hd, c), "attn_idx_w_q": (c, hi * di),
            "attn_idx_w_k": (c, di), "attn_idx_k_norm": (di,),
            "attn_idx_k_bias": (di,), "attn_idx_w_w": (c, hi),
            "moe_norm": (c,), "moe_w_router": (c, d["experts"]),
            "moe_experts_gate": (e, c, w), "moe_experts_up": (e, c, w),
            "moe_experts_down": (e, w, c)}


def shapes_of(cfg: Dict[str, Any]) -> List[Shapes]:
    """One dict of leaf shapes per unit of the program's layer table: the
    embedding, the blocks, the head."""
    d = dims(cfg)
    return ([{"weights": (d["vocab"], d["c"])}]
            + [block_shapes(d) for _ in range(d["layers"])]
            + [{"final_norm": (d["c"],), "weights": (d["c"], d["vocab"])}])


def n_params(cfg: Dict[str, Any]) -> int:
    return sum(math.prod(s) for layer in shapes_of(cfg)
               for s in layer.values())


def layer_names(cfg: Dict[str, Any]) -> List[str]:
    """Names of the blocks in the order the program's counters and the
    reference's lists hold them: `L<nn>`, the unit's scope."""
    return [f"L{i + 1:02d}" for i in range(dims(cfg)["layers"])]


# -- pairs -----------------------------------------------------------------------

def pairs_causal(seq: int) -> int:
    """(query, key) pairs with key <= query, of one sequence."""
    return seq * (seq + 1) // 2


def pairs_selected(seq: int, topk: int) -> int:
    """Pairs a query attends to: all its causal keys up to `topk` of
    them."""
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


# -- matrix operations -------------------------------------------------------------
# 2 operations a multiply-add.

def pair_flops(cfg: Dict[str, Any]) -> int:
    """One (query, key) pair through the main attention, forward: the
    score and the value product of every query head."""
    d = dims(cfg)
    return 2 * 2 * d["heads"] * d["d"]


def slot_flops(cfg: Dict[str, Any]) -> int:
    """One (token, slot) pair through one expert: three products."""
    d = dims(cfg)
    return 2 * 3 * d["c"] * d["expert_width"]


def attend_flops(cfg: Dict[str, Any], pairs: float, passes: int) -> float:
    """The main attention over `pairs` (query, key) pairs, `passes` times
    a forward's worth (forward 1, backward 2)."""
    return passes * pairs * pair_flops(cfg)


def train_flops_per_step(cfg: Dict[str, Any], batch: int) -> float:
    """Required operations of one step on `batch` sequences, by the rule
    at the top: forward, input gradient and weight gradient of every
    product (3 forwards' worth; the indexer's projections read a stopped
    gradient and have no input gradient: 2). The embedding is a gather;
    recomputed operations do not count. The held experts at balance:
    `top_k * held / experts` slots a token."""
    d = dims(cfg)
    c, seq = d["c"], d["seq"]
    proj = 2 * c * d["d"] * (2 * d["heads"] + 2 * d["kv_heads"])
    index_proj = 2 * c * (d["index_heads"] * d["index_dim"]
                          + d["index_dim"] + d["index_heads"])
    router = 2 * c * d["experts"]
    routed = slot_flops(cfg) * d["top_k"] * d["held"] / d["experts"]
    per_token = d["layers"] * (3 * (proj + router + routed) + 2 * index_proj) \
        + 3 * 2 * c * d["vocab"]
    index_pair = 2 * d["index_heads"] * d["index_dim"]
    per_sequence = d["layers"] * 3 * (
        pairs_selected(seq, d["topk"]) * pair_flops(cfg)
        + pairs_causal(seq) * index_pair)
    return float(batch * (seq * per_token + per_sequence))


# -- the four kernels of the main attention ----------------------------------------
# What a `veles_dsa_*` kernel EXECUTES on the matrix unit in one step: every
# (query, key) pair of the tiles it visits (all tiles that hold a causal
# pair; a pair the selection leaves out is scored like any other), through
# its products. Over the kernel's device time and the chip's peak that is
# its roofline share, `<kernel>_roofline`: compute bounds these kernels.

#: queries and keys a grid step holds (`pallas_kernels._DSA_BLK_Q`,
#: `_DSA_BLK_K`, shrunk to divide the sequence: a test holds the two
#: together), and the bands of queries `veles_dsa_pmean` is called in
DSA_BLOCKS = (512, 1024)
#: products over a tile by kernel (scores; values or their transposes)
DSA_KERNEL_PRODUCTS = {"veles_dsa_attend_fwd": 2, "veles_dsa_pmean": 1,
                       "veles_dsa_attend_dq": 3, "veles_dsa_attend_dkv": 4}
#: calls a block and step: the mean-head probabilities are formed for the
#: loss forward and again for its gradient
DSA_KERNEL_CALLS = {"veles_dsa_attend_fwd": 1, "veles_dsa_pmean": 2,
                    "veles_dsa_attend_dq": 1, "veles_dsa_attend_dkv": 1}


def _fit(seq: int, blk: int) -> int:
    blk = min(blk, seq)
    while blk > 128 and seq % blk:
        blk //= 2
    return blk


def pairs_visited(seq: int, bands: int = 1) -> int:
    """Pairs of the tiles a kernel visits over one sequence walked in
    `bands` bands of queries, band b against the keys up to its end."""
    per = seq // bands
    total = 0
    for b in range(bands):
        lo, hi = b * per, (b + 1) * per
        bq, bk = _fit(per, DSA_BLOCKS[0]), _fit(hi, DSA_BLOCKS[1])
        for i in range(per // bq):
            last = (lo + i * bq + bq - 1) // bk
            total += bq * bk * (min(last, hi // bk - 1) + 1)
    return total


def dsa_kernel_flops(cfg: Dict[str, Any], kernel: str, batch: int) -> float:
    """Operations `kernel` executes in one step on `batch` sequences."""
    d = dims(cfg)
    bands = cfg.get("key_bands", 4) if kernel == "veles_dsa_pmean" else 1
    return float(batch * d["layers"] * DSA_KERNEL_CALLS[kernel]
                 * DSA_KERNEL_PRODUCTS[kernel] * 2 * d["heads"] * d["d"]
                 * pairs_visited(d["seq"], bands))


#: products of one (token, slot) pair's 2 x C x H operations that the
#: held experts' kernels execute a layer and step: `veles_gmm` the
#: forward's three, the same three when the backward recomputes its
#: branch, and the three that carry the gradient to the rows;
#: `veles_tgmm` the three that form the weights' gradient
GROUPED_KERNEL_PRODUCTS = {"veles_gmm": 9, "veles_tgmm": 3}


def grouped_kernel_flops(cfg: Dict[str, Any], kernel: str,
                         held_slots: float) -> float:
    """Operations `kernel` executes in one step on the rows of
    `held_slots` held (token, slot) pairs, all layers together."""
    d = dims(cfg)
    return float(GROUPED_KERNEL_PRODUCTS[kernel] * held_slots
                 * 2 * d["c"] * d["expert_width"])
