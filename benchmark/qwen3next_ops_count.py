"""Shapes, parameters and matrix operations of a `qwen3next_lm`
configuration, from its file alone. Nothing here imports the program.

A configuration file (`configs/qwen3next_ep16.json`) keeps the published
`config.json`'s keys; those it lists under `reduced` give what THIS chip
holds (`num_hidden_layers`, `num_experts`, `vocab_size`), `published` what
the model has. The router keeps the published number of outputs; the
mixers, the router and the shared expert are whole.

REQUIRED operations (what `step_mxu_share` and `gdn_scan_mxu_share` divide
by time) are what the mathematics needs, whatever implements it: of a Gated
DeltaNet's recurrence three products of a key by the state a token and
value head (the read before the write, the write, the read after it: 6 dk
dv operations), of full attention the causal pairs. The chunked form the
program runs does more (products inside a chunk, an inverse) and is
credited with no more, so no share can pass 100 %.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

Shapes = Dict[str, Tuple[int, ...]]


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    pub = cfg.get("published", {})
    return {
        "c": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "key_heads": cfg["linear_num_key_heads"],
        "value_heads": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "conv": cfg["linear_conv_kernel_dim"],
        "interval": cfg["full_attention_interval"],
        "expert_width": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"],
        "held_first": cfg.get("held_experts_first", 0),
        "experts": pub.get("num_experts", cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"], "seq": cfg["seq_len"],
    }


def is_full(d: Dict[str, int], layer: int) -> bool:
    """Whether layer `layer` (from 0) mixes by full attention."""
    return (layer + 1) % d["interval"] == 0


def block_shapes(d: Dict[str, int], full: bool) -> Shapes:
    c, w, e = d["c"], d["expert_width"], d["held"]
    if full:
        h, kv, hd = d["heads"], d["kv_heads"], d["d"]
        mixer = {"attn_norm": (c,), "attn_w_q": (c, h * 2 * hd),
                 "attn_w_k": (c, kv * hd), "attn_w_v": (c, kv * hd),
                 "attn_q_norm": (hd,), "attn_k_norm": (hd,),
                 "attn_w_o": (h * hd, c)}
    else:
        kw, vw = d["key_heads"] * d["dk"], d["value_heads"] * d["dv"]
        mixer = {"attn_norm": (c,), "attn_w_qkvz": (c, 2 * kw + 2 * vw),
                 "attn_w_ba": (c, 2 * d["value_heads"]),
                 "attn_conv": (d["conv"], 2 * kw + vw),
                 "attn_a_log": (d["value_heads"],),
                 "attn_dt_bias": (d["value_heads"],),
                 "attn_o_norm": (d["dv"],), "attn_w_o": (vw, c)}
    return {**mixer, "moe_norm": (c,), "moe_w_router": (c, d["experts"]),
            "moe_experts_gate": (e, c, w), "moe_experts_up": (e, c, w),
            "moe_experts_down": (e, w, c), "moe_shared_gate": (c, w),
            "moe_shared_up": (c, w), "moe_shared_down": (w, c),
            "moe_shared_mix": (c, 1)}


def shapes_of(cfg: Dict[str, Any]) -> List[Shapes]:
    """One dict of leaf shapes per unit of the program's layer table: the
    embedding, the blocks, the head."""
    d = dims(cfg)
    return ([{"weights": (d["vocab"], d["c"])}]
            + [block_shapes(d, is_full(d, i)) for i in range(d["layers"])]
            + [{"final_norm": (d["c"],), "weights": (d["c"], d["vocab"])}])


def n_params(cfg: Dict[str, Any]) -> int:
    return sum(math.prod(s) for layer in shapes_of(cfg)
               for s in layer.values())


def layer_names(cfg: Dict[str, Any]) -> List[str]:
    """Names of the blocks in the order the program's counters and the
    reference's lists hold them: `L<nn>`, the unit's scope."""
    return [f"L{i + 1:02d}" for i in range(dims(cfg)["layers"])]


def linear_units(cfg: Dict[str, Any]) -> List[int]:
    """The Gated DeltaNet blocks' places in `shapes_of`'s list."""
    d = dims(cfg)
    return [i + 1 for i in range(d["layers"]) if not is_full(d, i)]


# -- matrix operations -------------------------------------------------------------
# 2 operations a multiply-add.

def pairs_causal(seq: int) -> int:
    """(query, key) pairs with key <= query, of one sequence."""
    return seq * (seq + 1) // 2


def slot_flops(cfg: Dict[str, Any]) -> int:
    """One (token, slot) pair through one expert: three products."""
    d = dims(cfg)
    return 2 * 3 * d["c"] * d["expert_width"]


def gdn_scan_flops(cfg: Dict[str, Any], batch: int, passes: int = 3) -> float:
    """Required operations of the Gated DeltaNet layers' recurrences in one
    step on `batch` sequences, `passes` forwards' worth (forward 1,
    backward 2): 6 dk dv a token and value head (module docstring)."""
    d = dims(cfg)
    return float(passes * batch * d["seq"] * len(linear_units(cfg))
                 * d["value_heads"] * 6 * d["dk"] * d["dv"])


def forward_flops(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """Required operations of one forward pass on `batch` sequences, by
    part: the linear layers' projections, their recurrences, the full
    layers' projections and causal pairs, the expert layers (router, the
    held experts at balance: `top_k * held / experts` slots a token, the
    shared expert and its gate), the head. The embedding is a gather."""
    d = dims(cfg)
    c, seq = d["c"], d["seq"]
    n_lin = len(linear_units(cfg))
    n_full = d["layers"] - n_lin
    kw, vw = d["key_heads"] * d["dk"], d["value_heads"] * d["dv"]
    tokens = batch * seq
    lin_proj = 2 * c * (2 * kw + 2 * vw + 2 * d["value_heads"]) + 2 * vw * c
    full_proj = 2 * c * d["d"] * (2 * d["heads"] + 2 * d["kv_heads"]) \
        + 2 * d["heads"] * d["d"] * c
    moe = 2 * c * d["experts"] \
        + slot_flops(cfg) * d["top_k"] * d["held"] / d["experts"] \
        + slot_flops(cfg) + 2 * c
    return {
        "gdn_proj": float(tokens * n_lin * lin_proj),
        "gdn_scan": gdn_scan_flops(cfg, batch, 1),
        "attn_proj": float(tokens * n_full * full_proj),
        "attn_pairs": float(batch * n_full * pairs_causal(seq)
                            * 2 * 2 * d["heads"] * d["d"]),
        "moe": float(tokens * d["layers"] * moe),
        "head": float(tokens * 2 * c * d["vocab"]),
    }


def train_flops_per_step(cfg: Dict[str, Any], batch: int) -> float:
    """Required operations of one step on `batch` sequences: forward,
    input gradient and weight gradient of every product, 3 forwards'
    worth; recomputed operations do not count."""
    return 3.0 * sum(forward_flops(cfg, batch).values())
