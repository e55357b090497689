"""Shapes, parameters and matrix operations of a `xing4_lm` configuration,
from its file alone. Nothing here imports the program.

A configuration file (`configs/xing4_ep8.json`) keeps the published
`config.json`'s keys; the four it lists under `reduced` give what THIS
chip holds (`num_hidden_layers`, `n_routed_experts`, `num_attention_heads`,
`vocab_size`), `published` what the model has. The router keeps the
published number of outputs. Of the held layers the first
`dense_layers_held` are dense, the rest expert layers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

Shapes = Dict[str, Tuple[int, ...]]


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    pub = cfg.get("published", {})
    return {
        "c": cfg["hidden_size"], "n": cfg["hc_mult"],
        "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"],
        "experts": pub.get("n_routed_experts", cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["dense_layers_held"],
        "mtp": cfg["num_nextn_predict_layers"],
        "seq": cfg["seq_len"],
    }


def hc_shapes(d: Dict[str, int], prefix: str) -> Shapes:
    n, nc = d["n"], d["n"] * d["c"]
    return {prefix + "p_pre": (nc, n), prefix + "p_post": (nc, n),
            prefix + "p_res": (nc, n * n),
            prefix + "a_pre": (1,), prefix + "a_post": (1,),
            prefix + "a_res": (1,),
            prefix + "b_pre": (n,), prefix + "b_post": (n,),
            prefix + "b_res": (n, n)}


def attention_shapes(d: Dict[str, int]) -> Shapes:
    c, h = d["c"], d["heads"]
    return {"attn_norm": (c,), "attn_w_dq": (c, d["q_rank"]),
            "attn_q_norm": (d["q_rank"],),
            "attn_w_uq": (d["q_rank"], h * (d["nope"] + d["rope"])),
            "attn_w_dkv": (c, d["kv_rank"] + d["rope"]),
            "attn_kv_norm": (d["kv_rank"],),
            "attn_w_ukv": (d["kv_rank"], h * (d["nope"] + d["v"])),
            "attn_w_o": (h * d["v"], c)}


def ffn_shapes(d: Dict[str, int], kind: str) -> Shapes:
    c = d["c"]
    if kind == "dense":
        w = d["dense_width"]
        return {"mlp_norm": (c,), "mlp_w_gate": (c, w), "mlp_w_up": (c, w),
                "mlp_w_down": (w, c)}
    w, e = d["expert_width"], d["held"]
    return {"moe_norm": (c,), "moe_w_router": (c, d["experts"]),
            "moe_shared_gate": (c, w), "moe_shared_up": (c, w),
            "moe_shared_down": (w, c),
            "moe_experts_gate": (e, c, w), "moe_experts_up": (e, c, w),
            "moe_experts_down": (e, w, c)}


def block_shapes(d: Dict[str, int], kind: str, prefix: str = "") -> Shapes:
    """One block: the hyper-connection around attention, attention, the
    hyper-connection around the MLP or expert layer, that layer."""
    shapes = {**hc_shapes(d, "hca_"), **attention_shapes(d),
              **hc_shapes(d, "hcm_"), **ffn_shapes(d, kind)}
    return {prefix + k: v for k, v in shapes.items()}


def block_kinds(d: Dict[str, int]) -> List[str]:
    return ["dense"] * d["dense_layers"] \
        + ["experts"] * (d["layers"] - d["dense_layers"])


def shapes_of(cfg: Dict[str, Any]) -> List[Shapes]:
    """One dict of leaf shapes per unit of the program's layer table: the
    embedding, the blocks, the head (with the multi-token-prediction
    module's projection, norms and block, where the model has one)."""
    d = dims(cfg)
    head: Shapes = {"final_norm": (d["c"],), "weights": (d["c"], d["vocab"])}
    if d["mtp"]:
        head.update({"mtp_norm_h": (d["c"],), "mtp_norm_e": (d["c"],),
                     "mtp_w_proj": (2 * d["c"], d["c"]),
                     "mtp_final_norm": (d["c"],),
                     **block_shapes(d, "experts", "mtp_")})
    return ([{"weights": (d["vocab"], d["c"])}]
            + [block_shapes(d, kind) for kind in block_kinds(d)] + [head])


def n_params(cfg: Dict[str, Any]) -> int:
    return sum(math.prod(s) for layer in shapes_of(cfg)
               for s in layer.values())


def expert_layers(cfg: Dict[str, Any]) -> List[str]:
    """Names of the expert layers in the order the program's counters and
    the reference's lists hold them: `L<nn>` of the trunk, then `mtp`."""
    d = dims(cfg)
    out = [f"L{i + 1:02d}" for i, kind in enumerate(block_kinds(d))
           if kind == "experts"]
    return out + ["mtp"] * bool(d["mtp"])


# -- matrix operations ----------------------------------------------------------------
# 2 operations a multiply-add. Per token, forward.

def slot_flops(cfg: Dict[str, Any]) -> int:
    """One (token, slot) pair through one expert: three products."""
    d = dims(cfg)
    return 2 * 3 * d["c"] * d["expert_width"]


def forward_flops_per_token(cfg: Dict[str, Any]) -> Dict[str, float]:
    """By part. Attention's scores and values over the causal half of the
    sequence (what the mask leaves; plain attention computes the whole
    square). The held experts at balance: `top_k * held / experts` slots
    a token."""
    d = dims(cfg)
    c, h = d["c"], d["heads"]
    proj = (c * d["q_rank"] + d["q_rank"] * h * (d["nope"] + d["rope"])
            + c * (d["kv_rank"] + d["rope"])
            + d["kv_rank"] * h * (d["nope"] + d["v"]) + h * d["v"] * c)
    scores = (d["seq"] + 1) / 2 * h * (d["nope"] + d["rope"] + d["v"])
    attention = 2 * (proj + scores)
    hc = 2 * 2 * d["n"] * c * (2 * d["n"] + d["n"] ** 2)
    shared = slot_flops(cfg)
    routed = slot_flops(cfg) * d["top_k"] * d["held"] / d["experts"]
    router = 2 * c * d["experts"]
    n_dense = d["dense_layers"]
    n_exp = d["layers"] - n_dense + d["mtp"]
    n_blocks = d["layers"] + d["mtp"]
    return {
        "attention": n_blocks * attention,
        "hyper_connections": n_blocks * hc,
        "dense_mlp": n_dense * 2 * 3 * c * d["dense_width"],
        "router": n_exp * router,
        "shared_expert": n_exp * shared,
        "held_experts": n_exp * routed,
        "mtp_projection": d["mtp"] * 2 * 2 * c * c,
        "head": (1 + d["mtp"]) * 2 * c * d["vocab"],
    }


def train_flops_per_step(cfg: Dict[str, Any], batch: int) -> float:
    """Forward, input gradient and weight gradient of every product (the
    embedding is a gather). Recomputed operations do not count."""
    tokens = batch * dims(cfg)["seq"]
    return 3.0 * tokens * sum(forward_flops_per_token(cfg).values())


def grouped_flops(cfg: Dict[str, Any], slots: float, passes: int) -> float:
    """The three grouped products over `slots` (token, slot) pairs,
    `passes` times a forward's worth (forward 1, backward 2; a
    rematerialised forward would be 1 more, and is not required work)."""
    return passes * slots * slot_flops(cfg)
