"""A hybrid language model from a `config.json`-style dict: three Gated
DeltaNet (linear-attention) layers to one gated full-attention layer, many
small experts beside a gated shared expert. Qwen3-Next-80B-A3B-Instruct
(`model_type` `qwen3_next`,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).

No reference analog (SURVEY.md §5.7). `layer_table(config)` turns the
published keys into the program's layer table (`znicz/lm.py`): a token
embedding, `num_hidden_layers` blocks on a plain residual path
(`a = x + Mixer(N(x))`, `y = a + MoE(N(a))`, N the zero-centred RMSNorm),
the final norm and the untied head. The blocks are NOT alike: layer l
(from 0) mixes tokens by gated full attention if `(l + 1) %
full_attention_interval == 0`, else by a Gated DeltaNet
(`ops/linear_attention.py`): `linear_num_key_heads` key heads and
`linear_num_value_heads` value heads of `linear_key_head_dim` /
`linear_value_head_dim`, a causal depthwise convolution of
`linear_conv_kernel_dim` taps, computed `chunk` tokens at a time. Full
attention (`ops/attention.py::gated_attention`): `num_attention_heads`
query heads over `num_key_value_heads` key-value heads of `head_dim`, an
output gate from the query projection, zero-centred QK-norm, the rotary
embedding on the first `partial_rotary_factor` of a head. Experts: softmax
over the router's outputs, the `num_experts_per_tok` highest, gates
renormalised (`norm_topk_prob`), a shared expert behind a sigmoid gate of
its own, the auxiliary balance loss at `router_aux_loss_coef`. A share of a
deployment is said with two keys: `num_experts` is the number of experts
HELD here, with `published.num_experts` the router's width, and
`vocab_size` the slice of the vocabulary (docs/SCALING.md). The benchmark's
`qwen3next_ep16.seq8k` cell builds its program through this function.

Through the normal entry, fused only (the head owns its loss):

    python -m veles_tpu veles_tpu/samples/qwen3next.py --fused

trains `TINY` on random token sequences (zero-egress environment).
"""

from __future__ import annotations

from typing import Any, Dict, List

from veles_tpu.config import root
from veles_tpu.loader.synthetic import RandomTokenLoader
from veles_tpu.znicz import lm  # noqa: F401 (registers the layer types)
from veles_tpu.znicz.standard_workflow import StandardWorkflow

#: a preset the CPU holds, every mechanism present: one period of three
#: linear layers (2 key / 4 value heads) and one full layer (4 query / 2
#: key-value heads, a quarter of the head turned), 16 experts of which the
#: router picks 2 and 4 are held, a gated shared expert
TINY: Dict[str, Any] = {
    "hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 4, "published": {"num_experts": 16},
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001,
    "vocab_size": 64, "seq_len": 32, "chunk": 8, "scan_groups": 2,
}

root.qwen3next.config = TINY
root.qwen3next.loader.minibatch_size = 4
root.qwen3next.loader.n_train = 32
root.qwen3next.loader.n_validation = 8
root.qwen3next.decision.max_epochs = 3
root.qwen3next.decision.fail_iterations = 20
root.qwen3next.gd.learning_rate = 0.01
root.qwen3next.gd.gradient_moment = 0.9
root.qwen3next.gd.weights_decay = 0.0005


def is_full_attention(cfg: Dict[str, Any], layer: int) -> bool:
    """Whether layer `layer` (from 0) is a full-attention layer."""
    return (layer + 1) % cfg["full_attention_interval"] == 0


def layer_table(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The program's layer table of a `config.json`-style dict."""
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("gates that are not renormalised over the "
                         "selected experts are not implemented")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("dense layers among the expert layers are not "
                         "implemented")
    if cfg.get("shared_expert_intermediate_size",
               cfg["moe_intermediate_size"]) != cfg["moe_intermediate_size"]:
        raise ValueError("a shared expert of another width than the routed "
                         "ones is not implemented")
    held = cfg["num_experts"]
    init = {k: cfg[k] for k in ("init_std",) if k in cfg}
    block = {
        "type": "hc_block", "residual": "plain", "norm": "zero_centred",
        "norm_eps": cfg["rms_norm_eps"],
        "ffn": "experts", "scoring": "softmax", "shared": True,
        "shared_gate": True, "width": cfg["moe_intermediate_size"],
        "n_experts": cfg.get("published", {}).get("num_experts", held),
        "held": (cfg.get("held_experts_first", 0), held),
        "top_k": cfg["num_experts_per_tok"], **init}
    if "grouped" in cfg:
        block["grouped"] = cfg["grouped"]
    full = {
        **block, "attention": "gated",
        "n_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "rotary_dim": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "rope_theta": cfg["rope_theta"]}
    linear = {
        **block, "attention": "gated_delta",
        "n_heads": cfg["linear_num_value_heads"],
        "key_heads": cfg["linear_num_key_heads"],
        "value_heads": cfg["linear_num_value_heads"],
        "key_dim": cfg["linear_key_head_dim"],
        "value_dim": cfg["linear_value_head_dim"],
        "conv_kernel": cfg["linear_conv_kernel_dim"]}
    for key in ("chunk", "scan_groups"):
        if key in cfg:
            linear[key] = cfg[key]
    head: Dict[str, Any] = {
        "type": "lm_head", "vocab": cfg["vocab_size"],
        "norm": "zero_centred", "norm_eps": cfg["rms_norm_eps"],
        "term_weights": {"balance": cfg["router_aux_loss_coef"]}, **init}
    if "loss_chunk" in cfg:
        head["loss_chunk"] = cfg["loss_chunk"]
    return ([{"type": "token_embedding", "vocab": cfg["vocab_size"],
              "features": cfg["hidden_size"], **init}]
            + [dict(full if is_full_attention(cfg, i) else linear)
               for i in range(cfg["num_hidden_layers"])]
            + [head])


class Qwen3NextWorkflow(StandardWorkflow):
    """embedding -> blocks of a linear or a full mixer and experts -> head."""


def create_workflow() -> Qwen3NextWorkflow:
    node = root.qwen3next
    cfg = node.config.to_dict() if hasattr(node.config, "to_dict") \
        else dict(node.config)
    lc = node.loader
    loader = RandomTokenLoader(
        vocab=cfg["vocab_size"], seq_len=cfg["seq_len"], n_targets=1,
        n_train=lc.n_train, n_validation=lc.n_validation,
        minibatch_size=lc.minibatch_size, on_device=False)
    return Qwen3NextWorkflow(
        layers=layer_table(cfg), loader=loader, loss="softmax",
        n_classes=cfg["vocab_size"],
        decision_config=node.decision.to_dict(),
        gd_config=node.gd.to_dict(), name="Qwen3NextWorkflow")


def run(load, main):
    load(create_workflow)
    main()
