"""A sparse-expert language model with grouped-query attention over the
keys a learned indexer selects, from a `config.json`-style dict: the
language model of Keye-VL-2.0-30B-A3B (`model_type` `KeyeVL2`,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json;
the vision tower is no part of a text step).

No reference analog (SURVEY.md §5.7). `layer_table(config)` turns the
published keys into the program's layer table (`znicz/lm.py`): a token
embedding, `num_hidden_layers` alike blocks on a plain residual path
(`a = x + Attn(RMSNorm(x))`, `y = a + MoE(RMSNorm(a))`), the untied head.
Attention: `num_attention_heads` query heads over `num_key_value_heads`
key-value heads of `head_dim`, per-head QK-norm, rotary embedding over
the whole head; `sa_config` is the indexer (`indexer_num_heads` heads of
`indexer_head_dim`, one key head) whose scores select the `topk` keys a
query attends to (`ops/attention.py::indexed_attention`). Experts:
softmax over the router's outputs, the `num_experts_per_tok` highest,
gates renormalised (`norm_topk_prob`), no shared expert, the auxiliary
balance loss at `router_aux_loss_coef`; the indexer's own loss at
`index_loss_weight`. A share of a deployment is said with two keys:
`num_experts` is the number of experts HELD here, with
`published.num_experts` the router's width, and `vocab_size` the slice of
the vocabulary (docs/SCALING.md). The benchmark's `keye2_ep8.long16k`
cell builds its program through this function.

Through the normal entry, fused only (the head owns its loss):

    python -m veles_tpu veles_tpu/samples/keye2.py --fused

trains `TINY` on random token sequences (zero-egress environment).
"""

from __future__ import annotations

from typing import Any, Dict, List

from veles_tpu.config import root
from veles_tpu.loader.synthetic import RandomTokenLoader
from veles_tpu.znicz import lm  # noqa: F401 (registers the layer types)
from veles_tpu.znicz.standard_workflow import StandardWorkflow

#: a preset the CPU holds, every mechanism present: 4 query heads on 2
#: key-value heads, an indexer that keeps 8 of up to 32 keys, 8 experts of
#: which the router picks 2
TINY: Dict[str, Any] = {
    "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 8},
    "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001, "index_loss_weight": 1.0,
    "vocab_size": 64, "seq_len": 32, "query_block": 8,
}

root.keye2.config = TINY
root.keye2.loader.minibatch_size = 4
root.keye2.loader.n_train = 32
root.keye2.loader.n_validation = 8
root.keye2.decision.max_epochs = 3
root.keye2.decision.fail_iterations = 20
root.keye2.gd.learning_rate = 0.01
root.keye2.gd.gradient_moment = 0.9
root.keye2.gd.weights_decay = 0.0005


def layer_table(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The program's layer table of a `config.json`-style dict."""
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("gates that are not renormalised over the "
                         "selected experts are not implemented")
    sa = cfg["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer has one key head")
    held = cfg["num_experts"]
    init = {k: cfg[k] for k in ("init_std",) if k in cfg}
    block = {
        "type": "hc_block", "residual": "plain", "attention": "indexed",
        "n_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "index_topk": sa["topk"],
        "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
        "ffn": "experts", "scoring": "softmax", "shared": False,
        "width": cfg["moe_intermediate_size"],
        "n_experts": cfg.get("published", {}).get("num_experts", held),
        "held": (cfg.get("held_experts_first", 0), held),
        "top_k": cfg["num_experts_per_tok"], **init}
    for key in ("query_block", "key_bands", "grouped"):
        if key in cfg:
            block[key] = cfg[key]
    head: Dict[str, Any] = {
        "type": "lm_head", "vocab": cfg["vocab_size"],
        "norm_eps": cfg["rms_norm_eps"],
        "term_weights": {"balance": cfg["router_aux_loss_coef"],
                         "index": cfg["index_loss_weight"]}, **init}
    if "loss_chunk" in cfg:
        head["loss_chunk"] = cfg["loss_chunk"]
    return ([{"type": "token_embedding", "vocab": cfg["vocab_size"],
              "features": cfg["hidden_size"], **init}]
            + [dict(block) for _ in range(cfg["num_hidden_layers"])]
            + [head])


class Keye2Workflow(StandardWorkflow):
    """embedding -> blocks of indexed attention and experts -> head."""


def create_workflow() -> Keye2Workflow:
    cfg = root.keye2.config.to_dict() \
        if hasattr(root.keye2.config, "to_dict") else dict(root.keye2.config)
    lc = root.keye2.loader
    loader = RandomTokenLoader(
        vocab=cfg["vocab_size"], seq_len=cfg["seq_len"], n_targets=1,
        n_train=lc.n_train, n_validation=lc.n_validation,
        minibatch_size=lc.minibatch_size, on_device=False)
    return Keye2Workflow(
        layers=layer_table(cfg), loader=loader, loss="softmax",
        n_classes=cfg["vocab_size"],
        decision_config=root.keye2.decision.to_dict(),
        gd_config=root.keye2.gd.to_dict(), name="Keye2Workflow")


def run(load, main):
    load(create_workflow)
    main()
