"""A sparse-expert language model with latent attention, hyper-connected
residual streams and one multi-token-prediction module, from a
`config.json`-style dict: the architecture of Xing4.0-29B-A4B
(`model_type` `xing4_0`, https://huggingface.co/XingChen-AGI/
Xing4.0-29B-A4B/blob/main/config.json).

No reference analog (SURVEY.md §5.7). `layer_table(config)` turns the
published keys into the program's layer table (`znicz/lm.py`): a token
embedding, `num_hidden_layers` blocks of which the first
`dense_layers_held` (default `first_k_dense_replace`) carry a dense MLP
and the rest an expert layer, and the head with its MTP module. A share
of a deployment is said with three more keys: `n_routed_experts` is then
the number of experts HELD here, with `published.n_routed_experts` the
router's width, `num_attention_heads` the heads held, `vocab_size` the
slice of the vocabulary (docs/SCALING.md). The benchmark's
`xing4_ep8.step` cell builds its program through this function.

Through the normal entry, fused only (the head owns its two losses):

    python -m veles_tpu veles_tpu/samples/xing4.py --fused

trains `TINY` on random token sequences (zero-egress environment).
"""

from __future__ import annotations

from typing import Any, Dict, List

from veles_tpu.config import root
from veles_tpu.loader.synthetic import RandomTokenLoader
from veles_tpu.znicz import lm  # noqa: F401 (registers the layer types)
from veles_tpu.znicz.standard_workflow import StandardWorkflow

#: a preset the CPU holds, every mechanism present: 2 residual streams, 8
#: experts of which the router picks 2, one dense and two expert layers
TINY: Dict[str, Any] = {
    "hidden_size": 64, "hc_mult": 2, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.0, "num_nextn_predict_layers": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8,
                     "type": "yarn"},
    "vocab_size": 64, "seq_len": 16,
}

root.xing4.config = TINY
root.xing4.loader.minibatch_size = 4
root.xing4.loader.n_train = 32
root.xing4.loader.n_validation = 8
root.xing4.decision.max_epochs = 3
root.xing4.decision.fail_iterations = 20
root.xing4.gd.learning_rate = 0.01
root.xing4.gd.gradient_moment = 0.9
root.xing4.gd.weights_decay = 0.0005


def layer_table(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The program's layer table of a `config.json`-style dict."""
    n = cfg["hc_mult"]
    held = cfg["n_routed_experts"]
    experts = cfg.get("published", {}).get("n_routed_experts", held)
    first = cfg.get("held_experts_first", 0)
    extra = {k: cfg[k] for k in ("init_std", "bias_update_speed")
             if k in cfg}
    block = {
        "streams": n, "n_heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "rope_theta": cfg["rope_theta"],
        "rope_scaling": dict(cfg["rope_scaling"]),
        "sinkhorn_iters": cfg["hc_sinkhorn_iters"], "hc_eps": cfg["hc_eps"],
        "hc_clamp": (cfg["mhc_h_res_clamp_min"],
                     cfg["mhc_h_res_clamp_max"]),
        "norm_eps": cfg["rms_norm_eps"], **extra}
    dense = dict(block, type="hc_block", ffn="dense",
                 width=cfg["intermediate_size"])
    sparse = dict(block, ffn="experts", width=cfg["moe_intermediate_size"],
                  n_experts=experts, held=(first, held),
                  top_k=cfg["num_experts_per_tok"],
                  routed_scaling=cfg["routed_scaling_factor"])
    n_dense = cfg.get("dense_layers_held", cfg["first_k_dense_replace"])
    init = {k: cfg[k] for k in ("init_std",) if k in cfg}
    head: Dict[str, Any] = {
        "type": "lm_head", "vocab": cfg["vocab_size"], "streams": n,
        "norm_eps": cfg["rms_norm_eps"], **init}
    for key in ("loss_chunk", "mtp_weight"):
        if key in cfg:
            head[key] = cfg[key]
    if cfg.get("num_nextn_predict_layers"):
        head["mtp"] = {k: v for k, v in sparse.items() if k != "streams"}
    return ([{"type": "token_embedding", "vocab": cfg["vocab_size"],
              "features": cfg["hidden_size"], "streams": n, **init}]
            + [dict(dense) for _ in range(n_dense)]
            + [dict(sparse, type="hc_block")
               for _ in range(cfg["num_hidden_layers"] - n_dense)]
            + [head])


class Xing4Workflow(StandardWorkflow):
    """embedding -> hyper-connected blocks -> head with MTP."""


def create_workflow() -> Xing4Workflow:
    cfg = root.xing4.config.to_dict() \
        if hasattr(root.xing4.config, "to_dict") else dict(root.xing4.config)
    lc = root.xing4.loader
    loader = RandomTokenLoader(
        vocab=cfg["vocab_size"], seq_len=cfg["seq_len"],
        n_train=lc.n_train, n_validation=lc.n_validation,
        minibatch_size=lc.minibatch_size, on_device=False)
    return Xing4Workflow(
        layers=layer_table(cfg), loader=loader, loss="softmax",
        n_classes=cfg["vocab_size"],
        decision_config=root.xing4.decision.to_dict(),
        gd_config=root.xing4.gd.to_dict(), name="Xing4Workflow")


def run(load, main):
    load(create_workflow)
    main()
