"""The plain reference of a `xing4_lm` training step, and the comparison
with it.

Forward, both losses, gradients by `jax.grad`, momentum SGD with weight
decay and the selection-bias rule: straightforward `jax.numpy` in float32
under `jax.default_matmul_precision("highest")`, one sequence at a time
(a block of the step's tokens: attention does not cross sequences and
both losses are sums over tokens). It imports nothing of `veles_tpu` and
takes nothing the program has made. Experts are looped over with a mask:
no sorting, no kernels, no capacity. `precision="float8"` is the CONTROL
(`reference.py` describes it): every matrix product reads its operands
in e4m3 and passes its gradient back in e5m2.

The model (Xing4.0-29B-A4B, `model_type` `xing4_0`; each inference from
the config's key names is listed under `assumed` in the configuration
file). Per token, C = `hidden_size`, n = `hc_mult`; the residual state is
X in R^{n x C}. X_0 is the token's embedding copied to the n streams;
after the last block the streams are summed, then RMSNorm, then the head.

- Hyper-connection around every sub-layer F (attention, MLP or expert
  layer, each with its own parameters; mHC, arXiv:2512.24880, after
  hyper-connections, arXiv:2409.19606). x~ = RMSNorm(vec(X)) in R^{nC}
  (no learned scale). Hpre~ = a_pre (x~ P_pre) + b_pre (1 x n), Hpost~ =
  a_post (x~ P_post) + b_post (1 x n), Hres~ = a_res mat(x~ P_res) + b_res
  (n x n). Hpre = sigmoid(Hpre~), Hpost = 2 sigmoid(Hpost~), Hres =
  SK(clip(Hres~, mhc_h_res_clamp_min, mhc_h_res_clamp_max)); SK(M): M <-
  exp(M), then `hc_sinkhorn_iters` times: each row divided by (its sum +
  `hc_eps`), each column by (its sum + `hc_eps`). X <- Hres X + Hpost^T
  F(Hpre X); F holds its own pre-RMSNorm.
- Latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1). cQ =
  RMSNorm(h W_DQ); per head [q_nope; q_rope] = cQ W_UQ; [cKV; k_rope] =
  h W_DKV, cKV <- RMSNorm(cKV), k_rope shared by all heads; per head
  [k_nope; v] = cKV W_UKV; rotary on q_rope and k_rope with yarn
  frequencies (the cos/sin factor yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)); scores (q_nope . k_nope + q_rope .
  k_rope) (nope + rope)^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1;
  causal softmax; out = concat(P v) W_O. No biases. Given a share of the
  heads (`W_UQ`, `W_UKV`, `W_O` cut to them), the result is that share's
  part of the sum over heads.
- Dense MLP: (silu(h W_g) * (h W_u)) W_d. Expert layer (DeepSeek-V3,
  arXiv:2412.19437, sections 2.1.2 and 4.2, `noaux_tc`): s = sigmoid(h
  W_r); the `num_experts_per_tok` largest of s + b are selected; g_e =
  `routed_scaling_factor` s_e / (sum of the selected s + 1e-20); y = sum
  over the selected e of g_e SwiGLU_e(h) + SwiGLU_shared(h). Only the
  experts `held_first .. held_first + count` are computed: what the
  others would add is left out. b is no gradient leaf: after each step
  b_e <- b_e + u sign(mean load - load_e), loads counted over all experts
  on the step's tokens.
- Multi-token prediction (DeepSeek-V3, section 2.2; one module): h'_i =
  W_M [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))], h_i the trunk's summed
  streams before the final norm; one block of the expert kind, entered
  and read out as the trunk is; a final norm of its own; the SHARED
  embedding and head; it predicts t_{i+2}. loss = CE_main + lambda CE_MTP,
  each a mean over the step's tokens.
"""

from __future__ import annotations

import functools
import json
import math
import re
import time
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import xing4_ops_count as counts
from benchmark.reference import _fp8_act, _fp8_weight, _worst_leaf, \
    leaf_norms, worst_leaf_gap


#: tokens whose logits exist at a time in the head's loss
HEAD_BLOCK_ROWS = 1024


# -- the layers ---------------------------------------------------------------

class Precision:
    """How a matrix product reads its operands."""

    def __init__(self, name: str) -> None:
        if name not in ("float32", "float8"):
            raise ValueError(f"unknown precision {name!r}")
        self.low = name == "float8"

    def mm(self, x, w):
        if self.low:
            return _fp8_act(x) @ _fp8_weight(w)
        return x @ w


def rms_norm(x, scale, eps: float):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """Closed form, one dimension at a time: frequency i of `dim / 2` is
    theta^(-2i/dim), divided by `factor` where the ramp is 1."""
    def correction_dim(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        base = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(base * (1.0 - ramp) + base / factor * ramp)
    return np.asarray(out, np.float32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def rope(x, positions, inv_freq, factor: float):
    """x (S, ..., dim): pairs (i, i + dim/2) rotated by position x
    frequency i."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(cfg: Dict[str, Any], p: Dict[str, Any], h, prec: Precision,
              prefix: str = ""):
    """One sequence h (S, C) through the heads `p` holds (however many)."""
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    eps, rs = cfg["rms_norm_eps"], cfg["rope_scaling"]
    g = lambda name: p[prefix + "attn_" + name]  # noqa: E731
    s = h.shape[0]
    heads = g("w_o").shape[0] // vd
    h = rms_norm(h, g("norm"), eps)
    c_q = rms_norm(prec.mm(h, g("w_dq")), g("q_norm"), eps)
    q = prec.mm(c_q, g("w_uq")).reshape(s, heads, nope + rp)
    dkv = prec.mm(h, g("w_dkv"))
    c_kv = rms_norm(dkv[:, :cfg["kv_lora_rank"]], g("kv_norm"), eps)
    kv = prec.mm(c_kv, g("w_ukv")).reshape(s, heads, nope + vd)
    inv_freq = jnp.asarray(yarn_inv_freq(
        rp, cfg["rope_theta"], rs["factor"],
        rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"]))
    factor = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    pos = jnp.arange(s)
    q_rope = rope(q[..., nope:], pos, inv_freq, factor)
    k_rope = rope(dkv[:, cfg["kv_lora_rank"]:], pos, inv_freq, factor)
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (nope + rp) ** -0.5 * m * m
    # the scores are matrix products too
    qa = _fp8_act if prec.low else (lambda t: t)
    scores = (jnp.einsum("qhd,khd->hqk", qa(q[..., :nope]),
                         qa(kv[..., :nope]))
              + jnp.einsum("qhd,kd->hqk", qa(q_rope), qa(k_rope))) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", qa(probs), qa(kv[..., nope:]))
    return prec.mm(out.reshape(s, heads * vd), g("w_o"))


def swiglu(x, w_gate, w_up, w_down, prec: Precision):
    return prec.mm(jax.nn.silu(prec.mm(x, w_gate)) * prec.mm(x, w_up),
                   w_down)


def route(cfg: Dict[str, Any], w_router, h, bias, prec: Precision):
    """(selected experts (T, k), their gates (T, k))."""
    s = jax.nn.sigmoid(prec.mm(h, w_router))
    _, idx = lax.top_k(lax.stop_gradient(s + bias),
                       cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, axis=1)
    gates = cfg["routed_scaling_factor"] * picked \
        / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, gates


def expert_layer(cfg: Dict[str, Any], p: Dict[str, Any], h, bias,
                 held_first: int, prec: Precision, prefix: str = "",
                 shared: bool = True):
    """h (T, C) -> (y, selected experts (T, k)). The held experts are
    `held_first ..` as many as `p` holds; `shared=False` leaves the shared
    expert out (a share of a layer whose shared expert is counted
    elsewhere)."""
    g = lambda name: p[prefix + "moe_" + name]  # noqa: E731
    h = rms_norm(h, g("norm"), cfg["rms_norm_eps"])
    idx, gates = route(cfg, g("w_router"), h, bias, prec)
    y = jnp.zeros_like(h)
    for j in range(g("experts_gate").shape[0]):
        gate = jnp.where(idx == held_first + j, gates, 0.0).sum(axis=-1)
        y = y + gate[:, None] * swiglu(h, g("experts_gate")[j],
                                       g("experts_up")[j],
                                       g("experts_down")[j], prec)
    if shared:
        y = y + swiglu(h, g("shared_gate"), g("shared_up"),
                       g("shared_down"), prec)
    return y, idx


def dense_mlp(cfg: Dict[str, Any], p: Dict[str, Any], h, prec: Precision):
    h = rms_norm(h, p["mlp_norm"], cfg["rms_norm_eps"])
    return swiglu(h, p["mlp_w_gate"], p["mlp_w_up"], p["mlp_w_down"], prec)


def sinkhorn(m, iters: int, eps: float):
    """m (..., n, n): rows are axis -2, columns axis -1."""
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def hyper_connection(cfg: Dict[str, Any], p: Dict[str, Any], prefix: str,
                     x, f, prec: Precision):
    """x (T, n, C) -> Hres X + Hpost^T F(Hpre X); `f` maps (T, C) to
    ((T, C), anything)."""
    t, n, c = x.shape
    g = lambda name: p[prefix + name]  # noqa: E731
    xn = rms_norm(x.reshape(t, n * c), None, cfg["rms_norm_eps"])
    pre = g("a_pre") * prec.mm(xn, g("p_pre")) + g("b_pre")
    post = g("a_post") * prec.mm(xn, g("p_post")) + g("b_post")
    res = g("a_res") * prec.mm(xn, g("p_res")).reshape(t, n, n) + g("b_res")
    h_pre = jax.nn.sigmoid(pre)
    h_post = 2.0 * jax.nn.sigmoid(post)
    h_res = sinkhorn(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                              cfg["mhc_h_res_clamp_max"]),
                     cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    y, extra = f(jnp.einsum("tn,tnc->tc", h_pre, x))
    return (jnp.einsum("tij,tjc->tic", h_res, x)
            + h_post[:, :, None] * y[:, None, :]), extra


def block(cfg: Dict[str, Any], p: Dict[str, Any], kind: str, x, bias,
          held_first: int, prec: Precision, prefix: str = ""):
    """x (S, n, C) of one sequence -> (x, selected experts or None)."""
    x, _ = hyper_connection(
        cfg, p, prefix + "hca_", x,
        lambda h: (jax.checkpoint(
            lambda p_, h_: attention(cfg, p_, h_, prec, prefix))(p, h),
            None), prec)
    if kind == "dense":
        return hyper_connection(
            cfg, p, prefix + "hcm_", x,
            lambda h: (dense_mlp(cfg, p, h, prec), None), prec)
    return hyper_connection(
        cfg, p, prefix + "hcm_", x,
        lambda h: expert_layer(cfg, p, h, bias, held_first, prec, prefix),
        prec)


def sequence_losses(cfg: Dict[str, Any], params, ids, targets,
                    biases: Sequence[Any], held_first: int,
                    prec: Precision):
    """One sequence: ids (S,), targets (S, 2). Returns (sum of the main
    cross-entropy over its tokens, sum of the MTP cross-entropy, the
    selected experts of each expert layer: `counts.expert_layers`)."""
    d = counts.dims(cfg)
    n, eps = d["n"], cfg["rms_norm_eps"]
    table = params[0]["weights"]
    x = jnp.repeat(table[ids][:, None, :], n, axis=1)
    # the blocks in two runs, each recomputed in the backward pass from its
    # input, and every block again within its run: memory, not meaning
    # (`jax.checkpoint` changes no number)
    kinds = counts.block_kinds(d)
    layer_biases, b = [], iter(biases)
    for kind in kinds:
        layer_biases.append(next(b) if kind == "experts" else None)

    def run_of(lo: int, hi: int):
        def go(ps, x_, bs):
            out = []
            for p, kind, bias in zip(ps, kinds[lo:hi], bs):
                x_, idx = jax.checkpoint(
                    lambda p_, xx, b_, kind=kind: block(
                        cfg, p_, kind, xx, b_, held_first, prec))(
                    p, x_, bias)
                out.append(idx)
            return x_, out
        return jax.checkpoint(go)

    picked = []
    half = (len(kinds) + 1) // 2
    for lo, hi in ((0, half), (half, len(kinds))):
        x, idxs = run_of(lo, hi)(params[1 + lo:1 + hi], x,
                                 layer_biases[lo:hi])
        picked += [i for i in idxs if i is not None]
    head = params[-1]
    trunk = x.sum(axis=1)

    def ce_sum(h, labels):
        """In blocks of tokens, each recomputed in the backward pass: the
        logits of a whole sequence, their softmax and its gradient would
        be a gigabyte of the little room the reference has."""
        @jax.checkpoint
        def block_sum(hb, yb):
            logp = jax.nn.log_softmax(prec.mm(hb, head["weights"]), axis=-1)
            return -jnp.take_along_axis(logp, yb[:, None], 1)[:, 0].sum()
        rows = min(HEAD_BLOCK_ROWS, h.shape[0])
        return sum(block_sum(h[lo:lo + rows], labels[lo:lo + rows])
                   for lo in range(0, h.shape[0], rows))

    main = ce_sum(rms_norm(trunk, head["final_norm"], eps), targets[:, 0])
    if not d["mtp"]:
        return main, jnp.zeros(()), picked
    joined = jnp.concatenate(
        [rms_norm(trunk, head["mtp_norm_h"], eps),
         rms_norm(table[targets[:, 0]], head["mtp_norm_e"], eps)], axis=-1)
    x2 = jnp.repeat(prec.mm(joined, head["mtp_w_proj"])[:, None, :], n,
                    axis=1)
    x2, idx = jax.checkpoint(
        lambda p_, x_, b_: block(cfg, p_, "experts", x_, b_, held_first,
                                 prec, "mtp_"))(head, x2, next(b))
    picked.append(idx)
    mtp = ce_sum(rms_norm(x2.sum(axis=1), head["mtp_final_norm"], eps),
                 targets[:, 1])
    return main, mtp, picked


# -- the first steps ------------------------------------------------------------

def loads_of(idx, n_experts: int):
    return (idx[..., None] == jnp.arange(n_experts)).sum(axis=(0, 1))


def bias_step(bias, load, speed: float):
    """b_e <- b_e + u sign(mean load - load_e)."""
    load = load.astype(jnp.float32)
    return bias + speed * jnp.sign(load.mean() - load)


@functools.lru_cache(maxsize=4)
def _step_programs(cfg_json: str, held_first: int, precision: str):
    """(the gradient of one sequence added to a running sum, one leaf's
    update), jitted once per configuration and precision: a process that
    follows several seeds compiles them once."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    mu, wd = opt["gradient_moment"], opt["weights_decay"]
    lam = cfg["mtp_loss_weight"]
    prec = Precision(precision)

    def seq_loss(p, ids, targets, biases, n_tokens):
        main, mtp, picked = sequence_losses(cfg, p, ids, targets, biases,
                                            held_first, prec)
        return (main + lam * mtp) / n_tokens, (main / n_tokens,
                                               mtp / n_tokens, picked)

    def more(acc, p, ids, targets, biases, n_tokens):
        out, g = jax.value_and_grad(seq_loss, has_aux=True)(
            p, ids, targets, biases, n_tokens)
        return out, jax.tree.map(jnp.add, acc, g)

    def update(p, g, v, rate):
        """v <- mu v - rate (g + wd w);  w <- w + v."""
        v = mu * v - rate * (g + wd * p)
        return p + v, v

    return (jax.jit(more, donate_argnums=(0,)),
            jax.jit(update, donate_argnums=(0, 1)))


def unload() -> None:
    """Drop the compiled programs of `_step_programs`: loaded, they keep
    their temporaries reserved on the device, and a process that follows
    several seeds (`read_limits.py`) builds the next seed's program, which
    fills the chip, after this reference has run."""
    _step_programs.cache_clear()
    jax.clear_caches()


def _diff_norms(theirs, grads) -> Dict[str, float]:
    """Per leaf, the norm of `theirs` less the reference's first gradient
    `grads` (device arrays, read a leaf at a time). `theirs` is a tree
    like the parameters, host arrays will do; its leaves are set to None
    as they are read: the host is as full as the chip."""
    out = {}
    for i, layer in enumerate(grads):
        for name, g in layer.items():
            out[f"{i}.{name}"] = float(np.linalg.norm(
                np.asarray(theirs[i][name], np.float32).ravel()
                - np.asarray(g, np.float32).ravel()))
            theirs[i][name] = None
    return out


def reference_steps(cfg: Dict[str, Any], params0, batches, *,
                    first_params=None, held_first: int = 0,
                    precision: str = "float32",
                    first_grad_of_program=None, first_grads_of=None,
                    keep_first_grad: bool = False) -> Dict[str, Any]:
    """Follow the program's first steps from `params0` (device arrays,
    used up: the updates are made in place; `first_params` is the same on
    the host, where the caller has it already), zero velocity and zero
    selection bias: one (ids (B, S), targets (B, S, 2)) per step.
    Returns per step `loss` (total), `loss_main`, `loss_mtp` and
    `picked` (per expert layer the selected experts (B*S, k), on the
    host); the per-leaf norm of the first gradient; of the parameters'
    change after the last step; the biases after the last step; the
    `seconds` each part took. Given the program's first gradient (a tree
    like the parameters, used up as `_diff_norms` says), also the
    per-leaf norm of its difference from the reference's,
    `grad_diff_norm`; `first_grads_of` is a dict of more such trees by
    name (a control's beside the program's: one pass of the reference
    reads both), whose norms go to `grad_diff_norm_of[name]`; with
    `keep_first_grad` the first gradient itself, on the host (the
    control's, to be put in the program's place)."""
    opt, d = cfg["optimizer"], counts.dims(cfg)
    lr, bias_mult = opt["learning_rate"], opt["learning_rate_bias"]
    speed = cfg["bias_update_speed"]
    n_layers = len(counts.expert_layers(cfg))
    seconds = dict.fromkeys(("gradients", "first_gradient_read",
                             "updates", "host_copies"), 0.0)

    def timed(name: str, t0: float) -> None:
        seconds[name] += time.perf_counter() - t0

    more, update = _step_programs(
        json.dumps(cfg, sort_keys=True), held_first, precision)

    with jax.default_matmul_precision("highest"):
        # the chip has room for the parameters, one gradient and a block's
        # activations beside what the timed program leaves reserved: the
        # velocity and the first parameters stay on the host, and the
        # update goes leaf by leaf (a leaf of one dimension, which here is
        # a norm scale or a hyper-connection's scalar or bias, at
        # `learning_rate_bias` times the rate)
        t0 = time.perf_counter()
        if first_params is None:
            first_params = jax.device_get(params0)
        timed("host_copies", t0)
        params = [dict(layer) for layer in params0]
        vel: List[Dict[str, Any]] = [dict.fromkeys(layer)
                                     for layer in first_params]
        biases = [jnp.zeros((d["experts"],), jnp.float32)] * n_layers
        out: Dict[str, Any] = {"loss": [], "loss_main": [], "loss_mtp": [],
                               "picked": []}
        for s, (ids, targets) in enumerate(batches):
            t0 = time.perf_counter()
            n_tokens = float(ids.shape[0] * ids.shape[1])
            grads = jax.tree.map(jnp.zeros_like, tuple(params))
            sums, picked = np.zeros(3), []
            for b in range(ids.shape[0]):
                (tot, (main, mtp, idx)), grads = more(
                    grads, tuple(params), ids[b], targets[b], biases,
                    n_tokens)
                sums += [float(tot), float(main), float(mtp)]
                picked.append([np.asarray(i) for i in idx])
            picked = [np.concatenate(layer) for layer in zip(*picked)]
            for name, v in zip(("loss", "loss_main", "loss_mtp"), sums):
                out[name].append(float(v))
            out["picked"].append(picked)
            timed("gradients", t0)
            if s == 0:
                t0 = time.perf_counter()
                out["grad_norm"] = leaf_norms(grads)
                if first_grad_of_program is not None:
                    out["grad_diff_norm"] = _diff_norms(
                        first_grad_of_program, grads)
                out["grad_diff_norm_of"] = {
                    name: _diff_norms(theirs, grads)
                    for name, theirs in (first_grads_of or {}).items()}
                if keep_first_grad:
                    out["first_grad"] = jax.device_get(grads)
                timed("first_gradient_read", t0)
            t0 = time.perf_counter()
            last = s == len(batches) - 1
            for i, layer in enumerate(params):
                for name in layer:
                    rate = lr * (bias_mult if layer[name].ndim == 1 else 1.0)
                    v = vel[i][name]        # from rest: zeros, made there
                    layer[name], v = update(
                        layer[name], grads[i][name],
                        jnp.zeros_like(layer[name]) if v is None else v,
                        rate)
                    # (nobody reads the velocity after the last step)
                    vel[i][name] = None if last else np.asarray(v)
            biases = [bias_step(b, jnp.asarray(
                loads_of(idx, d["experts"])), speed)
                for b, idx in zip(biases, picked)]
            timed("updates", t0)
        t0 = time.perf_counter()
        out["dparam_norm"] = {
            f"{i}.{name}": float(np.linalg.norm(
                (np.asarray(a) - first_params[i][name]).ravel()))
            for i, layer in enumerate(params) for name, a in layer.items()}
        timed("host_copies", t0)
        out["bias"] = [np.asarray(b) for b in biases]
        out["seconds"] = seconds
        for layer in params:
            for a in layer.values():
                a.delete()
        return out


# -- the comparison that decides `correct` ----------------------------------------

def route_mismatch(prog_picked, ref_picked, n_experts: int) -> float:
    """Share of the program's selected (token, expert) pairs that the
    reference did not select: both (T, k), the order within a token
    aside."""
    def member(idx):
        m = np.zeros((idx.shape[0], n_experts), bool)
        np.put_along_axis(m, np.asarray(idx), True, axis=1)
        return m
    a, b = member(prog_picked), member(ref_picked)
    return float((a & ~b).sum()) / float(a.sum())


_HC_SMALL = re.compile(r"^(.*hc[am])_[ab]_(?:pre|post|res)$")


def pooled(norms: Dict[str, float]) -> Dict[str, float]:
    """Per-leaf norms with the scalars and biases of one hyper-connection
    (`a_pre`, `a_post`, `a_res`, `b_pre`, `b_post`, `b_res`: 3 + 2 n + n^2
    numbers) read together as one leaf, `<unit>.<hca|hcm>_ab`. A gradient
    of ONE number is a sum over the step's tokens of terms of either
    sign, which comes out near zero on some seeds by chance, and a
    relative error over it is then as large as one likes: read leaf by
    leaf, the worst leaf of a sound bfloat16 step was one of these on
    most seeds and read anything from 0.1 to 0.7 (PERF.md section 2).
    Together they are a vector whose norm does not vanish by chance."""
    squares: Dict[str, float] = {}
    for name, v in norms.items():
        m = _HC_SMALL.match(name)
        key = m.group(1) + "_ab" if m else name
        squares[key] = squares.get(key, 0.0) + v * v
    return {k: math.sqrt(v) for k, v in squares.items()}


def tables(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """What `compare` reads, leaf by leaf and step by step (the selected
    experts aside): what a limit is set from."""
    return {
        "loss_main": [prog["loss_main"], ref["loss_main"]],
        "loss_mtp": [prog["loss_mtp"], ref["loss_mtp"]],
        "grad_norm": [prog["grad_norm"], ref["grad_norm"]],
        "grad_diff_norm": ref["grad_diff_norm"],
        "dparam_norm": [prog["dparam_norm"], ref["dparam_norm"]],
        "bias": [[np.asarray(b).tolist() for b in prog["bias"]],
                 [np.asarray(b).tolist() for b in ref["bias"]]],
    }


def compare(cfg: Dict[str, Any], prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared, beside its limit. `prog` holds what the
    session read of the timed object: per step `loss_main`, `loss_mtp`
    and `picked`; `grad_norm`, `dparam_norm`, `bias`, `slots_dropped`."""
    d = counts.dims(cfg)
    layers = counts.expert_layers(cfg)
    loss_gap, at = 0.0, "-"
    for name in ("loss_main", "loss_mtp"):
        for s, (p, r) in enumerate(zip(prog[name], ref[name])):
            gap = abs(p - r) / max(abs(r), 1e-30) \
                if math.isfinite(p) else math.inf
            if gap >= loss_gap:
                loss_gap, at = gap, f"{name} step {s}"
    g_ref = pooled(ref["grad_norm"])
    g_gap, g_leaf = worst_leaf_gap(pooled(prog["grad_norm"]), g_ref)
    d_gap, d_leaf = worst_leaf_gap(pooled(prog["dparam_norm"]),
                                   pooled(ref["dparam_norm"]))
    e_gap, e_leaf = _worst_leaf(pooled(ref["grad_diff_norm"]), g_ref)
    # the head's weight gradient, h^T (p - y), is linear in a rounding of
    # the products before it, with no gate or routing choice behind it:
    # the number that tells the precisions apart (reference.py)
    head = f"{max(int(n.split('.')[0]) for n in ref['grad_norm'])}.weights"
    h_gap = ref["grad_diff_norm"][head] / max(ref["grad_norm"][head], 1e-30)
    r_gap, r_at = 0.0, "-"
    for s, (pp, rp) in enumerate(zip(prog["picked"], ref["picked"])):
        for name, a, b in zip(layers, pp, rp):
            gap = route_mismatch(a, b, d["experts"])
            if gap >= r_gap:
                r_gap, r_at = gap, f"{name} step {s}"
    # in steps of the update speed: the share of (expert, step) signs on
    # which the two disagree shows as a gap of 2 on that expert
    b_gap, b_at = 0.0, "-"
    for name, a, b in zip(layers, prog["bias"], ref["bias"]):
        gap = float(np.abs(np.asarray(a) - np.asarray(b)).mean()
                    / cfg["bias_update_speed"])
        if gap >= b_gap:
            b_gap, b_at = gap, name
    rows = [
        {"name": "loss_rel_gap", "value": loss_gap, "at": at},
        {"name": "grad_norm_gap", "value": g_gap, "at": g_leaf},
        {"name": "grad_rel_err", "value": e_gap, "at": e_leaf},
        {"name": "head_grad_rel_err", "value": h_gap, "at": head},
        {"name": "dparam_norm_gap", "value": d_gap, "at": d_leaf},
        {"name": "route_mismatch_share", "value": r_gap, "at": r_at},
        {"name": "balance_bias_gap", "value": b_gap, "at": b_at},
        {"name": "slots_dropped", "value": float(prog["slots_dropped"]),
         "at": "first steps and window"},
    ]
    for row in rows:
        row["limit"] = limits[row["name"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
