"""The plain reference of a `keye2_lm` training step, and the comparison
with it.

Forward, the three terms of the loss, gradients by `jax.grad`, momentum
SGD with weight decay: straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time. It
imports nothing of `veles_tpu` and takes nothing the program has made.
Experts are looped over with a mask: no sorting, no kernels, no capacity.
The selection is a plain `lax.top_k` of the index scores. `precision=
"float8"` is the CONTROL (`reference.py` describes it): every matrix
product reads its operands in e4m3 and passes its gradient back in e5m2.

The model (the language model of Keye-VL-2.0-30B-A3B, `model_type`
`KeyeVL2`; each inference from the config's key names is listed under
`assumed` in the configuration file). T tokens of one sequence, positions
from 0; `h` is the RMS-normed input of a sub-layer.

- Block: a = x + Attn(RMSNorm(x)), y = a + MoE(RMSNorm(a)); after the
  last block RMSNorm, then the untied head over the held ids.
- Attention: q = h Wq (H heads of D), k = h Wk, v = h Wv (Hkv heads of
  D), no biases; q and k through an RMSNorm over the head's D with a
  learned scale; rotary embedding over the whole head, two-halves layout,
  theta `rope_theta`. Query head j reads key-value head j // (H / Hkv).
- Indexer (DeepSeek-V3.2-Exp's lightning indexer; its input is
  stop_gradient(h)): qI = rope(h WqI) (Hi heads of Di), kI =
  rope(LayerNorm(h WkI)) (one head), w = h Ww Hi^-1/2 Di^-1/2;
  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <= t. S_t = the
  `topk` keys of highest I[t, :] among s <= t, all of them while t < topk.
- Attend: o[t, j] = sum_{s in S_t} softmax_{s in S_t}(q[t, j] .
  k[s, j // g] / sqrt(D)) v[s, j // g]; Attn = concat_j(o) Wo.
- Index loss: p[t, s] = the mean over the heads of the attention
  probabilities on S_t, stop_gradient; L_I = mean_t sum_{s in S_t} p (log
  p - log softmax_{S_t}(I[t, .])). The main model gets no gradient from
  L_I, the indexer none from the language-model loss.
- Experts: r = softmax(h Wr) over all experts; the `num_experts_per_tok`
  highest; gates r_e / sum of the selected; MoE = sum over the selected
  experts HELD here of gate x SwiGLU_e(h). Balance loss of a layer L_B =
  E sum_e (slots_e / T) mean_t r[t, e].
- Loss: next-token cross-entropy over the held ids +
  `router_aux_loss_coef` mean_l L_B + `index_loss_weight` sum_l L_I.

Departures from the source, each for room and none for meaning: a layer
walks its queries a block of `QUERY_BLOCK` at a time, every block against
ALL the keys under the causal mask (the source tiles both by `q_chunk_size`
/ `kv_chunk_size`), in a loop XLA sees once (`lax.map`); every layer and
every block is recomputed in the backward pass (`jax.checkpoint` changes
no number); a step's selection is found by a forward pass of its own and
READ by the pass that is differentiated (the same `top_k` of the same
numbers, once instead of three times); the head's logits exist
`HEAD_BLOCK_ROWS` tokens at a time.
"""

from __future__ import annotations

import functools
import json
import math
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import keye2_ops_count as counts
from benchmark.reference import _fp8_act, _fp8_weight, _worst_leaf, \
    leaf_norms, worst_leaf_gap
# (what the two language-model references do alike, stated once)
from benchmark.xing4_reference import _diff_norms, route_mismatch, swiglu

#: queries whose scores exist at a time, against every key
QUERY_BLOCK = 256
#: tokens whose logits exist at a time in the head's loss
HEAD_BLOCK_ROWS = 1024
#: the three terms of the loss, as the rows and tables name them
TERMS = ("loss_ce", "loss_balance", "loss_index")


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

    def act(self, x):
        """An operand of a product of two activations (scores, values)."""
        return _fp8_act(x) if self.low else x


def rms_norm(x, scale, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_norm(x, scale, bias, eps: float):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) \
        * scale + bias


def rope(x, theta: float):
    """x (S, ..., dim): pairs (i, i + dim/2) rotated by position x
    theta^(-2i/dim)."""
    dim = x.shape[-1]
    half = dim // 2
    inv_freq = jnp.asarray([theta ** (-2.0 * i / dim) for i in range(half)],
                           jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(cfg: Dict[str, Any], p: Dict[str, Any], x, prec: Precision,
              given=None):
    """One sequence x (S, C) -> (Attn (S, C), the layer's index loss, the
    selection packed 8 keys a byte (S, S / 8)). `given` is that selection
    from an earlier pass over the same numbers: it is then read, not
    searched for again (`reference_steps` says why)."""
    d = counts.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads, kvh, hd = d["heads"], d["kv_heads"], d["d"]
    g = lambda name: p["attn_" + name]  # noqa: E731
    s = x.shape[0]
    h = rms_norm(x, g("norm"), eps)
    q = rope(rms_norm(prec.mm(h, g("w_q")).reshape(s, heads, hd),
                      g("q_norm"), eps), theta)
    k = rope(rms_norm(prec.mm(h, g("w_k")).reshape(s, kvh, hd),
                      g("k_norm"), eps), theta)
    v = prec.mm(h, g("w_v")).reshape(s, kvh, hd)
    hs = lax.stop_gradient(h)
    qi = rope(prec.mm(hs, g("idx_w_q")).reshape(
        s, d["index_heads"], d["index_dim"]), theta)
    ki = rope(layer_norm(prec.mm(hs, g("idx_w_k")), g("idx_k_norm"),
                         g("idx_k_bias"), eps), theta)
    w = prec.mm(hs, g("idx_w_w")) \
        * (d["index_heads"] ** -0.5 * d["index_dim"] ** -0.5)
    k_all = jnp.repeat(k, heads // kvh, axis=1)        # a key head a query head
    v_all = jnp.repeat(v, heads // kvh, axis=1)
    topk = min(d["topk"], s)
    rows = min(QUERY_BLOCK, s)
    if s % rows:
        raise ValueError(f"{s} queries do not divide into blocks of {rows}")

    @jax.checkpoint
    def block(qb, qib, wb, pos, bits):
        causal = jnp.arange(s)[None, :] <= pos[:, None]
        index = jnp.einsum("qh,qhk->qk", wb, jax.nn.relu(jnp.einsum(
            "qhd,kd->qhk", prec.act(qib), prec.act(ki))))
        if given is None:
            _, idx = lax.top_k(jnp.where(causal, lax.stop_gradient(index),
                                         -jnp.inf), topk)
            picked = jnp.zeros(causal.shape, bool).at[
                jnp.arange(rows)[:, None], idx].set(True) & causal
        else:
            picked = jnp.unpackbits(bits, axis=-1).astype(bool)
        scores = jnp.einsum("qhd,khd->hqk", prec.act(qb), prec.act(k_all)
                            ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(picked, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", prec.act(probs), prec.act(v_all))
        target = lax.stop_gradient(probs.mean(axis=0))
        log_q = jax.nn.log_softmax(jnp.where(picked, index, -jnp.inf),
                                   axis=-1)
        live = picked & (target > 0)
        kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                       - jnp.where(live, log_q, 0.0)), 0.0)
        return (out.reshape(rows, heads * hd), kl.sum(),
                jnp.packbits(picked, axis=-1))

    cut = lambda a: a.reshape((s // rows, rows) + a.shape[1:])  # noqa: E731
    out, kl, bits = lax.map(lambda xs: block(*xs), (
        cut(q), cut(qi), cut(w), cut(jnp.arange(s)),
        cut(jnp.zeros((s, s // 8), jnp.uint8) if given is None else given)))
    return (prec.mm(out.reshape(s, heads * hd), g("w_o")), kl.sum() / s,
            bits.reshape(s, s // 8))


def expert_layer(cfg: Dict[str, Any], p: Dict[str, Any], x, held_first: int,
                 prec: Precision):
    """x (T, C) -> (MoE (T, C), the layer's balance loss, the selected
    experts (T, k)). The held experts are `held_first ..` as many as `p`
    holds; the router scores all."""
    h = rms_norm(x, p["moe_norm"], cfg["rms_norm_eps"])
    r = jax.nn.softmax(prec.mm(h, p["moe_w_router"]), axis=-1)
    _, idx = lax.top_k(lax.stop_gradient(r), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(r, idx, axis=1)
    gates = picked / picked.sum(axis=-1, keepdims=True)
    n_experts = r.shape[1]
    slots = (idx[..., None] == jnp.arange(n_experts)).sum(axis=(0, 1))
    balance = n_experts * jnp.sum(slots / x.shape[0] * r.mean(axis=0))
    y = jnp.zeros_like(h)
    for j in range(p["moe_experts_gate"].shape[0]):
        gate = jnp.where(idx == held_first + j, gates, 0.0).sum(axis=-1)
        y = y + gate[:, None] * swiglu(h, p["moe_experts_gate"][j],
                                       p["moe_experts_up"][j],
                                       p["moe_experts_down"][j], prec)
    return y, balance, idx


def sequence_losses(cfg: Dict[str, Any], params, ids, targets,
                    held_first: int, prec: Precision, given=None):
    """One sequence: ids and targets (S,). Returns (sum of the
    cross-entropy over its tokens, sum over the layers of the balance
    loss, sum over the layers of the index loss, per layer the selected
    experts (S, k) and the selection packed 8 keys a byte (S, S / 8)).
    `given`: per layer the selection of an earlier pass, or None."""

    @jax.checkpoint
    def layer(p, x, bits):
        a, index_loss, bits = attention(cfg, p, x, prec, bits)
        x = x + a
        y, balance, idx = expert_layer(cfg, p, x, held_first, prec)
        return x + y, balance, index_loss, idx, bits

    x = params[0]["weights"][ids]
    balance = index = 0.0
    picked, selected = [], []
    for n, p in enumerate(params[1:-1]):
        x, b, i, idx, bits = layer(p, x, None if given is None else given[n])
        balance, index = balance + b, index + i
        picked.append(idx)
        selected.append(bits)
    head = params[-1]
    h = rms_norm(x, head["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def block_sum(hb, yb):
        logp = jax.nn.log_softmax(prec.mm(hb, head["weights"]), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], 1)[:, 0].sum()

    rows = min(HEAD_BLOCK_ROWS, h.shape[0])
    ce = lax.map(lambda xs: block_sum(*xs),
                 (h.reshape(-1, rows, h.shape[1]),
                  targets.reshape(-1, rows))).sum()
    return ce, balance, index, picked, selected


# -- the first steps ------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _step_programs(cfg_json: str, held_first: int, precision: str):
    """(the selection of one sequence, the gradient of one sequence added
    to a running sum, one leaf's update), jitted once per configuration
    and precision."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    mu, wd = opt["gradient_moment"], opt["weights_decay"]
    n_layers = cfg["num_hidden_layers"]
    prec = Precision(precision)

    def select(p, ids, targets):
        return sequence_losses(cfg, p, ids, targets, held_first, prec)[4]

    def seq_loss(p, ids, targets, n_seq, given):
        ce, balance, index, picked, selected = sequence_losses(
            cfg, p, ids, targets, held_first, prec, given)
        # each term a mean over the step: the cross-entropy over its
        # tokens, the layers' losses over its sequences
        terms = (ce / (n_seq * ids.shape[0]), balance / (n_layers * n_seq),
                 index / n_seq)
        loss = terms[0] + cfg["router_aux_loss_coef"] * terms[1] \
            + cfg["index_loss_weight"] * terms[2]
        return loss, (terms, picked, selected)

    def more(acc, p, ids, targets, n_seq, given):
        out, g = jax.value_and_grad(seq_loss, has_aux=True)(
            p, ids, targets, n_seq, given)
        return out, jax.tree.map(jnp.add, acc, g)

    def update(p, g, v, rate):
        """v <- mu v - rate (g + wd w);  w <- w + v."""
        v = mu * v - rate * (g + wd * p)
        return p + v, v

    return (jax.jit(select), jax.jit(more, donate_argnums=(0,)),
            jax.jit(update, donate_argnums=(0, 1)))


def unload() -> None:
    """Drop the compiled programs of `_step_programs`: loaded, they keep
    their temporaries reserved on the device."""
    _step_programs.cache_clear()
    jax.clear_caches()


def reference_steps(cfg: Dict[str, Any], params0, batches, *,
                    first_params=None, precision: str = "float32",
                    first_grad_of_program=None, first_grads_of=None,
                    keep_first_grad: bool = False) -> Dict[str, Any]:
    """Follow the program's first steps from `params0` (device arrays,
    used up: the updates are made in place; `first_params` is the same on
    the host, where the caller has it already) and zero velocity: one
    (ids (B, S), targets (B, S)) per step. Returns per step `loss`
    (total), the three `TERMS`, `picked` (per layer the selected experts
    (B*S, k)) and `selected` (per layer the selection, (B*S, S/8) uint8),
    on the host; the per-leaf norm of the first gradient; of the
    parameters' change after the last step; the `seconds` each part took.
    Given the program's first gradient (a tree like the parameters, used
    up as `_diff_norms` says), also the per-leaf norm of its difference
    from the reference's, `grad_diff_norm`; `first_grads_of` is a dict of
    more such trees by name, whose norms go to `grad_diff_norm_of[name]`;
    with `keep_first_grad` the first gradient itself, on the host."""
    opt = cfg["optimizer"]
    lr, bias_mult = opt["learning_rate"], opt["learning_rate_bias"]
    seconds = dict.fromkeys(("gradients", "first_gradient_read",
                             "updates", "host_copies"), 0.0)

    def timed(name: str, t0: float) -> None:
        seconds[name] += time.perf_counter() - t0

    select, more, update = _step_programs(
        json.dumps(cfg, sort_keys=True), counts.dims(cfg)["held_first"],
        precision)

    with jax.default_matmul_precision("highest"):
        # the velocity and the first parameters stay on the host, and the
        # update goes leaf by leaf (a leaf of one dimension, which here is
        # a norm scale or the indexer's LayerNorm bias, at
        # `learning_rate_bias` times the rate)
        t0 = time.perf_counter()
        if first_params is None:
            first_params = jax.device_get(params0)
        timed("host_copies", t0)
        params = [dict(layer) for layer in params0]
        vel: List[Dict[str, Any]] = [dict.fromkeys(layer)
                                     for layer in first_params]
        out: Dict[str, Any] = {"loss": [], "picked": [], "selected": [],
                               **{t: [] for t in TERMS}}
        for s, (ids, targets) in enumerate(batches):
            t0 = time.perf_counter()
            n_seq = float(ids.shape[0])
            grads = jax.tree.map(jnp.zeros_like, tuple(params))
            sums, picked, selected = np.zeros(4), [], []
            for b in range(ids.shape[0]):
                # the selection first, by one forward pass of its own: the
                # gradient pass recomputes every block twice (memory), and
                # a `top_k` of 2,048 among 16,384 is a sort, the slowest
                # thing a block holds on a TPU; read back, it is searched
                # for once
                bits = select(tuple(params), ids[b], targets[b])
                (tot, (terms, idx, bits)), grads = more(
                    grads, tuple(params), ids[b], targets[b], n_seq, bits)
                sums += [float(tot)] + [float(t) for t in terms]
                picked.append([np.asarray(i) for i in idx])
                selected.append([np.asarray(i) for i in bits])
            for name, v in zip(("loss",) + TERMS, sums):
                out[name].append(float(v))
            out["picked"].append([np.concatenate(x) for x in zip(*picked)])
            out["selected"].append([np.concatenate(x)
                                    for x in zip(*selected)])
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
            timed("updates", t0)
        t0 = time.perf_counter()
        out["dparam_norm"] = {
            f"{i}.{name}": float(np.linalg.norm(
                (np.asarray(a) - first_params[i][name]).ravel()))
            for i, layer in enumerate(params) for name, a in layer.items()}
        timed("host_copies", t0)
        out["seconds"] = seconds
        for layer in params:
            for a in layer.values():
                a.delete()
        return out


# -- the comparison that decides `correct` ----------------------------------------

_ONES = np.unpackbits(np.arange(256, dtype=np.uint8)[:, None], axis=1
                      ).sum(axis=1)


def select_mismatch(prog_bits, ref_bits) -> float:
    """Share of the program's selected (query, key) pairs that the
    reference did not select: both (T, S/8) uint8, 8 keys a byte."""
    a, b = np.asarray(prog_bits, np.uint8), np.asarray(ref_bits, np.uint8)
    return float(_ONES[a & ~b].sum()) / max(float(_ONES[a].sum()), 1.0)


def tables(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """What `compare` reads, leaf by leaf and step by step (the selected
    experts and keys aside): what a limit is set from."""
    return {
        **{t: [prog[t], ref[t]] for t in TERMS},
        "grad_norm": [prog["grad_norm"], ref["grad_norm"]],
        "grad_diff_norm": ref["grad_diff_norm"],
        "dparam_norm": [prog["dparam_norm"], ref["dparam_norm"]],
    }


def compare(cfg: Dict[str, Any], prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared, beside its limit. `prog` holds what the
    session read of the timed object: per step the three `TERMS`,
    `picked` and `selected`; `grad_norm`, `dparam_norm`,
    `slots_dropped`."""
    d = counts.dims(cfg)
    layers = counts.layer_names(cfg)
    loss_gap, at = 0.0, "-"
    for name in TERMS:
        for s, (p, r) in enumerate(zip(prog[name], ref[name])):
            gap = abs(p - r) / max(abs(r), 1e-30) \
                if math.isfinite(p) else math.inf
            if gap >= loss_gap:
                loss_gap, at = gap, f"{name} step {s}"
    g_gap, g_leaf = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    d_gap, d_leaf = worst_leaf_gap(prog["dparam_norm"], ref["dparam_norm"])
    e_gap, e_leaf = _worst_leaf(ref["grad_diff_norm"], ref["grad_norm"])
    # the head's weight gradient, h^T (p - y), is linear in a rounding of
    # the products before it, with no gate, routing or selection choice
    # behind it: the number that tells the precisions apart (reference.py)
    head = f"{max(int(n.split('.')[0]) for n in ref['grad_norm'])}.weights"
    h_gap = ref["grad_diff_norm"][head] / max(ref["grad_norm"][head], 1e-30)
    worst = {"picked": (0.0, "-"), "selected": (0.0, "-")}
    for key, gap_of in (("picked", lambda a, b: route_mismatch(
            a, b, d["experts"])), ("selected", select_mismatch)):
        for s, (pp, rp) in enumerate(zip(prog[key], ref[key])):
            for name, a, b in zip(layers, pp, rp):
                gap = gap_of(a, b)
                if gap >= worst[key][0]:
                    worst[key] = (gap, f"{name} step {s}")
    rows = [
        {"name": "loss_rel_gap", "value": loss_gap, "at": at},
        {"name": "grad_norm_gap", "value": g_gap, "at": g_leaf},
        {"name": "grad_rel_err", "value": e_gap, "at": e_leaf},
        {"name": "head_grad_rel_err", "value": h_gap, "at": head},
        {"name": "dparam_norm_gap", "value": d_gap, "at": d_leaf},
        {"name": "route_mismatch_share", "value": worst["picked"][0],
         "at": worst["picked"][1]},
        {"name": "select_mismatch_share", "value": worst["selected"][0],
         "at": worst["selected"][1]},
        {"name": "slots_dropped", "value": float(prog["slots_dropped"]),
         "at": "first steps and window"},
    ]
    for row in rows:
        row["limit"] = limits[row["name"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
