"""The plain reference of a `qwen3next_lm` training step, and the comparison
with it.

Forward, the two terms of the loss, gradients by `jax.grad`, momentum SGD
with weight decay: straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time. It
imports nothing of `veles_tpu` and takes nothing the program has made. The
Gated DeltaNet is its RECURRENCE, token by token (a `lax.scan` over the
tokens: no chunks, no inverse, none of the chunked algebra); experts are
looped over with a mask: no sorting, no kernels, no capacity.
`precision="float8"` is the CONTROL (`reference.py` describes it): every
matrix product reads its operands in e4m3 and passes its gradient back in
e5m2.

The model (Qwen3-Next-80B-A3B-Instruct, `model_type` `qwen3_next`; each
inference is listed under `assumed` in the configuration file). T tokens of
one sequence, positions from 0.

- Norm: N(x) = x rsqrt(mean x^2 + eps) (1 + w). Block: a = x + Mixer(N(x)),
  y = a + MoE(N(a)); after the last block N, then the untied head over the
  held ids. The mixer of layer l (from 0) is full attention if (l + 1) %
  `full_attention_interval` == 0, else a Gated DeltaNet.
- Gated DeltaNet (Hk key heads, Hv value heads, dk, dv): [q, k, v, z] = h
  W_qkvz, [b, a] = h W_ba; [q, k, v] through a causal depthwise convolution
  of `linear_conv_kernel_dim` taps over time, no bias, then SiLU; beta =
  sigmoid(b), g = -exp(A_log) softplus(a + dt_bias); q and k L2-normalised
  over the head (eps 1e-6), q times dk^-1/2, a key head serving Hv / Hk
  value heads; per value head S_0 = 0, S'_t = exp(g_t) S_{t-1}, S_t = S'_t
  + k_t (beta_t (v_t - S'_t^T k_t))^T, o_t = S_t^T q_t; y_t = (o_t
  rsqrt(mean o_t^2 + eps) w_n) SiLU(z_t) over each head; out y W_o.
- Gated attention (H query heads, Hkv key-value heads of D): [q, gate] = h
  W_q a head, k = h W_k, v = h W_v; q and k through N over the head; the
  rotary embedding on the first `partial_rotary_factor` D of a head,
  two-halves layout, theta `rope_theta`; causal softmax at D^-1/2, query
  head j reading key-value head j // (H / Hkv); out (attn sigmoid(gate))
  W_o.
- Experts: r = softmax(h Wr) over all experts; the `num_experts_per_tok`
  highest; gates r_e / sum of the selected; MoE = sum over the selected
  experts HELD here of gate x SwiGLU_e(h) + sigmoid(h w_s) SwiGLU_shared(h).
  Balance loss of a layer L_B = E sum_e (slots_e / T) mean_t r[t, e].
- Loss: next-token cross-entropy over the held ids +
  `router_aux_loss_coef` mean_l L_B.

Departures from the source, each for room and none for meaning: the
recurrence is walked in segments of `SEGMENT` tokens and a segment is
recomputed in the backward pass; the full layer walks its queries a block
of `QUERY_BLOCK` at a time, every block against ALL the keys under the
causal mask; every layer is recomputed in the backward pass
(`jax.checkpoint` changes no number); the head's logits exist
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

from benchmark import qwen3next_ops_count as counts
from benchmark.keye2_reference import Precision, rope
from benchmark.reference import _worst_leaf, leaf_norms, worst_leaf_gap
# (what the language-model references do alike, stated once)
from benchmark.xing4_reference import _diff_norms, route_mismatch, swiglu

#: tokens of the recurrence whose states are kept: a segment's inside is
#: recomputed in the backward pass
SEGMENT = 64
#: queries whose scores exist at a time, against every key
QUERY_BLOCK = 256
#: tokens whose logits exist at a time in the head's loss
HEAD_BLOCK_ROWS = 1024
#: the two terms of the loss, as the rows and tables name them
TERMS = ("loss_ce", "loss_balance")


# -- the layers ---------------------------------------------------------------

def norm(x, w, eps: float):
    """The zero-centred RMSNorm."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def delta_rule(q, k, v, g, beta):
    """The recurrence of ONE sequence: q, k (S, H, dk), v (S, H, dv), g and
    beta (S, H) -> (o (S, H, dv), the final state (H, dk, dv))."""
    s, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   b_t[:, None] * (v_t - read))
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def segment(state, xs):
        return lax.scan(token, state, xs)

    seg = math.gcd(s, SEGMENT)
    cut = lambda a: a.reshape((s // seg, seg) + a.shape[1:])  # noqa: E731
    state, o = lax.scan(segment, jnp.zeros((h, dk, dv), jnp.float32),
                        tuple(cut(a) for a in (q, k, v, g, beta)))
    return o.reshape(s, h, dv), state


def gated_delta_net(cfg: Dict[str, Any], p: Dict[str, Any], x,
                    prec: Precision):
    """One sequence x (S, C) -> (the layer's output (S, C), its final
    state (Hv, dk, dv))."""
    d = counts.dims(cfg)
    eps = cfg["rms_norm_eps"]
    hk, hv, dk, dv = d["key_heads"], d["value_heads"], d["dk"], d["dv"]
    kw, vw = hk * dk, hv * dv
    g_ = lambda name: p["attn_" + name]  # noqa: E731
    s = x.shape[0]
    h = norm(x, g_("norm"), eps)
    qkvz = prec.mm(h, g_("w_qkvz"))
    ba = prec.mm(h, g_("w_ba"))
    taps = g_("conv")                                   # (K, 2 kw + vw)
    n_taps = taps.shape[0]
    mixed = jnp.pad(qkvz[:, :2 * kw + vw], ((n_taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(mixed[i:i + s] * taps[i] for i in range(n_taps)))
    z = qkvz[:, 2 * kw + vw:].reshape(s, hv, dv)

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = unit(qkv[:, :kw].reshape(s, hk, dk)) * dk ** -0.5
    k = unit(qkv[:, kw:2 * kw].reshape(s, hk, dk))
    v = qkv[:, 2 * kw:].reshape(s, hv, dv)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(ba[:, :hv])
    decay = -jnp.exp(g_("a_log")) * jax.nn.softplus(ba[:, hv:]
                                                    + g_("dt_bias"))
    o, state = delta_rule(prec.act(q), prec.act(k), prec.act(v), decay, beta)
    y = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * g_("o_norm") * jax.nn.silu(z)
    return prec.mm(y.reshape(s, vw), g_("w_o")), state


def gated_attention(cfg: Dict[str, Any], p: Dict[str, Any], x,
                    prec: Precision):
    """One sequence x (S, C) -> the layer's output (S, C)."""
    d = counts.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads, kvh, hd, rot = d["heads"], d["kv_heads"], d["d"], d["rotary"]
    g_ = lambda name: p["attn_" + name]  # noqa: E731
    s = x.shape[0]
    h = norm(x, g_("norm"), eps)
    qg = prec.mm(h, g_("w_q")).reshape(s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = prec.mm(h, g_("w_k")).reshape(s, kvh, hd)
    v = prec.mm(h, g_("w_v")).reshape(s, kvh, hd)

    def turned(a, w):
        a = norm(a, w, eps)
        return jnp.concatenate([rope(a[..., :rot], theta), a[..., rot:]],
                               axis=-1)

    q, k = turned(q, g_("q_norm")), turned(k, g_("k_norm"))
    k_all = jnp.repeat(k, heads // kvh, axis=1)         # a key head a query head
    v_all = jnp.repeat(v, heads // kvh, axis=1)
    rows = min(QUERY_BLOCK, s)
    if s % rows:
        raise ValueError(f"{s} queries do not divide into blocks of {rows}")

    @jax.checkpoint
    def block(qb, pos):
        causal = jnp.arange(s)[None, :] <= pos[:, None]
        scores = jnp.einsum("qhd,khd->hqk", prec.act(qb), prec.act(k_all)
                            ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prec.act(probs), prec.act(v_all))

    cut = lambda a: a.reshape((s // rows, rows) + a.shape[1:])  # noqa: E731
    out = lax.map(lambda xs: block(*xs), (cut(q), cut(jnp.arange(s))))
    out = out.reshape(s, heads, hd) * jax.nn.sigmoid(gate)
    return prec.mm(out.reshape(s, heads * hd), g_("w_o"))


def expert_layer(cfg: Dict[str, Any], p: Dict[str, Any], x, held_first: int,
                 prec: Precision, shared: bool = True):
    """x (T, C) -> (MoE (T, C), the layer's balance loss, the selected
    experts (T, k)). The held experts are `held_first ..` as many as `p`
    holds; the router scores all. `shared` False leaves the shared expert
    out (a share's part that every chip computes alike is counted once)."""
    h = norm(x, p["moe_norm"], cfg["rms_norm_eps"])
    r = jax.nn.softmax(prec.mm(h, p["moe_w_router"]), axis=-1)
    _, idx = lax.top_k(lax.stop_gradient(r), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(r, idx, axis=1)
    gates = picked / picked.sum(axis=-1, keepdims=True)
    n_experts = r.shape[1]
    slots = (idx[..., None] == jnp.arange(n_experts)).sum(axis=(0, 1))
    balance = n_experts * jnp.sum(slots / x.shape[0] * r.mean(axis=0))

    @jax.checkpoint
    def one(j, w_gate, w_up, w_down):
        gate = jnp.where(idx == held_first + j, gates, 0.0).sum(axis=-1)
        return gate[:, None] * swiglu(h, w_gate, w_up, w_down, prec)

    held = p["moe_experts_gate"].shape[0]
    # (a loop XLA sees once; the sum is outside the checkpoint, so no
    # expert's partial sum is kept for the backward pass)
    y, _ = lax.scan(lambda y, xs: (y + one(*xs), None), jnp.zeros_like(h), (
        jnp.arange(held), p["moe_experts_gate"], p["moe_experts_up"],
        p["moe_experts_down"]))
    if shared:
        y = y + jax.nn.sigmoid(prec.mm(h, p["moe_shared_mix"])) * swiglu(
            h, p["moe_shared_gate"], p["moe_shared_up"], p["moe_shared_down"],
            prec)
    return y, balance, idx


def mixer(cfg: Dict[str, Any], p: Dict[str, Any], x, prec: Precision):
    """The layer's token mixer, by the leaves it holds: (its output, a
    linear layer's final state or None)."""
    if "attn_w_qkvz" in p:
        return gated_delta_net(cfg, p, x, prec)
    return gated_attention(cfg, p, x, prec), None


def sequence_losses(cfg: Dict[str, Any], params, ids, targets,
                    held_first: int, prec: Precision):
    """One sequence: ids and targets (S,). Returns (sum of the
    cross-entropy over its tokens, sum over the layers of the balance
    loss, per layer the selected experts (S, k), per linear layer its
    final state (Hv, dk, dv))."""

    @jax.checkpoint
    def layer(p, x):
        mixed, state = mixer(cfg, p, x, prec)
        x = x + mixed
        y, balance, idx = expert_layer(cfg, p, x, held_first, prec)
        return x + y, balance, idx, state

    x = params[0]["weights"][ids]
    balance, picked, states = 0.0, [], []
    for p in params[1:-1]:
        x, b, idx, state = layer(p, x)
        balance = balance + b
        picked.append(idx)
        if state is not None:
            states.append(lax.stop_gradient(state))
    head = params[-1]
    h = norm(x, head["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def block_sum(hb, yb):
        logp = jax.nn.log_softmax(prec.mm(hb, head["weights"]), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], 1)[:, 0].sum()

    rows = min(HEAD_BLOCK_ROWS, h.shape[0])
    ce = lax.map(lambda xs: block_sum(*xs),
                 (h.reshape(-1, rows, h.shape[1]),
                  targets.reshape(-1, rows))).sum()
    return ce, balance, picked, states


# -- the first steps ------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _step_programs(cfg_json: str, held_first: int, precision: str):
    """(the gradient of one sequence added to a running sum, one leaf's
    update), jitted once per configuration and precision."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    mu, wd = opt["gradient_moment"], opt["weights_decay"]
    n_layers = cfg["num_hidden_layers"]
    prec = Precision(precision)

    def seq_loss(p, ids, targets, n_seq):
        ce, balance, picked, states = sequence_losses(
            cfg, p, ids, targets, held_first, prec)
        # each term a mean over the step: the cross-entropy over its
        # tokens, the layers' losses over its sequences
        terms = (ce / (n_seq * ids.shape[0]), balance / (n_layers * n_seq))
        return terms[0] + cfg["router_aux_loss_coef"] * terms[1], \
            (terms, picked, states)

    def more(acc, p, ids, targets, n_seq):
        out, g = jax.value_and_grad(seq_loss, has_aux=True)(
            p, ids, targets, n_seq)
        return out, jax.tree.map(jnp.add, acc, g)

    def update(p, g, v, rate):
        """v <- mu v - rate (g + wd w);  w <- w + v."""
        v = mu * v - rate * (g + wd * p)
        return p + v, v

    return (jax.jit(more, donate_argnums=(0,)),
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
    (total), the two `TERMS`, `picked` (per layer the selected experts
    (B*S, k)) and `gdn_state` (per linear layer the sequences' final
    states (B, Hv, dk, dv)), on the host; the per-leaf norm of the first gradient; of
    the parameters' change after the last step; the `seconds` each part
    took. Given the program's first gradient (a tree like the parameters,
    used up as `_diff_norms` says), also the per-leaf norm of its
    difference from the reference's, `grad_diff_norm`; `first_grads_of` is
    a dict of more such trees by name, whose norms go to
    `grad_diff_norm_of[name]`; with `keep_first_grad` the first gradient
    itself, on the host."""
    opt = cfg["optimizer"]
    lr, bias_mult = opt["learning_rate"], opt["learning_rate_bias"]
    seconds = dict.fromkeys(("gradients", "first_gradient_read",
                             "updates", "host_copies"), 0.0)

    def timed(name: str, t0: float) -> None:
        seconds[name] += time.perf_counter() - t0

    more, update = _step_programs(
        json.dumps(cfg, sort_keys=True), counts.dims(cfg)["held_first"],
        precision)

    with jax.default_matmul_precision("highest"):
        # the velocity and the first parameters stay on the host, and the
        # update goes leaf by leaf (a leaf of one dimension, a norm's scale
        # or a linear layer's decay, at `learning_rate_bias` times the rate)
        t0 = time.perf_counter()
        if first_params is None:
            first_params = jax.device_get(params0)
        timed("host_copies", t0)
        params = [dict(layer) for layer in params0]
        vel: List[Dict[str, Any]] = [dict.fromkeys(layer)
                                     for layer in first_params]
        out: Dict[str, Any] = {"loss": [], "picked": [], "gdn_state": [],
                               **{t: [] for t in TERMS}}
        for s, (ids, targets) in enumerate(batches):
            t0 = time.perf_counter()
            n_seq = float(ids.shape[0])
            grads = jax.tree.map(jnp.zeros_like, tuple(params))
            sums, picked, states = np.zeros(3), [], []
            for b in range(ids.shape[0]):
                (tot, (terms, idx, state)), grads = more(
                    grads, tuple(params), ids[b], targets[b], n_seq)
                sums += [float(tot)] + [float(t) for t in terms]
                picked.append([np.asarray(i) for i in idx])
                states.append([np.asarray(a) for a in state])
            for name, v in zip(("loss",) + TERMS, sums):
                out[name].append(float(v))
            out["picked"].append([np.concatenate(x) for x in zip(*picked)])
            out["gdn_state"].append([np.stack(x) for x in zip(*states)])
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

def tables(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """What `compare` reads, leaf by leaf and step by step (the selected
    experts aside): what a limit is set from."""
    return {
        **{t: [prog[t], ref[t]] for t in TERMS},
        "grad_norm": [prog["grad_norm"], ref["grad_norm"]],
        "grad_diff_norm": ref["grad_diff_norm"],
        "dparam_norm": [prog["dparam_norm"], ref["dparam_norm"]],
    }


def compare(cfg: Dict[str, Any], prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared, beside its limit. `prog` holds what the
    session read of the timed object: per step the two `TERMS`,
    `picked` and `gdn_state`; `grad_norm`, `dparam_norm`,
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

    def rel_err(leaf: str) -> float:
        return ref["grad_diff_norm"][leaf] / max(ref["grad_norm"][leaf],
                                                 1e-30)

    # the head's weight gradient, h^T (p - y), is linear in a rounding of
    # the products before it, with no gate or routing choice behind it:
    # the number that tells the precisions apart (reference.py)
    head = f"{len(layers) + 1}.weights"
    # a linear layer's output projection sees the layer's output, o
    # normed and gated, on one side and the loss's gradient on the other,
    # again with no choice behind it: the number a recurrence whose decay
    # or state is held in fewer bits has to fail
    lin_gap, lin_leaf = max(
        (rel_err(f"{i}.attn_w_o"), f"{i}.attn_w_o")
        for i in counts.linear_units(cfg))
    # the state a linear layer's sequences END in, every sequence's, after
    # the FIRST step (the same parameters on both sides, as the gradients):
    # what a recurrence whose decays or state are held in fewer bits moves
    # first (a cumulative log-decay of -100 rounds by a quarter in bfloat16)
    s_gap, s_at = 0.0, "-"
    for i, a, b in zip(counts.linear_units(cfg), prog["gdn_state"][0],
                       ref["gdn_state"][0]):
        gap = float(np.linalg.norm((np.asarray(a, np.float32) - b).ravel())
                    / max(np.linalg.norm(b.ravel()), 1e-30))
        if not gap <= s_gap:            # (a gap that is no number is worst)
            s_gap, s_at = (gap if math.isfinite(gap) else math.inf,
                           layers[i - 1])
    worst = (0.0, "-")
    for s, (pp, rp) in enumerate(zip(prog["picked"], ref["picked"])):
        for name, a, b in zip(layers, pp, rp):
            gap = route_mismatch(a, b, d["experts"])
            if gap >= worst[0]:
                worst = (gap, f"{name} step {s}")
    rows = [
        {"name": "loss_rel_gap", "value": loss_gap, "at": at},
        {"name": "grad_norm_gap", "value": g_gap, "at": g_leaf},
        {"name": "grad_rel_err", "value": e_gap, "at": e_leaf},
        {"name": "head_grad_rel_err", "value": rel_err(head), "at": head},
        {"name": "gdn_out_grad_rel_err", "value": lin_gap, "at": lin_leaf},
        {"name": "gdn_state_rel_err", "value": s_gap, "at": s_at},
        {"name": "dparam_norm_gap", "value": d_gap, "at": d_leaf},
        {"name": "route_mismatch_share", "value": worst[0], "at": worst[1]},
        {"name": "slots_dropped", "value": float(prog["slots_dropped"]),
         "at": "first steps and window"},
    ]
    for row in rows:
        row["limit"] = limits[row["name"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
