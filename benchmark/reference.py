"""The plain reference of a training step, and the comparison with it.

Forward, softmax cross-entropy, gradients by `jax.grad`, momentum SGD with
weight decay: straightforward `jax.numpy` / `lax` in float32 under
`jax.default_matmul_precision("highest")`, read from the configuration
file's layer list. It imports nothing of `veles_tpu` and takes nothing the
program has made: weights and inputs come from the seed (`seeded.py`).
It works in blocks of rows (the loss is a weighted sum over rows), so it
fits beside nothing and stays under the program's own memory peak.

`precision="float8"` is the CONTROL: the same reference with every conv and
matmul computed as float8 training computes it (operands rounded to e4m3,
the gradient flowing back through the activations to e5m2, each scaled per
tensor to its format's range), the precision below the bfloat16 the
configurations state. It has to come out as not correct.

Departures from the papers are the configuration files' `assumed` lists.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

E4M3_MAX, E5M2_MAX = 448.0, 57344.0


# -- the layers ---------------------------------------------------------------

def _round_to(t, dtype, top: float):
    """Round to a float8 format at a per-tensor scale that fills its range."""
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / top
    return (t / scale).astype(dtype).astype(t.dtype) * scale


@jax.custom_vjp
def _fp8_weight(w):
    """A weight as a float8 matmul reads it: e4m3; its gradient comes out
    of the matmul in the wider type, untouched."""
    return _round_to(w, jnp.float8_e4m3fn, E4M3_MAX)


_fp8_weight.defvjp(lambda w: (_fp8_weight(w), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_act(x):
    """An activation as a float8 matmul reads it: e4m3 forward, and the
    gradient that flows back through it in e5m2 (the usual fp8 training
    recipe: the backward matmuls read their incoming gradient in e5m2)."""
    return _round_to(x, jnp.float8_e4m3fn, E4M3_MAX)


_fp8_act.defvjp(lambda x: (_fp8_act(x), None),
                lambda _, g: (_round_to(g, jnp.float8_e5m2, E5M2_MAX),))


def _lrn(x, k: float, alpha: float, beta: float, n: int):
    """y = x / (k + alpha * sum of x^2 over n channels centred here)^beta."""
    half = n // 2
    sq = jnp.pad(x * x, ((0, 0),) * (x.ndim - 1) + ((half, half),))
    c = x.shape[-1]
    ssum = sum(sq[..., d:d + c] for d in range(n))
    return x * (k + alpha * ssum) ** (-beta)


def _maxpool(x, ksize: Sequence[int], stride: Sequence[int]):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, ksize[0], ksize[1], 1),
                             (1, stride[0], stride[1], 1), "VALID")


def forward(layers: Sequence[Dict[str, Any]], params, x, masks,
            normalize: Optional[Dict[str, float]] = None,
            precision: str = "float32"):
    """Logits of `x` (rows, H, W, C). `masks[i]` is layer i's dropout mask
    for these rows (already scaled by 1/keep)."""
    if precision == "float8":
        qa, qw = _fp8_act, _fp8_weight
    else:
        qa = qw = lambda t: t  # noqa: E731
    x = x.astype(jnp.float32)
    if normalize:
        x = x * normalize["scale"] + normalize["offset"]
    for i, spec in enumerate(layers):
        kind = spec["type"]
        if kind == "conv_strictrelu":
            py, px = spec["padding"]
            y = lax.conv_general_dilated(
                qa(x), qw(params[i]["weights"]),
                window_strides=tuple(spec["stride"]),
                padding=[(py, py), (px, px)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jnp.maximum(y + params[i]["bias"], 0.0)
        elif kind == "norm":
            x = _lrn(x, spec["k"], spec["alpha"], spec["beta"], spec["n"])
        elif kind == "max_pooling":
            x = _maxpool(x, spec["ksize"], spec["stride"])
        elif kind in ("all2all_strictrelu", "softmax"):
            y = qa(x.reshape(x.shape[0], -1)) @ qw(params[i]["weights"]) \
                + params[i]["bias"]
            x = jnp.maximum(y, 0.0) if kind == "all2all_strictrelu" else y
        elif kind == "dropout":
            x = x * masks[i]
        else:
            raise ValueError(f"layer {i}: unknown type {kind!r}")
    return x


def dropout_mask(key, shape: Tuple[int, ...], ratio: float, platform: str):
    """The configuration's mask stream (its `assumed.dropout_bits`)."""
    keep = 1.0 - ratio
    if platform == "cpu":
        bits = jax.random.uniform(key, shape) < keep
    else:
        kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
        _, raw = lax.rng_bit_generator(jnp.concatenate([kd] * 4)[:4], shape,
                                       dtype=jnp.uint32)
        bits = raw < np.uint32(min(keep * 2.0 ** 32, 2.0 ** 32 - 1))
    return bits.astype(jnp.float32) / np.float32(keep)


def step_masks(config: Dict[str, Any], step_key, n_rows: int, n_shards: int,
               platform: str) -> Dict[int, Any]:
    """Every dropout layer's mask for one step's whole batch. On a mesh
    each shard folds its index into the step key and draws its own rows."""
    from benchmark import ops_count
    table = ops_count.layer_table(config)
    out = {}
    for i, spec in enumerate(config["layers"]):
        if spec["type"] != "dropout":
            continue
        rows = n_rows // n_shards
        parts = []
        for s in range(n_shards):
            k = jax.random.fold_in(step_key, s) if n_shards > 1 else step_key
            parts.append(dropout_mask(jax.random.fold_in(k, i),
                                      (rows,) + tuple(table[i]["in"]),
                                      spec["dropout_ratio"], platform))
        out[i] = jnp.concatenate(parts) if n_shards > 1 else parts[0]
    return out


# -- one step, in blocks of rows ------------------------------------------------

def leaf_norms(tree) -> Dict[str, float]:
    out = {}
    for i, layer in enumerate(tree):
        for name, a in layer.items():
            out[f"{i}.{name}"] = float(jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32)))))
    return out


def reference_steps(config: Dict[str, Any], params0, key0,
                    batches: Sequence[Tuple[Any, Any, Any]], *,
                    n_shards: int = 1, block_rows: int = 64,
                    normalize: Optional[Dict[str, float]] = None,
                    precision: str = "float32",
                    platform: Optional[str] = None,
                    first_grad_of_program=None,
                    keep_first_grad: bool = False) -> Dict[str, Any]:
    """Follow the program's first steps: one (x, y, w) per step. Returns
    each step's loss, the per-leaf norm of the first gradient and the
    per-leaf norm of the parameters' change after the last step. Given the
    program's first gradient (a tree like the parameters), also the
    per-leaf norm of its difference from the reference's; with
    `keep_first_grad` the first gradient itself (the control's, to be put
    in the program's place)."""
    platform = platform or jax.devices()[0].platform
    layers = config["layers"]
    opt = config["optimizer"]
    lr, mu = opt["learning_rate"], opt["gradient_moment"]
    wd, bias_mult = opt["weights_decay"], opt["learning_rate_bias"]

    def block_loss(p, xb, yb, wb, mb, wsum):
        logits = forward(layers, p, xb, mb, normalize, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, yb[:, None], 1)[:, 0]
        return -(picked * wb).sum() / wsum

    grad_fn = jax.jit(jax.value_and_grad(block_loss))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))

    def sgd(p, g, v):
        """v <- mu v - lr (g + wd w);  w <- w + v; biases at bias_mult lr."""
        new_p, new_v = [], []
        for pl, gl, vl in zip(p, g, v):
            lp, lv = {}, {}
            for name, a in pl.items():
                rate = lr * (bias_mult if a.ndim == 1 else 1.0)
                lv[name] = mu * vl[name] - rate * (gl[name] + wd * a)
                lp[name] = a + lv[name]
            new_p.append(lp)
            new_v.append(lv)
        return tuple(new_p), tuple(new_v)

    sgd_fn = jax.jit(sgd, donate_argnums=(2,))

    with jax.default_matmul_precision("highest"):
        params = params0
        vel = jax.tree.map(jnp.zeros_like, params0)
        key = key0
        losses: List[float] = []
        grad_norm: Dict[str, float] = {}
        out: Dict[str, Any] = {}
        for s, (x, y, w) in enumerate(batches):
            n = x.shape[0]
            w = jnp.ones((n,), jnp.float32) if w is None \
                else jnp.asarray(w, jnp.float32)
            wsum = jnp.maximum(w.sum(), 1e-9)
            masks = step_masks(config, key, n, n_shards, platform)
            loss, grads = 0.0, None
            for lo in range(0, n, block_rows):
                hi = min(n, lo + block_rows)
                mb = {i: m[lo:hi] for i, m in masks.items()}
                lb, gb = grad_fn(params, x[lo:hi], jnp.asarray(y[lo:hi]),
                                 w[lo:hi], mb, wsum)
                loss += float(lb)
                grads = gb if grads is None else add(grads, gb)
            losses.append(loss)
            if s == 0:
                grad_norm = leaf_norms(grads)
                if first_grad_of_program is not None:
                    out["grad_diff_norm"] = leaf_norms(jax.jit(
                        lambda a, b: jax.tree.map(jnp.subtract, a, b))(
                            first_grad_of_program, grads))
                if keep_first_grad:
                    out["first_grad"] = jax.tree.map(jnp.copy, grads)
            new_params, vel = sgd_fn(params, grads, vel)
            if params is not params0:
                jax.tree.map(lambda a: a.delete(), params)
            params = new_params
            key = jax.random.fold_in(key, 1)
        dparam = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
            params, params0)
        out.update({"loss": losses, "grad_norm": grad_norm,
                    "dparam_norm": leaf_norms(dparam)})
        return out


# -- the comparison that decides `correct` ----------------------------------------

def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]
                   ) -> Tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    return _worst_leaf({n: abs(p - ref[n]) for n, p in prog.items()}, ref)


def _worst_leaf(numerator: Dict[str, float], ref: Dict[str, float]
                ) -> Tuple[float, str]:
    if set(numerator) != set(ref):
        raise ValueError(
            f"leaves differ: {sorted(set(numerator) ^ set(ref))}")
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        gap = numerator[name] / max(r, floor, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared, beside its limit."""
    loss_gap, at = 0.0, 0
    for s, (p, r) in enumerate(zip(prog["loss"], ref["loss"])):
        gap = abs(p - r) / abs(r) if math.isfinite(p) else math.inf
        if gap >= loss_gap:
            loss_gap, at = gap, s
    g_gap, g_leaf = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    d_gap, d_leaf = worst_leaf_gap(prog["dparam_norm"], ref["dparam_norm"])
    # the norm of the DIFFERENCE of the first gradients, by the worst leaf:
    # rounding moves a norm only in the second order, so the norm gaps above
    # hardly tell bfloat16 from float8; this one does
    e_gap, e_leaf = _worst_leaf(ref["grad_diff_norm"], ref["grad_norm"])
    # ... and the same for the classifier's weights alone. ReLU gates and
    # pooling routes turn a rounding of size e into a gradient error of
    # about sqrt(e), on every leaf behind one: there bfloat16 and float8
    # lie only three times apart. The head's weight gradient, h^T (p - y),
    # has no gate behind it and is linear in e: they lie thirteen apart.
    head = max((n for n in ref["grad_norm"] if n.endswith(".weights")),
               key=lambda n: int(n.split(".")[0]))
    h_gap = ref["grad_diff_norm"][head] / max(ref["grad_norm"][head], 1e-30)
    rows = [
        {"name": "loss_rel_gap", "value": loss_gap, "at": f"step {at}"},
        {"name": "grad_norm_gap", "value": g_gap, "at": g_leaf},
        {"name": "grad_rel_err", "value": e_gap, "at": e_leaf},
        {"name": "head_grad_rel_err", "value": h_gap, "at": head},
        {"name": "dparam_norm_gap", "value": d_gap, "at": d_leaf},
    ]
    for row in rows:
        row["limit"] = limits[row["name"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
