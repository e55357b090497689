"""Language-model ops: RMSNorm, SwiGLU, yarn rotary frequencies, the
Sinkhorn-projected hyper-connection around a sub-layer, and the chunked
next-token cross-entropy of an untied head.

Not in the reference (a 2015 codebase). `jax.numpy`: every function is
differentiable by `jax.grad`, traces into the fused step and knows no
unit; the units are `znicz/lm.py`. The benchmark's plain float32 reference
of the same equations is `benchmark/xing4_reference.py`, which imports
nothing from here.

One op has two lowerings (`ops/variants.py`, op `hc`; the section "the
same connection, one pass a side" below says why): `xla`, the three
expressions `hc_maps` / `hc_read` / `hc_write` under autodiff, and
`pallas_one_pass`, two `jax.custom_vjp` functions over four kernels tiled
over tokens. `hyper_connection` runs either; the platform and the shape
choose, no option does.

Layouts are chosen for the TPU's (8, 128) tiles. The `n` residual streams
of a token lie side by side in ONE row of `n * C` features (stream `i` is
columns `i*C:(i+1)*C`), never as a trailing `(n, C)` pair, whose
second-minor dimension of 4 would pad to 16 in bfloat16. The per-token
mixing matrices keep the tokens in the minor dimension, `(n, n, T)`, so
that twenty Sinkhorn iterations are dense elementwise passes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def rms_norm(x, scale=None, eps: float = 1e-6, offset: float = 0.0):
    """x / sqrt(mean(x^2) + eps) over the last axis, in float32, times
    `offset + scale` (no scale: the plain normalisation; `offset` 1 is the
    zero-centred norm, whose scale starts from 0); back in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if scale is not None:
        scale = scale.astype(jnp.float32)
        y = y * (offset + scale if offset else scale)
    return y.astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-6):
    """(x - mean) / sqrt(var + eps) over the last axis, in float32, times
    `scale` plus `bias`; back in x's dtype."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    y = xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def mm(x, w):
    """x @ w accumulated in float32, rounded to x's dtype."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32
                      ).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """(silu(x W_g) * (x W_u)) W_d."""
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


# -- rotary embedding with yarn frequencies ------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The `dim / 2` rotary frequencies under yarn (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V2's code states it): dimensions that
    turn more than `beta_fast` times over the original context keep their
    frequency, those that turn less than `beta_slow` times are divided by
    `factor`, and a linear ramp joins them."""
    def turns_at(n_rot: float) -> float:
        return dim * math.log(original / (n_rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    freq = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 / freq) * (1.0 - ramp) + (1.0 / (factor * freq)) * ramp
            ).astype(np.float32)


def rope_inv_freq(dim: int, theta: float) -> np.ndarray:
    """The `dim / 2` plain rotary frequencies theta^(-2i/dim)."""
    return (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def rope_tables(seq_len: int, inv_freq: np.ndarray,
                factor: float = 1.0) -> Tuple[Any, Any]:
    """cos and sin, (S, dim / 2) float32, of position x frequency."""
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def apply_rope(x, cos, sin):
    """Rotate the pairs (x[..., i], x[..., i + dim/2]) of x (N, S, ..., dim)
    by the position's angles (the two-halves layout)."""
    half = x.shape[-1] // 2
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# -- hyper-connections ------------------------------------------------------------

def sinkhorn(logits, iters: int, eps: float):
    """`exp`, then `iters` times: every row divided by (its sum + eps),
    every column by (its sum + eps). `logits` is (n, n, ...): rows on
    axis 0, columns on axis 1, anything after rides along."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
    return m


def hc_init_biases(n: int) -> Dict[str, np.ndarray]:
    """Biases at which, with x~ = 0, every stream is read at 1/n, the
    sub-layer's output is written at 1 and the streams stay apart."""
    return {"b_pre": np.full((n,), math.log(1.0 / (n - 1)) if n > 1
                             else 30.0, np.float32),
            "b_post": np.zeros((n,), np.float32),
            "b_res": (8.0 * np.eye(n)).astype(np.float32)}


def hc_maps(p: Dict[str, Any], x, n: int, *, iters: int, eps: float,
            clamp: Tuple[float, float], norm_eps: float):
    """The three mixing maps of one hyper-connection from the token's
    streams x (T, n*C): (Hpre (n, T), Hpost (n, T), Hres (n, n, T)),
    float32. `p` holds `p_pre`, `p_post` (n*C, n) and `p_res` (n*C, n*n), the
    scalars `a_pre`, `a_post`, `a_res` (shape (1,)) and the biases `b_pre`
    (n,), `b_post` (n,), `b_res` (n, n)."""
    xn = rms_norm(x, eps=norm_eps)
    # one product for the three maps, (2n + n*n, T): tokens in the minor
    # dimension from here on
    p_maps = jnp.concatenate([p["p_pre"], p["p_post"], p["p_res"]], axis=1)
    raw = lax.dot_general(p_maps, xn, (((0,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    pre = f32("a_pre") * raw[:n] + f32("b_pre")[:, None]
    post = f32("a_post") * raw[n:2 * n] + f32("b_post")[:, None]
    res = f32("a_res") * raw[2 * n:].reshape(n, n, -1) \
        + f32("b_res")[:, :, None]
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(jnp.clip(res, clamp[0], clamp[1]), iters, eps))


def hc_read(x, h_pre, n: int):
    """Hpre X: the sub-layer's input (T, C) from the streams (T, n*C)."""
    c = x.shape[-1] // n
    acc = sum(h_pre[i][:, None] * x[:, i * c:(i + 1) * c].astype(jnp.float32)
              for i in range(n))
    return acc.astype(x.dtype)


def hc_write(x, y, h_post, h_res, n: int):
    """Hres X + Hpost^T y: the streams (T, n*C) after the sub-layer's
    output y (T, C)."""
    c = x.shape[-1] // n
    xs = [x[:, i * c:(i + 1) * c].astype(jnp.float32) for i in range(n)]
    yf = y.astype(jnp.float32)
    out = [sum(h_res[j, i][:, None] * xs[i] for i in range(n))
           + h_post[j][:, None] * yf for j in range(n)]
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


# -- the same connection, one pass a side (ISSUE 34) ------------------------------
#
# `hc_maps` / `hc_read` / `hc_write` above are the op `hc`'s `xla` lowering:
# three `jax.numpy` expressions that autodiff differentiates, 37 passes
# over the streams a connection on a v5e (a normed copy of x before the
# maps' product, x read again by the read and by the write, float32
# cotangents of every slice of x, twenty unrolled Sinkhorn iterations and
# their transposes as fusions of their own). The `pallas_one_pass` lowering
# below is the same mathematics as two `jax.custom_vjp` functions, one a
# side, whose work is four kernels (`ops/pallas_kernels.py`, `veles_hc_*`,
# each behind ONE module-level `jax.jit` that every site of a step calls):
# x is read once a side and direction, the maps with their Sinkhorn
# iterations are made and differentiated inside the kernels, and nothing
# stream-sized exists in float32 outside one. The RMS norm of x is a
# per-token scalar r, so it is applied AFTER the product, raw = (P^T x) r:
# the equation of `rms_norm` then `dot_general` with one rounding to
# bfloat16 fewer and no second copy of x. Outside the kernels stay the
# operands' assembly (P^T, the scalars and biases by column) and the two
# sums over tokens that are the scalars' and biases' gradients. What
# chooses between the two is `ops/variants.py::resolve` (platform) and
# `pallas_kernels.hc_view` (shape); no option does.

def _hc_operands(p: Dict[str, Any], n: int, dtype):
    """(P^T (kp, n*C) in the streams' dtype, the affine pair (2, kp)
    float32: a raw + b by column, 0 past the maps) for the kernels."""
    from veles_tpu.ops import pallas_kernels as pk
    k, kp = pk.hc_maps_width(n)
    pt = jnp.concatenate([p["p_pre"], p["p_post"], p["p_res"]],
                         axis=1).astype(dtype).T
    f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    a = jnp.concatenate([jnp.broadcast_to(f32("a_pre"), (n,)),
                         jnp.broadcast_to(f32("a_post"), (n,)),
                         jnp.broadcast_to(f32("a_res"), (n * n,))])
    b = jnp.concatenate([f32("b_pre"), f32("b_post"),
                         f32("b_res").reshape(-1)])
    return (jnp.pad(pt, ((0, kp - k), (0, 0))),
            jnp.pad(jnp.stack([a, b]), ((0, 0), (0, kp - k))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _hc_pre_core(x, pt, aff, static):
    """-> (the maps m (T, kp), h (T, C), x). x goes through untouched for
    the post side to take: what its backward sends to x then arrives HERE,
    as the third cotangent, and the pre side's backward kernel adds it
    into its own dx in float32; handed x itself, the post side would leave
    autodiff two stream-sized cotangents to add in a pass of its own.
    `static` is the kernels' keywords as a sorted tuple of pairs."""
    return _hc_pre_core_fwd(x, pt, aff, static)[0]


def _hc_pre_core_fwd(x, pt, aff, static):
    from veles_tpu.ops import pallas_kernels as pk
    raw, m, h = pk.hc_pre_forward_pallas(x, pt, aff, **dict(static))
    return (m, h, x), (x, pt, aff, raw)


def _hc_pre_core_bwd(static, res, cts):
    from veles_tpu.ops import pallas_kernels as pk
    x, pt, aff, raw = res
    dm, dh, gx = cts
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope("hc_pre"):
        dx, dlin, dpt = pk.hc_pre_backward_pallas(
            x, gx, dh, raw, dm, pt, aff,
            **{k: v for k, v in static if k != "norm_eps"})
        daff = jnp.stack([(dlin * raw).sum(axis=0), dlin.sum(axis=0)])
        return dx, dpt.astype(pt.dtype), daff


_hc_pre_core.defvjp(_hc_pre_core_fwd, _hc_pre_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _hc_post_core(x, y, m, n: int, interpret: bool):
    from veles_tpu.ops import pallas_kernels as pk
    return pk.hc_post_forward_pallas(x, y, m, n=n, interpret=interpret)


def _hc_post_core_fwd(x, y, m, n, interpret):
    return _hc_post_core(x, y, m, n, interpret), (x, y, m)


def _hc_post_core_bwd(n, interpret, res, g):
    from veles_tpu.ops import pallas_kernels as pk
    with jax.named_scope("hc_post"):        # as in `_hc_pre_core_bwd`
        return pk.hc_post_backward_pallas(g, *res, n=n, interpret=interpret)


_hc_post_core.defvjp(_hc_post_core_fwd, _hc_post_core_bwd)


def hc_pre_xla(p: Dict[str, Any], x, n: int, **kw):
    """(h, what the post side takes: (Hpost, Hres), x) of one connection,
    the `xla` lowering."""
    h_pre, h_post, h_res = hc_maps(p, x, n, **kw)
    return hc_read(x, h_pre, n), (h_post, h_res), x


def hc_post_xla(x, y, maps, n: int):
    return hc_write(x, y, *maps, n)


def hc_pre_pallas(p: Dict[str, Any], x, n: int, **kw):
    """The same in one pass over x (`pallas_one_pass`), for streams the
    kernels take (`pallas_kernels.hc_view`: the caller's question,
    `znicz/lm.py::BlockSpec.lowerings`)."""
    from veles_tpu.ops import pallas_kernels as pk
    static = dict(kw, n=n, interpret=pk._interpret(),
                  clamp=tuple(float(v) for v in kw["clamp"]))
    m, h, x = _hc_pre_core(x, *_hc_operands(p, n, x.dtype),
                           tuple(sorted(static.items())))
    return h, m, x


def hc_post_pallas(x, y, maps, n: int):
    """`hc_write` as one pass (`pallas_one_pass`)."""
    from veles_tpu.ops import pallas_kernels as pk
    return _hc_post_core(x, y, maps, n, pk._interpret())


def hyper_connection(pre, post, p: Dict[str, Any], x, f, n: int, **kw):
    """One hyper-connection around `f` through a lowering's two sides:
    x (T, n*C) -> (Hres X + Hpost^T f(Hpre X), f's extra), under the
    scopes `hc_pre` and `hc_post` (the backwards of `pallas_one_pass` open
    them again: a `custom_vjp`'s backward is traced outside the
    forward's)."""
    with jax.named_scope("hc_pre"):
        h, maps, x = pre(p, x, n, **kw)
    y, extra = f(h)
    with jax.named_scope("hc_post"):
        return post(x, y, maps, n), extra


# -- the head and its loss ------------------------------------------------------------

def chunked_ce(h, w_head, targets, weights, chunk: int):
    """Sum over tokens of weight x cross-entropy of softmax(h W) against
    the target, and the count of weighted tokens whose largest logit is
    not the target: h (T, C), w_head (C, V), targets and weights (T,).
    The logits exist a `chunk` of tokens at a time, in float32, and are
    recomputed in the backward pass."""
    t = h.shape[0]
    if t % chunk:
        raise ValueError(f"{t} tokens do not divide into chunks of {chunk}")

    @jax.checkpoint
    def one(hc, yc, wc):
        logits = jnp.matmul(hc, w_head, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[:, None], 1)[:, 0]
        wrong = (logits.argmax(axis=-1) != yc) & (wc > 0)
        return ((lse - picked) * wc).sum(), wrong.sum()

    def body(carry, xs):
        loss, n_err = one(*xs)
        return (carry[0] + loss, carry[1] + n_err), None

    n = t // chunk
    zero = (weights[0] * 0.0, (targets[0] * 0).astype(jnp.int32))
    (loss, n_err), _ = lax.scan(
        body, zero, (h.reshape(n, chunk, -1), targets.reshape(n, chunk),
                     weights.reshape(n, chunk)))
    return loss, n_err
