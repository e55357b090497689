"""jnp/lax implementations of the znicz ops — the TPU compute path.

Parity: replaces BOTH hand-written kernel families of the reference
(`veles/znicz/ocl/*.cl` and `veles/znicz/cuda/*.cu`) with XLA lowerings:
matmuls/convs hit the MXU via lax.dot_general/conv_general_dilated,
elementwise chains fuse into them, and backwards come from `jax.vjp` instead
of hand-derived kernels. Semantics match `ops.reference` exactly (tested by
tests/test_ops_equivalence.py; tolerance-based, SURVEY.md §4).

All functions are pure and jit-safe: static shapes, no Python control flow
on traced values.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
# private: not exported from jax.lax on the installed jax (0.9.0). The
# Varying -> Invariant all-gather, whose result shard_map's varying-axes
# check knows to be the same on every shard (parallel/fused.py takes it
# from here)
from jax._src.lax.parallel import all_gather_invariant

TANH_A = 1.7159
TANH_B = 0.6666

# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def act_forward(name: str, x):
    if name == "linear":
        return x
    if name == "tanh":
        return TANH_A * jnp.tanh(TANH_B * x)
    if name == "relu":  # reference smooth RELU = softplus
        return jax.nn.softplus(x)
    if name == "strictrelu":
        return jnp.maximum(x, 0.0)
    if name == "sigmoid":
        return jax.nn.sigmoid(x)
    if name == "log":
        return jnp.arcsinh(x)
    raise ValueError(f"unknown activation {name!r}")


def act_backward(name: str, y, err, x=None):
    """dL/dx from dL/dy and the forward OUTPUT y (input x only where the
    derivative needs it) — the reference's memory model: pre-activations
    are never retained. Mirrors ops.reference.act_backward; used inside
    the GD units' fused backward+update steps."""
    if name == "linear":
        return err
    if name == "tanh":
        return err * (TANH_B * (TANH_A - y * y / TANH_A))
    if name == "relu":
        return err * (1.0 - jnp.exp(-y))
    if name == "strictrelu":
        return err * (y > 0)
    if name == "sigmoid":
        return err * y * (1.0 - y)
    if name == "log":
        assert x is not None
        return err / jnp.sqrt(x * x + 1.0)
    raise ValueError(f"unknown activation {name!r}")


# ---------------------------------------------------------------------------
# fully connected
# ---------------------------------------------------------------------------


def all2all_forward(x, w, b, activation: str = "linear",
                    grad_gather_axis: Optional[str] = None):
    """y = act(x @ W + b). Flattens trailing dims of x (parity: All2All
    accepts image inputs). The matmul is the MXU hot path — callers feed
    bf16 inputs under mixed precision; accumulation stays f32.

    `grad_gather_axis` names the mesh axis the batch is sharded over where
    the BACKWARD is to form the global weight gradient from gathered
    operands (`dense_gathered_grad`); the fused dp step sets it per layer
    from shapes (parallel/fused.py, `dense_grad_form`). None, the
    default, is plain autodiff: this shard's rows only."""
    x2 = x.reshape(x.shape[0], -1)
    if grad_gather_axis is None:
        return act_forward(activation, x2 @ w + b)
    return act_forward(activation,
                       dense_gathered_grad(x2, w, b, grad_gather_axis))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def dense_gathered_grad(x2, w, b, axis_name: str):
    """x2 @ w + b for rows sharded over `axis_name` under `shard_map`, `w`
    and `b` the same on every shard (invariant). The backward exchanges
    the layer's OPERANDS, not its gradient (Krizhevsky 2014,
    arXiv:1404.5997): every shard all-gathers the rows of `x2` and of
    `dy` and forms the whole `dW = X^T dY` in one matmul whose
    contraction runs over the global batch — the sum the one-device
    program forms at that batch, in the dtype autodiff gives it there.
    `dW` and `db` come out invariant over the axis: whoever sums
    per-shard partials must leave these two alone (a `psum` on top would
    count them once a shard). `dX` stays local."""
    return x2 @ w + b


def _dense_gathered_fwd(x2, w, b, axis_name):
    return x2 @ w + b, (x2, w, b)


def _dense_gathered_bwd(axis_name, res, dy):
    x2, w, b = res
    dx = jnp.einsum("bo,io->bi", dy, w).astype(x2.dtype)
    x_all = all_gather_invariant(x2, axis_name, axis=0, tiled=True)
    dy_all = all_gather_invariant(dy, axis_name, axis=0, tiled=True)
    dw = jnp.einsum("bi,bo->io", x_all, dy_all).astype(w.dtype)
    return dx, dw, dy_all.sum(axis=0).astype(b.dtype)


dense_gathered_grad.defvjp(_dense_gathered_fwd, _dense_gathered_bwd)


def softmax(x):
    return jax.nn.softmax(x, axis=-1)


def all2all_softmax_forward(x, w, b):
    """Fused linear+max-subtract+softmax (parity: All2AllSoftmax)."""
    x2 = x.reshape(x.shape[0], -1)
    return jax.nn.softmax(x2 @ w + b, axis=-1)


# ---------------------------------------------------------------------------
# convolution — NHWC/HWIO (TPU-native layouts)
# ---------------------------------------------------------------------------


def conv2d_forward(x, w, b, stride: Tuple[int, int] = (1, 1),
                   padding: Tuple[int, int] = (0, 0),
                   activation: str = "linear", s2d: bool = False,
                   acc: str = "native"):
    """acc="f32" pins the conv accumulator to f32
    (preferred_element_type) — a real axis only under a sub-f32 compute
    dtype, where it trades MXU-native accumulation for exactness; the
    "native" default keeps XLA's dtype-following rule (today's
    behavior). A generated conv_stem template axis (ops.templates)."""
    ph, pw = padding
    pet = jnp.float32 if acc == "f32" else None
    if s2d and stride[0] == stride[1] and stride[0] > 1:
        y = conv2d_space_to_depth(x, w, stride[0], (ph, pw), acc=acc)
    else:
        y = lax.conv_general_dilated(
            x, w, window_strides=stride, padding=[(ph, ph), (pw, pw)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=pet)
    if pet is not None:
        y = y.astype(x.dtype)
    return act_forward(activation, y + b)


def conv2d_space_to_depth(x, w, b_: int, padding: Tuple[int, int],
                          acc: str = "native"):
    """EXACT rewrite of a stride-b conv as a stride-1 conv on a
    space-to-depth-packed input — the classic TPU entry-conv trick for
    thin-channel inputs (AlexNet/ResNet stems: cin=3 fills 3/128 of an
    MXU tile; packing b×b stride blocks into channels yields cin·b² and
    a b×-smaller spatial extent, so the systolic array runs full tiles).

    Equivalence: pad H/W and the kernel up to multiples of b with zeros
    (zero taps read anything, contribute nothing), rearrange both input
    and kernel into (H/b, W/b, C·b²) blocks, convolve stride 1. Output
    matches lax.conv_general_dilated bit-for-math on the same dtype.
    """
    n, h, wdt, c = x.shape
    kh, kw, _, co = w.shape
    ph, pw = padding
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        h, wdt = h + 2 * ph, wdt + 2 * pw
    # valid output extent of the ORIGINAL conv
    oh = (h - kh) // b_ + 1
    ow = (wdt - kw) // b_ + 1
    # pad kernel to multiples of b (zero taps), input so every tap exists
    kh2 = -(-kh // b_) * b_
    kw2 = -(-kw // b_) * b_
    need_h = (oh - 1) * b_ + kh2
    need_w = (ow - 1) * b_ + kw2
    x = jnp.pad(x, ((0, 0), (0, max(0, need_h - h)),
                    (0, max(0, need_w - wdt)), (0, 0)))
    w = jnp.pad(w, ((0, kh2 - kh), (0, kw2 - kw), (0, 0), (0, 0)))
    hb, wb = need_h // b_, need_w // b_
    # space-to-depth: (N, Hb, b, Wb, b, C) -> (N, Hb, Wb, b*b*C)
    xs = x[:, :hb * b_, :wb * b_, :].reshape(n, hb, b_, wb, b_, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, hb, wb, b_ * b_ * c)
    # kernel: (kh2, kw2, C, O) -> (kh2/b, b, kw2/b, b, C, O) ->
    # (kh2/b, kw2/b, b*b*C, O), matching the input channel packing
    ws = w.reshape(kh2 // b_, b_, kw2 // b_, b_, c, co)
    ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(kh2 // b_, kw2 // b_,
                                                b_ * b_ * c, co)
    y = lax.conv_general_dilated(
        xs, ws, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=(jnp.float32 if acc == "f32" else None))
    return y.astype(x.dtype) if acc == "f32" else y


def deconv2d_forward(x, w, stride: Tuple[int, int] = (1, 1),
                     padding: Tuple[int, int] = (0, 0),
                     out_hw: Optional[Tuple[int, int]] = None):
    """Transposed conv as the EXACT adjoint of conv2d_forward wrt its input
    (parity: Deconv, which the reference defined as the conv gradient).
    Strided conv output sizes are ambiguous under transposition, so we
    transpose the concrete forward conv for the requested `out_hw` — XLA
    lowers this to a single fractionally-strided conv."""
    n, oh, ow, oc = x.shape
    kh, kw, c, _ = w.shape
    sy, sx = stride
    ph, pw = padding
    if out_hw is None:
        out_hw = ((oh - 1) * sy + kh - 2 * ph, (ow - 1) * sx + kw - 2 * pw)
    in_shape = (n, out_hw[0], out_hw[1], c)

    def fwd(inp):
        return lax.conv_general_dilated(
            inp, w, window_strides=stride, padding=[(ph, ph), (pw, pw)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    transpose = jax.linear_transpose(
        fwd, jax.ShapeDtypeStruct(in_shape, x.dtype))
    (y,) = transpose(x)
    return y


def deconv2d_backward(x, w, err_y, stride: Tuple[int, int] = (1, 1),
                      padding: Tuple[int, int] = (0, 0)):
    """Gradient of deconv2d_forward via jax.vjp (replaces the reference's
    hand-written gd_deconv kernels; XLA emits the two convs directly).
    Returns (err_x, dW)."""
    _, vjp = jax.vjp(
        lambda xx, ww: deconv2d_forward(xx, ww, stride, padding,
                                        out_hw=err_y.shape[1:3]), x, w)
    return vjp(err_y)


def depool_forward(x, idx, out_shape: Tuple[int, ...]):
    """Scatter pooled values to their recorded winner offsets (adjoint of
    max pooling — autoencoder decoders; sentinel offsets drop)."""
    size = 1
    for s in out_shape:
        size *= s
    flat = jnp.zeros(size, x.dtype)
    flat = flat.at[idx.ravel()].add(x.ravel(), mode="drop")
    return flat.reshape(out_shape)


def depool_backward(err_y, idx):
    flat = jnp.asarray(err_y).ravel()
    return flat.at[idx.ravel()].get(mode="fill", fill_value=0.0
                                    ).reshape(idx.shape)


def cut_forward(x, crop: Tuple[int, int]):
    cy, cx = crop
    n, h, w, c = x.shape
    return x[:, cy:h - cy, cx:w - cx, :]


def cut_backward(err_y, x_shape: Tuple[int, ...], crop: Tuple[int, int]):
    cy, cx = crop
    pads = [(0, 0), (cy, cy), (cx, cx), (0, 0)]
    return jnp.pad(err_y, pads)


# ---------------------------------------------------------------------------
# pooling — ceil-mode windows (reference semantics: edge windows truncate)
# ---------------------------------------------------------------------------


def _ceil_pads(h, w, ky, kx, sy, sx):
    oh = -(-(h - ky) // sy) + 1 if h > ky else 1
    ow = -(-(w - kx) // sx) + 1 if w > kx else 1
    return oh, ow, (oh - 1) * sy + ky - h, (ow - 1) * sx + kx - w


def _flat_offsets(choice, n, h, w, c, oh, ow, stride, kx):
    """Flat offsets into an (n,h,w,c) input from per-window winner indices
    `choice` (index within the ky*kx window, shape (n,oh,ow,c)). THE offset
    convention: the backward scatter (pool_scatter) and the numpy golden
    twins in ops.reference must agree with this formula."""
    sy, sx = stride
    dy, dx = choice // kx, choice % kx
    ii = jnp.arange(oh)[None, :, None, None] * sy
    jj = jnp.arange(ow)[None, None, :, None] * sx
    nn = jnp.arange(n)[:, None, None, None]
    cc = jnp.arange(c)[None, None, None, :]
    return ((nn * h + (ii + dy)) * w + (jj + dx)) * c + cc


def maxpool_forward(x, ksize: Tuple[int, int], stride: Tuple[int, int],
                    use_abs: bool = False):
    """reduce_window max pooling. Init/pad values are HOST scalars on
    purpose: a jnp.array init becomes a traced constant under jit and
    breaks reverse-mode linearization of reduce_window (the fused train
    step differentiates through this)."""
    ky, kx = ksize
    sy, sx = stride
    n, h, w, c = x.shape
    _, _, eh, ew = _ceil_pads(h, w, ky, kx, sy, sx)
    pads = [(0, 0, 0), (0, eh, 0), (0, ew, 0), (0, 0, 0)]
    dt = np.dtype(x.dtype)
    if use_abs:
        # keep the signed value of the max-|·| element (MaxAbsPooling)
        xp = lax.pad(x, np.zeros((), dt)[()], pads)
        return lax.reduce_window(
            xp, np.zeros((), dt)[()],
            lambda a, b: jnp.where(jnp.abs(a) >= jnp.abs(b), a, b),
            (1, ky, kx, 1), (1, sy, sx, 1), "VALID")
    ninf = np.asarray(-np.inf, dt)[()]
    xp = lax.pad(x, ninf, pads)
    return lax.reduce_window(xp, ninf, lax.max,
                             (1, ky, kx, 1), (1, sy, sx, 1), "VALID")


def maxpool_forward_with_idx(x, ksize: Tuple[int, int],
                             stride: Tuple[int, int], use_abs: bool = False):
    """Max pooling that also records flat winner offsets into x (reference
    parity: the kernels emitted argmax offsets for the backward scatter).
    Patches-based — used by the granular MaxPooling unit; the fused path
    uses the reduce_window flavor above."""
    ky, kx = ksize
    sy, sx = stride
    n, h, w, c = x.shape
    _, _, eh, ew = _ceil_pads(h, w, ky, kx, sy, sx)
    patches = lax.conv_general_dilated_patches(
        x, (ky, kx), (sy, sx), padding=[(0, eh), (0, ew)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    oh, ow = patches.shape[1], patches.shape[2]
    p = patches.reshape(n, oh, ow, c, ky * kx)
    # mask out padded slots so they never win (pad fills with 0)
    in_h = jnp.arange(oh)[:, None, None] * sy + \
        (jnp.arange(ky * kx)[None, None, :] // kx)
    in_w = jnp.arange(ow)[None, :, None] * sx + \
        (jnp.arange(ky * kx)[None, None, :] % kx)
    valid = (in_h < h) & (in_w < w)          # (oh, ow, ky*kx)
    key = jnp.abs(p) if use_abs else p
    key = jnp.where(valid[None, :, :, None, :], key, -jnp.inf)
    choice = key.argmax(-1)
    y = jnp.take_along_axis(p, choice[..., None], -1)[..., 0]
    return y, _flat_offsets(choice, n, h, w, c, oh, ow, stride, kx)


def maxpool_forward_slices(x, ksize: Tuple[int, int],
                           stride: Tuple[int, int], use_abs: bool = False,
                           fold: str = "linear"):
    """Max pooling as a max-fold over the ky·kx SHIFTED STRIDED SLICES of
    the (−inf-padded) input — numerically identical to the reduce_window
    flavor, but reverse-mode differentiates into selects + zero-pads
    (elementwise, fusion-friendly) instead of XLA's select_and_scatter.
    Candidate lowering for the fused step's backward (the registry's
    `maxpool` `slices`; `reduce_window` is the default). Each window
    always covers ≥1 real pixel (ceil-mode pads only trailing edges), so
    the fill never wins a window: −inf for plain max; 0 for the abs
    flavor (|−inf| = +inf would win every edge window; |0| only ties an
    all-zero window, where keeping 0 is correct — same fill
    maxpool_forward uses).

    `fold` shapes the combine DAG — a generated maxpool template axis
    (ops.templates): "linear" folds slices left-to-right (a ky·kx-deep
    select chain in the backward), "tree" reduces them pairwise (a
    log-depth balanced select tree; same values — on the measure-zero
    abs-tie case the two may keep a different sign, exactly like any
    reduction-order change)."""
    ky, kx = ksize
    sy, sx = stride
    n, h, w, c = x.shape
    oh, ow, eh, ew = _ceil_pads(h, w, ky, kx, sy, sx)
    dt = np.dtype(x.dtype)
    fill = (np.zeros((), dt) if use_abs else np.asarray(-np.inf, dt))[()]
    xp = lax.pad(x, fill, [(0, 0, 0), (0, eh, 0), (0, ew, 0), (0, 0, 0)])

    def comb(a, b):
        if use_abs:
            return jnp.where(jnp.abs(a) >= jnp.abs(b), a, b)
        return jnp.maximum(a, b)

    slices = [
        lax.slice(xp, (0, dy, dx, 0),
                  (n, dy + (oh - 1) * sy + 1,
                   dx + (ow - 1) * sx + 1, c),
                  (1, sy, sx, 1))
        for dy in range(ky) for dx in range(kx)]
    if fold == "tree":
        while len(slices) > 1:
            slices = [comb(slices[i], slices[i + 1])
                      if i + 1 < len(slices) else slices[i]
                      for i in range(0, len(slices), 2)]
        return slices[0]
    out = slices[0]
    for s in slices[1:]:
        out = comb(out, s)
    return out


def pool_scatter(err_y, idx, x_shape):
    """Backward scatter shared by max/maxabs/stochastic pooling: route err
    to the recorded winners; out-of-range sentinel offsets drop."""
    size = 1
    for s in x_shape:
        size *= s
    flat = jnp.zeros(size, err_y.dtype)
    flat = flat.at[idx.ravel()].add(err_y.ravel(), mode="drop")
    return flat.reshape(x_shape)


def avgpool_forward(x, ksize: Tuple[int, int], stride: Tuple[int, int]):
    """Mean over the *unpadded* window contents (matches the golden model's
    truncated edge windows)."""
    ky, kx = ksize
    sy, sx = stride
    n, h, w, c = x.shape
    _, _, eh, ew = _ceil_pads(h, w, ky, kx, sy, sx)
    pads = [(0, 0, 0), (0, eh, 0), (0, ew, 0), (0, 0, 0)]
    zero = np.zeros((), np.dtype(x.dtype))[()]  # host scalar: stays a
    # compile-time constant so reverse-mode through reduce_window works
    # under jit (see maxpool_forward)
    xp = lax.pad(x, zero, pads)
    ssum = lax.reduce_window(xp, zero, lax.add,
                             (1, ky, kx, 1), (1, sy, sx, 1), "VALID")
    ones = lax.pad(jnp.ones_like(x), zero, pads)
    cnt = lax.reduce_window(ones, zero, lax.add,
                            (1, ky, kx, 1), (1, sy, sx, 1), "VALID")
    return ssum / cnt


def stochastic_pool_forward_with_idx(x, key, ksize: Tuple[int, int],
                                     stride: Tuple[int, int]):
    """Stochastic pooling (Zeiler & Fergus; reference StochasticPooling):
    sample a window element with probability proportional to its positive
    magnitude; falls back to 0 where the window is all-nonpositive.

    Also returns flat winner offsets into x (same convention as the
    reference's max-pooling offsets; `x.size` marks dead all-nonpositive
    windows — scatter with mode="drop" ignores them), so the paired GD unit
    can route gradients without re-sampling."""
    ky, kx = ksize
    sy, sx = stride
    n, h, w, c = x.shape
    # same ceil-mode window geometry as max/avg pooling (truncated edge
    # windows), so the three pooling flavors are drop-in interchangeable
    _, _, eh, ew = _ceil_pads(h, w, ky, kx, sy, sx)
    patches = lax.conv_general_dilated_patches(
        x, (ky, kx), (sy, sx), padding=[(0, eh), (0, ew)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    oh, ow = patches.shape[1], patches.shape[2]
    # patches: (N, OH, OW, C*ky*kx) with feature dim ordered (C, ky*kx)
    p = patches.reshape(n, oh, ow, c, ky * kx)
    pos = jnp.maximum(p, 0.0)
    tot = pos.sum(-1, keepdims=True)
    probs = jnp.where(tot > 0, pos / jnp.maximum(tot, 1e-30), 0.0)
    g = jax.random.gumbel(key, p.shape, p.dtype)
    logp = jnp.where(probs > 0, jnp.log(jnp.maximum(probs, 1e-30)), -jnp.inf)
    choice = (logp + g).argmax(-1)
    picked = jnp.take_along_axis(p, choice[..., None], -1)[..., 0]
    alive = tot[..., 0] > 0
    y = jnp.where(alive, picked, 0.0)
    idx = _flat_offsets(choice, n, h, w, c, oh, ow, stride, kx)
    return y, jnp.where(alive, idx, x.size)


def stochastic_pool_forward(x, key, ksize: Tuple[int, int],
                            stride: Tuple[int, int]):
    return stochastic_pool_forward_with_idx(x, key, ksize, stride)[0]


# ---------------------------------------------------------------------------
# LRN
# ---------------------------------------------------------------------------


def _lrn_band(c: int, n: int):
    """(C, C) 0/1 band matrix: band[i, j] = |i−j| ≤ n//2. Hoisted to a
    compile-time constant by XLA (C ≤ a few hundred for LRN nets)."""
    i = np.arange(c)
    return jnp.asarray(
        (np.abs(i[:, None] - i[None, :]) <= n // 2), np.float32)


def _lrn_window_sum(a, n: int):
    """±half across-channel window sum as a BANDED MATMUL on the MXU:
    a @ B with B the 0/1 band matrix. A shifted-adds lowering (pad+
    slice per tap) leaves ~20 intermediate tensors the compiler will not
    fuse, so the pass is bound by HBM, not compute. As a dot, the window
    costs negligible MXU FLOPs (C·C per element-row, C∈{96,256}), the x²
    producer fuses into the operand read, ONE output hits HBM, and the
    f32 accumulator is numerically better than chained low-precision
    adds. The symmetric window is SELF-ADJOINT: its vjp/transpose is
    itself (used by the closed-form backward below).

    Shifted-adds kept as fallback for C too large for a band constant."""
    c = a.shape[-1]
    if c <= 4096:
        # accumulate in ≥f32 (f64 inputs keep f64 — the finite-difference
        # gradcheck runs under enable_x64)
        acc = a.dtype if a.dtype in (jnp.float32, jnp.float64) \
            else jnp.float32
        out = lax.dot_general(
            a, _lrn_band(c, n).astype(acc),
            (((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=acc)
        return out.astype(a.dtype)
    half = n // 2
    zeros = [(0, 0)] * (a.ndim - 1)
    out = a
    for d in range(1, half + 1):
        out = out + jnp.pad(a[..., d:], zeros + [(0, d)]) \
            + jnp.pad(a[..., :-d], zeros + [(d, 0)])
    return out


def _pow_neg_quarters(s, beta: float):
    """s^(-beta). When 4·beta is a small integer (AlexNet's beta=0.75 →
    q=3), decompose into sqrt/rsqrt + multiplies: s^(-q/4) as products of
    squarings of s^(-1/4)=sqrt(rsqrt(s)). The VPU has fast sqrt/rsqrt;
    the generic pow lowers to exp(−beta·log s) — two transcendentals over
    the full activation."""
    q4 = 4.0 * beta
    q = int(round(q4))
    if abs(q4 - q) < 1e-12 and 1 <= q <= 16:
        t = lax.sqrt(lax.rsqrt(s))        # s^(-1/4)
        out = None
        while q:
            if q & 1:
                out = t if out is None else out * t
            q >>= 1
            if q:
                t = t * t
        return out
    return s ** (-beta)


def lrn_forward(x, k: float = 2.0, alpha: float = 1e-4, beta: float = 0.75,
                n: int = 5):
    """AlexNet-style across-channel LRN: y = x·(k + α·W(x²))^(−β) with W
    the ±half shifted-add window (odd n only — even n would silently
    widen to n+1 taps; the Pallas and C++ twins share the ±half
    semantics, so all three agree only for odd n).

    custom-VJP: backward is the closed form
        err_x = g·d − 2αβ · x · W(g·x·d/s),  d = s^(−β)
    (W self-adjoint), with x the only residual: the backward writes s and
    d again from x, and inside a jitted step XLA's CSE keeps the
    forward's s where that is cheaper (read in the compiled AlexNet step,
    PR 27), so stashing them by hand adds a write and removes nothing."""
    if n % 2 == 0:
        raise ValueError(f"LRN window n must be odd, got {n}")
    return _lrn_cvjp(x, k, alpha, beta, n)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _lrn_cvjp(x, k, alpha, beta, n):
    s = k + alpha * _lrn_window_sum(x * x, n)
    return x * _pow_neg_quarters(s, beta)


def _lrn_fwd_rule(x, k, alpha, beta, n):
    return _lrn_cvjp(x, k, alpha, beta, n), x


def _lrn_bwd_rule(k, alpha, beta, n, x, g):
    s = k + alpha * _lrn_window_sum(x * x, n)
    d = _pow_neg_quarters(s, beta)
    core = _lrn_window_sum(g * x * d / s, n)
    return (g * d - (2.0 * alpha * beta) * x * core,)


_lrn_cvjp.defvjp(_lrn_fwd_rule, _lrn_bwd_rule)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------


def make_dropout_mask(key, shape, drop_prob: float, dtype=jnp.float32,
                      impl: str = "auto"):
    """Pre-scaled dropout mask (values 0 or 1/keep).

    impl="auto": on accelerators the bits come from the hardware
    `rng_bit_generator` (XLA RBG) instead of threefry — measured 4× less
    wall-clock per (512, 4096) mask on v5e (r4; dropout was ~7% of the
    AlexNet step under threefry, whose per-word rotate chains are VPU
    serial work). Still counter-based and deterministic per key on a
    given backend, but the mask STREAM differs from threefry's —
    trajectories are reproducible per backend, not bit-identical across
    impls (the reference had the same split between its xorshift device
    kernel and numpy host RNG). "threefry"/"rbg" force an impl; CPU
    defaults to threefry so golden tests are impl-stable."""
    keep = 1.0 - drop_prob
    use_rbg = (impl == "rbg"
               or (impl == "auto" and jax.default_backend() != "cpu"))
    if use_rbg and keep < 1.0:
        try:
            kd = jax.random.key_data(key)
        except TypeError:            # raw uint32 key array
            kd = jnp.asarray(key)
        kd = kd.astype(jnp.uint32).reshape(-1)
        rk = jnp.concatenate([kd, kd, kd, kd])[:4]   # RBG wants u32[4]
        _, bits = lax.rng_bit_generator(rk, shape, dtype=jnp.uint32)
        thr = np.uint32(min(keep * 2.0 ** 32, 2.0 ** 32 - 1))
        return (bits < thr).astype(dtype) / np.asarray(keep, dtype)[()]
    return ((jax.random.uniform(key, shape) < keep).astype(dtype)
            / np.asarray(keep, dtype)[()])


def dropout_forward(x, mask):
    return x * mask


# ---------------------------------------------------------------------------
# evaluators / losses
# ---------------------------------------------------------------------------


def softmax_ce(probs, labels, n_classes: int, weights=None):
    """Mirror of reference.softmax_ce on device: returns (loss, err wrt
    logits, n_err, confusion). All jit-safe. `weights` (N,) are sample
    weights (the Loader's pad mask): zero-weight rows contribute nothing
    to any metric — exact epoch metrics at any minibatch size with
    static shapes. weights=None == all-ones (the legacy mean forms)."""
    n = probs.shape[0]
    onehot = jax.nn.one_hot(labels, n_classes, dtype=probs.dtype)
    eps = jnp.finfo(probs.dtype).tiny
    picked = jnp.take_along_axis(probs, labels[:, None], 1)[:, 0]
    logs = -jnp.log(jnp.maximum(picked, eps))
    pred = probs.argmax(axis=1)
    wrong = pred != labels
    if weights is None:
        loss = logs.mean()
        err = (probs - onehot) / jnp.asarray(n, probs.dtype)
        n_err = wrong.sum()
        conf_inc = jnp.ones_like(labels, jnp.int32)
    else:
        w = weights.astype(probs.dtype)
        wsum = jnp.maximum(w.sum(), eps)
        loss = (logs * w).sum() / wsum
        err = (probs - onehot) * w[:, None] / wsum
        n_err = (wrong & (w > 0)).sum()
        conf_inc = (w > 0).astype(jnp.int32)
    confusion = jnp.zeros((n_classes, n_classes), jnp.int32
                          ).at[labels, pred].add(conf_inc)
    return loss, err, n_err, confusion


def ce_loss_from_logits(logits, labels, n_classes: int, weights=None,
                        denom=None):
    """Scalar CE loss from logits — the form jax.grad differentiates in the
    fused train step (log-softmax for stability). Accepts any leading
    dims: (N, C) classifier logits, or (N, S, C) per-token LM logits with
    (N, S) labels (mean over all tokens). `weights` must broadcast to the
    label shape; `denom` overrides the normalizer (the fused sharded step
    passes the GLOBAL psum'd weight sum so per-shard partial losses sum
    to the exact global weighted mean)."""
    logits = logits.reshape(-1, logits.shape[-1])
    flat = labels.reshape(-1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, flat[:, None], 1)[:, 0]
    if weights is None:
        return -picked.mean()
    w = jnp.broadcast_to(weights, labels.shape).reshape(-1)
    w = w.astype(picked.dtype)
    d = w.sum() if denom is None else denom
    return -(picked * w).sum() / jnp.maximum(d, 1e-9)


def mse(y, target, weights=None, denom=None):
    """(mean-over-batch MSE, err wrt y); `weights` (N,) sample weights,
    `denom` the (global) weight-sum normalizer as in ce_loss_from_logits."""
    n = y.shape[0]
    diff = y - target
    if weights is None:
        return (diff * diff).sum() / n, 2.0 * diff / jnp.asarray(n, y.dtype)
    wb = weights.astype(y.dtype).reshape((n,) + (1,) * (y.ndim - 1))
    d = weights.astype(y.dtype).sum() if denom is None else denom
    d = jnp.maximum(d, 1e-9)
    return (wb * diff * diff).sum() / d, 2.0 * diff * wb / d


# ---------------------------------------------------------------------------
# Kohonen SOM
# ---------------------------------------------------------------------------


def kohonen_forward(x, w):
    d2 = (x * x).sum(1)[:, None] - 2.0 * x @ w.T + (w * w).sum(1)[None, :]
    return d2.argmin(axis=1)


def kohonen_update(x, w, grid, lr, sigma):
    """Sequential-over-samples SOM update as a lax.scan (the update is
    order-dependent by definition; scan keeps it on-device and compiled —
    parity: KohonenTrainer)."""
    grid = jnp.asarray(grid)

    def step(w, xi):
        d2 = ((w - xi[None, :]) ** 2).sum(1)
        win = d2.argmin()
        gd2 = ((grid - grid[win]) ** 2).sum(1)
        h = jnp.exp(-gd2 / (2.0 * sigma * sigma)).astype(w.dtype)
        return w + lr * h[:, None] * (xi[None, :] - w), None

    w_new, _ = lax.scan(step, w, x)
    return w_new


# ---------------------------------------------------------------------------
# RBM
# ---------------------------------------------------------------------------


def rbm_cd1(v0, w, bv, bh, key):
    h0p = jax.nn.sigmoid(v0 @ w + bh)
    h0 = (jax.random.uniform(key, h0p.shape) < h0p).astype(v0.dtype)
    v1p = jax.nn.sigmoid(h0 @ w.T + bv)
    h1p = jax.nn.sigmoid(v1p @ w + bh)
    n = v0.shape[0]
    dw = (v0.T @ h0p - v1p.T @ h1p) / n
    dbv = (v0 - v1p).mean(axis=0)
    dbh = (h0p - h1p).mean(axis=0)
    return dw, dbv, dbh


# ---------------------------------------------------------------------------
# LSTM
# ---------------------------------------------------------------------------


def lstm_step(x, h, c, wx, wh, b):
    z = x @ wx + h @ wh + b
    hsz = h.shape[1]
    i, f, g, o = (z[:, k * hsz:(k + 1) * hsz] for k in range(4))
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new


@partial(jax.jit, static_argnames=())
def lstm_scan(xs, h0, c0, wx, wh, b):
    """Unroll over time with lax.scan (parity: the reference unrolled time
    steps in the unit graph on host — SURVEY.md §5.7; scan is the TPU way).
    xs: (T, N, D) -> hs: (T, N, H)."""

    def step(carry, x):
        h, c = carry
        h, c = lstm_step(x, h, c, wx, wh, b)
        return (h, c), h

    (h, c), hs = lax.scan(step, (h0, c0), xs)
    return hs, h, c
