"""Gated DeltaNet: a linear-attention layer whose state is a matrix a head,
moved along the sequence by a gated delta rule (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; the layer as Qwen3-Next states it).

Not in the reference (a 2015 codebase). Per value head, with keys of `dk`
and values of `dv`, S in R^{dk x dv}, S_0 = 0:

    S'_t = exp(g_t) S_{t-1}
    S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T
    o_t  = S_t^T q_t

Token by token that is a chain as long as the sequence. `gated_delta_chunked`
computes the same numbers a CHUNK of C tokens at a time (the WY form of the
published kernels): with gamma_i the cumulative log-decay inside a chunk and
S the state at its start,

    A[i, j] = beta_i exp(gamma_i - gamma_j) (k_i . k_j)   for j < i, else 0
    T  = (I + A)^-1
    W  = T (beta exp(gamma) * K),   U0 = T (beta * V)
    U  = U0 - W S                                    (the chunk's updates)
    O  = (exp(gamma) * Q) S + tril(exp(gamma_i - gamma_j) Q K^T) U
    S' = exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T U

Only `U` and `S'` depend on the state, so everything else is formed for all
chunks at once, and what is sequential is two small products a chunk
(`_delta_scan`, a `jax.custom_vjp` whose backward is the same chain walked
from the end). Every decay enters as the exponential of a DIFFERENCE of
cumulative log-decays, never as a quotient of exponentials: nothing
overflows, whatever the decay.

Precision: gates, decays, the inverse and the state are float32 (`scan_dtype`,
an argument of the functions here and no key of a layer table, says
otherwise only where a test breaks it on purpose); the matrix products read
operands of the activations' dtype and accumulate in float32.

One algorithm, two implementations of its first stage (ISSUE 42). What is
formed "for all chunks at once" before the chain (`A`, `T`, `W`, `U0`, the
decayed keys and queries, the decay-weighted `Q K^T`) is a dozen (C, C)
float32 matrices a chunk and head; as XLA traces the equations
(`_operands_xla`) each passes through HBM with its 64 lanes padded to 128.
Where the caller says kernels may be traced (`kernels`: the step allows
them and the platform runs them, `variants.kernels_ok`), for chunks of 64,
heads of whole lanes, float32 decays and bfloat16 operands
(`pallas_kernels.gdn_view` says the shapes), the stage is
`_chunk_operands`: the kernels `veles_gdn_chunk_fwd` and
`veles_gdn_chunk_bwd` under a `jax.custom_vjp` that keeps its INPUTS
alone, every (C, C) matrix living and dying in VMEM, the backward forming `T` and the decay matrix again
(which is the recomputation the XLA form's own `jax.checkpoint` asks for,
inside VMEM). The cumulative sums along a chunk, the chain and the
outputs are XLA's in both.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: the `jax.ad_checkpoint.checkpoint_name` of a whole layer's output: a
#: block runs the layer under checkpoints of its own, a group of sequences
#: at a time (`znicz.lm.BlockSpec(scan_groups=...)`), and its own
#: checkpoint keeps this, so that the layer is formed again once, not twice
GDN_OUT = "gdn_out"

#: rows of the diagonal blocks `unit_lower_inverse` inverts by a product
#: of powers; larger blocks are put together by substitution
INVERSE_BLOCK = 16


def causal_conv_silu(x, w):
    """A depthwise causal convolution over time, no bias, then SiLU:
    x (N, S, D), w (K, D) -> (N, S, D) in x's dtype. Tap K - 1 meets the
    token itself, tap 0 the token K - 1 before it; before the sequence
    there are zeros. The sum is float32."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(xp[:, i:i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
            for i in range(taps))
    return jax.nn.silu(y).astype(x.dtype)


def l2_normalize(x, eps: float = 1e-6):
    """x rsqrt(sum x^2 + eps) over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


# -- (I + A)^-1 of a strictly lower-triangular A -----------------------------------

def _block_masks(c: int, block: int):
    """(the mask of the diagonal blocks of `block` rows, per doubling of
    the block the mask of the lower-left quarter of every doubled block)."""
    r = np.arange(c)
    diag = (r[:, None] // block) == (r[None, :] // block)
    merges, size = [], block
    while size < c:
        same = (r[:, None] // (2 * size)) == (r[None, :] // (2 * size))
        low = ((r[:, None] // size) % 2 == 1) & ((r[None, :] // size) % 2 == 0)
        merges.append(same & low)
        size *= 2
    return diag, merges


def _inverse_of(a):
    c = a.shape[-1]
    block = min(INVERSE_BLOCK, c)
    if c & (c - 1):
        raise ValueError(f"a chunk of {c} tokens is no power of two")
    diag, merges = _block_masks(c, block)
    eye = jnp.eye(c, dtype=a.dtype)
    # the diagonal blocks: (I + D)^-1 = (I - D)(I + D^2)(I + D^4) ... for
    # D nilpotent; inside 16 rows a power's entries stay small enough for
    # float32 whatever the keys (at most C(15, 7) paths of products <= 1)
    d = jnp.where(diag, a, 0)
    inv, power, reach = eye - d, d, 2
    while reach < block:
        power = power @ power
        inv = inv @ (eye + power)
        reach *= 2
    # two inverted blocks and the block between them: substitution,
    # [[P, 0], [M, Q]]^-1 = [[P^-1, 0], [-Q^-1 M P^-1, Q^-1]]
    for low in merges:
        inv = inv - inv @ jnp.where(low, a, 0) @ inv
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + a)^-1 for a (..., C, C) strictly lower triangular, C a power of
    two, in a's dtype: by substitution over blocks of `INVERSE_BLOCK` rows,
    every step a C x C product, ten of them at 64, at the ambient matmul
    precision (on a TPU one bfloat16 pass: at the cell's size the final
    states and `w_o`'s gradient read what six passes read, seed by seed to
    the fourth digit, for 76 ms a step less; my chip runs, PR 41). The
    backward reads the inverse alone: d a = -T^T (d T) T^T."""
    return _inverse_of(a)


def _inverse_fwd(a):
    t = _inverse_of(a)
    return t, t


def _inverse_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    return (-(tt @ g @ tt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# -- the chain over the chunks -------------------------------------------------------

def _dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _scan_forward(static, w, u0, kd, last):
    state_dtype, op = static
    b, dk, dv = w.shape[1], w.shape[3], u0.shape[-1]

    def body(s, xs):
        w_n, u0_n, kd_n, last_n = xs
        s_op = s.astype(op)
        u = (u0_n.astype(jnp.float32)
             - _dot("bck,bkv->bcv", w_n, s_op)).astype(op)
        new = last_n[:, None, None] * s.astype(jnp.float32) \
            + _dot("bck,bcv->bkv", kd_n, u)
        return new.astype(state_dtype), (s_op, u)

    final, (states, updates) = lax.scan(
        body, jnp.zeros((b, dk, dv), state_dtype), (w, u0, kd, last))
    return states, updates, final.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _delta_scan(static, w, u0, kd, last):
    """The chain: chunk by chunk U = U0 - W S, S' = last S + Kd^T U from
    S = 0. w and kd (n, B, C, dk), u0 (n, B, C, dv), all in the operands'
    dtype, last (n, B) float32 ->
    (the state at every chunk's start (n, B, dk, dv) and the updates (n, B,
    C, dv), both as the products read them, the final state float32).
    `static` = (the dtype the state is carried in, the operands' dtype)."""
    return _scan_forward(static, w, u0, kd, last)


def _delta_scan_fwd(static, w, u0, kd, last):
    states, updates, final = _scan_forward(static, w, u0, kd, last)
    return (states, updates, final), (w, kd, last, states, updates)


def _delta_scan_bwd(static, res, cts):
    """The same chain from its end: the carry is the loss's gradient by
    the state a chunk hands on."""
    _, op = static
    w, kd, last, states, updates = res
    d_states, d_updates, d_final = cts

    def body(g, xs):
        w_n, kd_n, last_n, s_n, u_n, ds_n, du_n = xs
        g_op = g.astype(op)
        du = du_n.astype(jnp.float32) + _dot("bck,bkv->bcv", kd_n, g_op)
        du_op = du.astype(op)
        d_kd = _dot("bcv,bkv->bck", u_n, g_op)
        d_last = jnp.sum(g * s_n.astype(jnp.float32), axis=(1, 2))
        d_w = -_dot("bcv,bkv->bck", du_op, s_n)
        g = ds_n.astype(jnp.float32) + last_n[:, None, None] * g \
            - _dot("bck,bcv->bkv", w_n, du_op)
        return g, (d_w, du, d_kd, d_last)

    with jax.named_scope("gdn"), jax.named_scope("scan"):
        _, (d_w, d_u0, d_kd, d_last) = lax.scan(
            body, d_final.astype(jnp.float32),
            (w, kd, last, states, updates, d_states, d_updates),
            reverse=True)
    # (u0 comes in the operands' dtype, as w and kd do)
    return (d_w.astype(op), d_u0.astype(op), d_kd.astype(op),
            d_last.astype(last.dtype))


_delta_scan.defvjp(_delta_scan_fwd, _delta_scan_bwd)


# -- the chunks' operands as two kernels -----------------------------------------

def _kernels_take(kernels: bool, chunk_heads: int, chunk: int, dk: int,
                  dv: int, scan_dtype, op) -> bool:
    """Whether the operand stage runs as `veles_gdn_chunk_fwd` / `_bwd`:
    where `kernels` may be traced (`gated_delta_chunked`'s), for the
    shapes `pallas_kernels.gdn_view` takes."""
    from veles_tpu.ops import pallas_kernels as pk
    return bool(kernels and pk.gdn_view(chunk_heads, chunk, dk, dv,
                                        scan_dtype, op))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk_operands(interpret, q, k, v, gamma, beta):
    """`gated_delta_chunked`'s `operands` from the cumulative log-decay on,
    as ONE kernel a direction in which every (C, C) matrix of a chunk
    stays in VMEM: q and k (n, B, C, dk), v (n, B, C, dv) in the operands'
    dtype, gamma and beta (n, B, C) float32 -> (w, u0, kd, last, attn,
    qg). It keeps its inputs alone: the backward kernel forms the inverse
    and the decay matrix again."""
    return _chunk_operands_fwd(interpret, q, k, v, gamma, beta)[0]


def _over_chunk_heads(kernel, interpret, *arrays):
    """`kernel` over (n, B, ...) arrays as the (n B, ...) chunk-heads it
    takes, its results laid out (n, B, ...) again."""
    lead = arrays[0].shape[:2]
    return tuple(
        a.reshape(lead + a.shape[1:]) for a in kernel(
            *(a.reshape((-1,) + a.shape[2:]) for a in arrays),
            inverse_block=INVERSE_BLOCK, interpret=interpret))


def _chunk_operands_fwd(interpret, q, k, v, gamma, beta):
    from veles_tpu.ops import pallas_kernels as pk
    w, u0, kd, attn, qg = _over_chunk_heads(
        pk.gdn_chunk_forward_pallas, interpret, q, k, v, gamma, beta)
    return ((w, u0, kd, jnp.exp(gamma[..., -1]), attn, qg),
            (q, k, v, gamma, beta))


def _chunk_operands_bwd(interpret, res, cts):
    from veles_tpu.ops import pallas_kernels as pk
    gamma = res[3]
    d_w, d_u0, d_kd, d_last, d_attn, d_qg = cts
    with jax.named_scope("gdn"), jax.named_scope("scan"):
        d_q, d_k, d_v, d_gamma, d_beta = _over_chunk_heads(
            pk.gdn_chunk_backward_pallas, interpret, *res, d_w, d_u0, d_kd,
            d_attn, d_qg)
        d_gamma = d_gamma.at[..., -1].add(d_last * jnp.exp(gamma[..., -1]))
    return d_q, d_k, d_v, d_gamma, d_beta


_chunk_operands.defvjp(_chunk_operands_fwd, _chunk_operands_bwd)


def _operands_xla(op, q, k, v, g, beta):
    """The chunks' operands in plain XLA: q and k (n, B, C, dk), v (n, B,
    C, dv) in the products' dtype `op`, g and beta (n, B, C) -> (w, u0, kd,
    last, attn, qg, the lowest cumulative log-decay a chunk reaches)."""
    chunk = q.shape[-2]
    gamma = jnp.cumsum(g, axis=-1).astype(jnp.float32)      # (nc, B, C)
    beta = beta.astype(jnp.float32)[..., None]
    lower = np.tril(np.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a = jnp.where(np.tril(lower, -1),
                  beta * decay * _dot("bnik,bnjk->bnij", k, k), 0.0)
    t = unit_lower_inverse(a).astype(op)
    grow = jnp.exp(gamma)[..., None]                        # (nc, B, C, 1)
    kf = k.astype(jnp.float32)
    w = _dot("bnij,bnjk->bnik", t, (beta * grow * kf).astype(op))
    u0 = _dot("bnij,bnjv->bniv", t,
              (beta * v.astype(jnp.float32)).astype(op))
    kd = (jnp.exp(gamma[..., -1:] - gamma)[..., None] * kf).astype(op)
    attn = (decay * _dot("bnik,bnjk->bnij", q, k)).astype(op)
    qg = (grow * q.astype(jnp.float32)).astype(op)
    return (w.astype(op), u0.astype(op), kd, jnp.exp(gamma[..., -1]),
            attn, qg, lax.stop_gradient(gamma[..., -1].min()))


def _operands_kernels(q, k, v, g, beta):
    """`_operands_xla`'s results through the two kernels. The sums along a
    chunk stay XLA's (exact in float32; as a triangular product at one
    bfloat16 pass they would round g), and so does what is a function of
    a chunk's last gamma alone."""
    from veles_tpu.ops import pallas_kernels as pk
    gamma = jnp.cumsum(g, axis=-1).astype(jnp.float32)
    return _chunk_operands(pk._interpret(), q, k, v, gamma,
                           beta.astype(jnp.float32)) \
        + (lax.stop_gradient(gamma[..., -1].min()),)


def chunks_of(seq: int, chunk: int) -> Tuple[int, int]:
    """(tokens a chunk, chunks a sequence): `chunk`, or the least power of
    two that holds a shorter sequence whole; the last chunk is filled up."""
    chunk = min(chunk, 1 << max(seq - 1, 0).bit_length())
    return chunk, -(-seq // chunk)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64,
                        scan_dtype=jnp.float32, finish=None, gate=None,
                        finish_args=(), kernels: bool = False
                        ) -> Tuple[Any, Any, Any]:
    """The gated delta rule over whole sequences, a chunk at a time (module
    docstring). q and k (N, S, H, dk), already normalised and scaled, v
    (N, S, H, dv), g (the log-decay, <= 0) and beta (N, S, H) float32 ->
    (o (N, S, H, dv) float32, the final state (N, H, dk, dv) float32, the
    lowest cumulative log-decay a chunk reaches). The products read
    operands of v's dtype. A sequence that is no multiple of `chunk` is
    filled up with tokens that neither write (beta 0, k 0) nor decay
    (g 0). `kernels`: whether Pallas kernels may be traced
    (`variants.kernels_ok`, the caller's); the operand stage is then the
    two kernels where `pallas_kernels.gdn_view` takes the shape.

    With `finish` the first result is `finish(o, gate, *finish_args)` in
    o's place, `gate` (N, S, H, dv) met chunk by chunk as o is laid out
    (`finish` works along the last axis). The two stages around the chain
    then stand under a `jax.checkpoint` each, the chunks' operands (the
    products inside a chunk and the inverse) and the outputs with
    `finish`: their float32 insides, most of them (C, C) a chunk and head,
    are formed again in the backward pass, a stage at a time, not kept
    side by side. Where the operands are the two kernels (module
    docstring) they need no checkpoint: the stage keeps its inputs and
    its backward kernel forms the insides again."""
    n, s, h, dk = q.shape
    dv = v.shape[-1]
    op = v.dtype
    chunk, nc = chunks_of(s, chunk)
    pad = nc * chunk - s

    def chunks(a):
        """(N, S, H, ...) -> (chunks, N * H, C, ...)."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((n, nc, chunk, h) + a.shape[3:])
        a = jnp.moveaxis(a, 3, 1)                       # (N, H, nc, C, ...)
        a = jnp.moveaxis(a, 2, 0)                       # (nc, N, H, C, ...)
        return a.reshape((nc, n * h, chunk) + a.shape[4:])

    def read(qg, attn, states, updates, gate, *args):
        o = _dot("bnik,bnkv->bniv", qg, states) \
            + _dot("bnij,bnjv->bniv", attn, updates)
        if finish is not None:
            o = finish(o, gate, *args)
        o = o.reshape(nc, n, h, chunk, dv).transpose(1, 0, 3, 2, 4)
        return o.reshape(n, nc * chunk, h, dv)[:, :s]

    if _kernels_take(kernels, nc * n * h, chunk, dk, dv, scan_dtype, op):
        # (their backward IS the stage's recomputation, inside VMEM)
        operands = _operands_kernels
    else:
        operands = functools.partial(_operands_xla, op)
        if finish is not None:
            operands = jax.checkpoint(operands)
    if finish is not None:
        read = jax.checkpoint(read)
    w, u0, kd, last, attn, qg, lowest = operands(
        chunks(q.astype(op)), chunks(k.astype(op)), chunks(v),
        chunks(g.astype(scan_dtype)), chunks(beta.astype(scan_dtype)))
    states, updates, final = _delta_scan(
        (jnp.dtype(scan_dtype), jnp.dtype(op)), w, u0, kd, last)
    o = read(qg, attn, states, updates,
             None if gate is None else chunks(gate), *finish_args)
    return o, final.reshape(n, h, dk, dv), lowest


def gated_delta_net(p: Dict[str, Any], h, *, key_heads: int,
                    value_heads: int, key_dim: int, value_dim: int,
                    chunk: int = 64, norm_eps: float = 1e-6,
                    scan_dtype=jnp.float32, kernels: bool = False):
    """One Gated DeltaNet layer on normed input h (N, S, C) -> ((N, S, C),
    what the layer counted: the final state (N, Hv, dk, dv) float32 and
    its root mean square, the lowest cumulative log-decay of a chunk).
    `p`: `w_qkvz` (C, 2 Hk dk + 2 Hv dv: queries, keys, values, the
    output gate z), `w_ba` (C, 2 Hv:
    the write strength b, the decay's input a), `conv` (K, 2 Hk dk + Hv dv)
    over [q, k, v], `a_log` and `dt_bias` (Hv,), `o_norm` (dv,), `w_o` (Hv
    dv, C). beta = sigmoid(b); g = -exp(a_log) softplus(a + dt_bias); q and
    k L2-normalised a head, q times dk^-1/2; a key head serves Hv / Hk value
    heads; y = RMSNorm(o) o_norm * SiLU(z) a head, then `w_o`. Scopes
    `proj`, `conv`, `scan`, `out` (the caller opens `gdn`). What is
    elementwise on either side of the scan (the convolution with the
    normalisations and the gates; the outputs' norm and gate) stands under
    a `jax.checkpoint` of its own: its float32 insides are formed again in
    the backward pass, not kept. `kernels` is `gated_delta_chunked`'s."""
    from veles_tpu.ops.lm import mm, rms_norm
    n, s, _ = h.shape
    kw, vw = key_heads * key_dim, value_heads * value_dim

    @jax.checkpoint
    def mixed(qkvz, ba, conv, a_log, dt_bias):
        with jax.named_scope("conv"):
            qkv = causal_conv_silu(qkvz[..., :2 * kw + vw], conv)
            q = l2_normalize(qkv[..., :kw].reshape(n, s, key_heads, key_dim),
                             1e-6) * key_dim ** -0.5
            k = l2_normalize(qkv[..., kw:2 * kw].reshape(
                n, s, key_heads, key_dim), 1e-6)
            v = qkv[..., 2 * kw:].reshape(n, s, value_heads, value_dim)
            rep = value_heads // key_heads
            q = jnp.repeat(q.astype(h.dtype), rep, axis=2)
            k = jnp.repeat(k.astype(h.dtype), rep, axis=2)
            beta = jax.nn.sigmoid(ba[..., :value_heads])
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., value_heads:] + dt_bias.astype(jnp.float32))
            return q, k, v, g, beta

    def gated(o, z, o_norm):
        with jax.named_scope("out"):
            return (rms_norm(o, o_norm, norm_eps)
                    * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)

    with jax.named_scope("proj"):
        qkvz = mm(h, p["w_qkvz"])
        ba = jnp.matmul(h, p["w_ba"], preferred_element_type=jnp.float32)
    q, k, v, g, beta = mixed(qkvz, ba, p["conv"], p["a_log"], p["dt_bias"])
    with jax.named_scope("scan"):
        y, final, lowest = gated_delta_chunked(
            q, k, v, g, beta, chunk=chunk, scan_dtype=scan_dtype,
            finish=gated, finish_args=(p["o_norm"],), kernels=kernels,
            gate=qkvz[..., 2 * kw + vw:].reshape(n, s, value_heads,
                                                 value_dim))
    with jax.named_scope("out"):
        y = mm(y.reshape(n, s, vw), p["w_o"])
    final = lax.stop_gradient(final)
    return y, {"gdn_state": final, "gdn_decay_min": lowest,
               "gdn_state_rms": jnp.sqrt(jnp.mean(jnp.square(final)))}
