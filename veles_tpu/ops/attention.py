"""Attention ops: single-device reference + sequence-parallel forms.

The reference framework (2015-era) has NO attention anywhere (SURVEY.md
§5.7); this module is a capability the TPU build adds because long-context
support is first-class here. Two sequence-parallel schemes are provided,
matching the two standard TPU recipes:

- **Ring attention** (`ring_attention`): Q stays sharded over the "seq"
  mesh axis; K/V shards rotate around the ring via `lax.ppermute` while a
  flash-style online softmax accumulates (m, l, o) — numerically identical
  to full attention, memory O(S_local), and the permute rides ICI
  neighbor links. Use when S is huge and heads are few.
- **Ulysses / all-to-all** (`ulysses_attention`): `all_to_all` swaps the
  sequence sharding for a head sharding, full-sequence attention runs per
  head group, then swaps back. Use when n_heads >= mesh axis.

Both run inside `shard_map` over a `Mesh` "seq" axis (parallel/mesh.py)
and degrade to plain attention on a 1-device axis. Tested against
`mha_forward` on the 8-device CPU mesh (tests/test_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.lax import axis_size as _axis_size

NEG_INF = -1e30


def mha_forward(q, k, v, scale: Optional[float] = None,
                causal: bool = False):
    """Plain multi-head attention. q/k/v: (B, S, H, D) -> (B, S, H, D).
    The single-device golden model for the parallel forms."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        q_idx = jnp.arange(q.shape[1])[:, None]
        k_idx = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((k_idx <= q_idx)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_accum(q, k, v, scale, mask, m, l, o):
    """One online-softmax accumulation step (flash-attention recurrence).
    q: (B,Sq,H,D), k/v: (B,Sk,H,D); m/l: (B,H,Sq), o: (B,Sq,H,D)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_blk = s.max(axis=-1)                      # (B,H,Sq)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])           # (B,H,Sq,Sk)
    alpha = jnp.exp(m - m_new)                  # (B,H,Sq)
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] \
        + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str,
                   scale: Optional[float] = None, causal: bool = False,
                   kv_block: Optional[int] = None,
                   kv_order: str = "fwd"):
    """Sequence-parallel attention over a ring. Call INSIDE shard_map with
    q/k/v sharded on the sequence dim: (B, S/n, H, D) per device.

    Per step, each device computes attention of its Q shard against the
    currently-held K/V shard, then passes the K/V shard to its ring
    neighbor (`ppermute`) — n steps see every KV shard exactly once. The
    online-softmax (m, l, o) carry makes the result bit-comparable to
    full attention regardless of arrival order.

    `kv_block` tiles WITHIN each hop: the held KV shard is consumed in
    blocks of that size by an inner `lax.scan` of the same flash
    recurrence, so the materialized score block is (B,H,Sq_local,
    kv_block) instead of (B,H,Sq_local,S_local) — the difference between
    fitting and not fitting long-context meshes in HBM. Each block step
    is `jax.checkpoint`-ed, so the backward recomputes scores/probs
    per block instead of storing them (flash-attention memory profile,
    differentiable end-to-end). None → min(S_local, 1024); a value that
    does not divide S_local falls back to one block per hop.

    `kv_block`/`kv_order` are the flash_attn search axes reaching the
    ring hop (MultiHeadAttention.ring_params wires the registry winner's
    blk_k/kv_order here): "rev" visits the held shard's inner blocks
    last-to-first — the online softmax is order-invariant, so the
    choice only probes prefetch/locality, exactly like the local
    kernel's kv_order axis."""
    if kv_order not in ("fwd", "rev"):
        raise ValueError(f"kv_order must be 'fwd'|'rev', got "
                         f"{kv_order!r}")
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    n = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, _ = q.shape
    if kv_block is None:
        kv_block = min(s_loc, 1024)
    if s_loc % kv_block:
        kv_block = s_loc
    nb = s_loc // kv_block

    q_idx = my * s_loc + jnp.arange(s_loc)      # global Q positions

    # the carry must be device-varying from step 0 (shard_map vma typing:
    # it mixes with the varying K/V inside the loop). Deriving it from q
    # arithmetic inherits q's full varying-axis set, whatever outer mesh
    # axes the caller sharded over.
    zero_bhs = q[..., 0].transpose(0, 2, 1) * 0.0
    m0 = zero_bhs + jnp.asarray(NEG_INF, q.dtype)
    l0 = zero_bhs
    o0 = q * 0.0
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        m, l, o, k_t, v_t = carry
        # after t rotations we hold the shard originally on (my - t) mod n
        src = (my - t) % n
        k0 = src * s_loc                      # global base of held shard
        if nb == 1:
            if causal:
                k_idx = k0 + jnp.arange(s_loc)
                mask = (k_idx[None, :] <= q_idx[:, None])[None, None]
            else:
                mask = None
            m, l, o = _block_accum(q, k_t, v_t, scale, mask, m, l, o)
        else:
            kr = jnp.moveaxis(
                k_t.reshape(b, nb, kv_block, h, d), 1, 0)
            vr = jnp.moveaxis(
                v_t.reshape(b, nb, kv_block, h, d), 1, 0)
            order = jnp.arange(nb)
            if kv_order == "rev":
                kr, vr, order = kr[::-1], vr[::-1], order[::-1]

            @jax.checkpoint
            def blk(c, xs):
                mc, lc, oc = c
                kb, vb, j = xs
                if causal:
                    k_idx = k0 + j * kv_block + jnp.arange(kv_block)
                    mask = (k_idx[None, :]
                            <= q_idx[:, None])[None, None]
                else:
                    mask = None
                return _block_accum(q, kb, vb, scale, mask,
                                    mc, lc, oc), None

            (m, l, o), _ = lax.scan(blk, (m, l, o), (kr, vr, order))
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return m, l, o, k_t, v_t

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    return o / l.transpose(0, 2, 1)[..., None]


def ulysses_attention(q, k, v, axis_name: str,
                      scale: Optional[float] = None, causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme). Call
    INSIDE shard_map with q/k/v sequence-sharded (B, S/n, H, D); requires
    H divisible by the axis size. The all_to_all trades the sequence
    sharding for a head sharding, full-sequence attention runs on H/n
    local heads, and a second all_to_all restores the sequence sharding.
    """
    n = _axis_size(axis_name)

    def seq_to_heads(x):  # (B, S/n, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # (B, S, H/n, D) -> (B, S/n, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = mha_forward(qh, kh, vh, scale, causal)
    return heads_to_seq(oh)


#: queries a block of `latent_attention`'s XLA form: a sequence that
#: divides into such blocks is computed block by block, a shorter one whole
LATENT_QUERY_BLOCK = 1024

#: `jax.ad_checkpoint.checkpoint_name`s of what the flash kernels' backward
#: (`pallas_kernels.flash_attention_pallas`) needs beside its operands and
#: a surrounding `jax.checkpoint` should save, not recompute: the heads'
#: outputs and the queries' logsumexps (8 MB + 0.13 MB a site of
#: xing4_ep8.step); a policy that saves them leaves the backward pass no
#: second forward kernel
FLASH_SAVED = ("flash_out", "flash_lse")


def _latent_core_xla(q_nope, q_rope, k_nope, k_rope, v, scale: float):
    """The core of latent attention, plain XLA: q_nope, k_nope (N, S, H,
    nope), q_rope (N, S, H, rope), k_rope (N, S, rope) shared by the
    heads, v (N, S, H, Dv) -> (N, S, H, Dv). A block of queries meets the
    keys up to its own end only: the blocks above the diagonal are never
    formed (5/8 of the square at four blocks), and only the diagonal
    block needs the mask; a block's float32 scores pass through HBM."""
    s = q_nope.shape[1]
    block = LATENT_QUERY_BLOCK if s % LATENT_QUERY_BLOCK == 0 else s
    outs = []
    for lo in range(0, s, block):
        hi = lo + block
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, lo:hi],
                             k_nope[:, :hi],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope[:, lo:hi],
                               k_rope[:, :hi],
                               preferred_element_type=jnp.float32)) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype),
                               v[:, :hi],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).astype(v.dtype)


def _latent_core_flash(flash, q_nope, q_rope, k_nope, k_rope, v,
                       scale: float):
    """The same through `flash`, a lowering of the registry op
    `flash_attn`: no (queries, keys) array exists in HBM, forward or
    backward. A head's key is its own dimensions and the rotary ones all
    heads share, side by side: 12.6 MB a site of xing4_ep8.step, which
    the kernels then read as one operand (a contraction of 192)."""
    n, s, h, _ = q_nope.shape
    return flash(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], (n, s, h, k_rope.shape[-1]))], axis=-1),
        v, scale=scale, causal=True, scope="mla")


def latent_attention(p, h, *, n_heads: int, nope: int, rope: int,
                     v_dim: int, cos, sin, scale: float,
                     norm_eps: float = 1e-6, flash=None):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434,
    section 2.1) over the `n_heads` heads whose up-projections `p` holds:
    h (N, S, C) -> (N, S, C), causal. Queries and keys-values pass
    through low-rank latents with their own norms (`w_dq`, `q_norm`,
    `w_uq`; `w_dkv`, `kv_norm`, `w_ukv`); a head scores on `nope`
    dimensions of its own plus a `rope`-wide rotary part whose key is
    shared by all heads, and reads values of `v_dim`. The down-projections
    are whole whatever the number of heads held, so a share of the heads
    gives its part of the sum `concat(P v) W_O`: nothing is exchanged here.
    Scores and softmax in float32, the probabilities rounded to the
    compute dtype before they meet the values, in both forms of the core:
    the blocked XLA one, or the flash kernels where the caller hands on
    `flash`, the registry's `flash_attn` lowering (its rule:
    `znicz/lm.py::BlockSpec.lowerings`)."""
    from veles_tpu.ops.lm import apply_rope, mm, rms_norm
    n, s, _ = h.shape
    c_q = rms_norm(mm(h, p["w_dq"]), p["q_norm"], norm_eps)
    q = mm(c_q, p["w_uq"]).reshape(n, s, n_heads, nope + rope)
    dkv = mm(h, p["w_dkv"])
    kv_rank = dkv.shape[-1] - rope
    c_kv = rms_norm(dkv[..., :kv_rank], p["kv_norm"], norm_eps)
    kv = mm(c_kv, p["w_ukv"]).reshape(n, s, n_heads, nope + v_dim)
    q_rope = apply_rope(q[..., nope:], cos, sin)
    k_rope = apply_rope(dkv[..., kv_rank:], cos, sin)       # (N, S, rope)
    core = _latent_core_xla if flash is None \
        else functools.partial(_latent_core_flash, flash)
    out = core(q[..., :nope], q_rope, kv[..., :nope], k_rope,
               kv[..., nope:], scale)
    return mm(out.reshape(n, s, n_heads * v_dim), p["w_o"])


# -- grouped-query attention over the keys a learned indexer selects -------------
#
# DeepSeek-V3.2-Exp's sparse attention (its "lightning indexer"), around
# grouped-query attention with per-head QK-norm: a small indexer scores
# every causal (query, key) pair, I[t, s] = sum_j w[t, j] relu(qI[t, j] .
# kI[s]); a query attends to the `topk` keys of highest score only. The
# selection of a query is fully described by ONE number, the score of its
# `topk`-th key: `kth_largest_key` finds it by counting passes over the
# scores and no sort, and `mask = causal & (score >= threshold)` is the
# selection. Two lowerings (`ops/variants.py`, op `dsa`). `xla`, here: the
# main attention scores every causal pair of a block of queries and masks
# (nothing is approximated). `pallas_flash`, further down: the same as four
# kernels. A kernel that visits only tiles holding a selected key is
# ROADMAP 2.17's.
#
# In `xla` nothing (S, S)-sized exists: a sequence is cut into `bands` of queries,
# band b meeting the keys up to its own end only, and a band is walked a
# block of `query_block` queries at a time by ONE `lax.map`. A band is a
# `jax.custom_vjp`: its backward walks the same blocks, recomputes a block
# from the saved threshold (no second selection) and differentiates it by
# `jax.vjp`, summing the keys' and values' gradients in float32; so scores
# are formed twice a step (forward, backward) whatever `jax.checkpoint`
# wraps around, given a policy that saves the names below.

#: `jax.ad_checkpoint.checkpoint_name`s of what a backward needs and a
#: surrounding `jax.checkpoint` should save, not recompute: the heads'
#: outputs and the index loss; of the `xla` lowering the thresholds (one
#: uint32 a query), of `pallas_flash` the queries' logsumexps and the
#: selection itself, 8 keys a byte
DSA_SAVED = ("dsa_threshold", "dsa_out", "dsa_index_loss", "dsa_lse",
             "dsa_selected")


def float_order_key(x):
    """float32 -> uint32 with the same order; no finite value or infinity
    maps to 0, which is free to mean "not a candidate"."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


#: bits of a threshold one pass of `kth_largest_key` settles. In the step
#: of keye2_ep8.long16k on a v5e (blocks of 256 queries x up to 16,384
#: keys, six layers), the index scores coming from a kernel (ISSUE 36: no
#: producer is left for XLA to fuse the search's reads into), 2 bits a pass
#: gave a step of 1,321 ms, 4 bits 1,400, 1 bit (plain bisection) 1,621 (my
#: chip runs, PR 36, one seed, the rest of the step the same; PR 35 read
#: 1,552 / 1,631 / 1,844 with the scores as an XLA fusion: the same order).
#: The selection pass timed ALONE still says otherwise (a layer's 13.6 ms
#: at 1 bit, 14.3 at 2, 28.5 at 4; PR 35: 0.161 / 0.222 / 0.618 ms a
#: block): decide in the step
SEARCH_DIGIT_BITS = 2


def kth_largest_key(keys, k: int, digit_bits: int = SEARCH_DIGIT_BITS):
    """keys (..., K) uint32 -> (...): the largest u with at least `k` keys
    >= u, which is the `k`-th largest key; 0 where the row is shorter than
    `k` (then every key is >= u). A search by digits of `digit_bits` bits
    from the top: each pass counts the keys at or above the 2^bits - 1
    candidates that extend the prefix found so far, one read of `keys`."""
    if 32 % digit_bits:
        raise ValueError(f"{digit_bits} bits a digit do not divide 32")
    digits = jnp.arange(1, 1 << digit_bits, dtype=jnp.uint32)

    def one_pass(i, prefix):
        shift = (32 - digit_bits * (i + 1)).astype(jnp.uint32)
        cands = prefix[..., None] | (digits << shift)
        counts = (keys[..., None, :] >= cands[..., :, None]).sum(
            axis=-1, dtype=jnp.int32)
        # the counts fall as the digit rises: as many digits pass as the
        # largest that does
        d = (counts >= k).sum(axis=-1).astype(jnp.uint32)
        return prefix | (d << shift)

    return lax.fori_loop(0, 32 // digit_bits, one_pass,
                         jnp.zeros(keys.shape[:-1], jnp.uint32))


def index_scores(qi, w, ki):
    """I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]): qi (Tq, Hi, Di),
    w (Tq, Hi) float32, ki (K, Di) -> (Tq, K) float32."""
    s = jnp.einsum("qhd,kd->hqk", qi, ki,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w.T[:, :, None]).sum(axis=0)


def select_topk(index, causal, topk: int, thr=None):
    """(mask (Tq, K): the `topk` causal keys of highest `index` of every
    query, all of them where there are fewer; the threshold (Tq,) uint32
    that describes it). Given `thr`, the mask it describes: nothing is
    searched. Ties at the threshold are all selected."""
    key = float_order_key(lax.stop_gradient(index))
    if thr is None:
        thr = kth_largest_key(jnp.where(causal, key, jnp.uint32(0)), topk)
    return causal & (key >= thr[:, None]), thr


def _dsa_block(static, q, w, qi, pos, k, v, ki, thr=None):
    """One block of queries of one sequence against the keys [0, K):
    q (Tq, H, D), w (Tq, Hi), qi (Tq, Hi, Di), pos (Tq,) the queries'
    positions, k and v (K, Hkv, D), ki (K, Di). Returns (the heads'
    outputs (Tq, H*D), sum over the queries of KL(p || softmax_S(I)), the
    thresholds, the selection (Tq, K))."""
    topk, scale = static
    tq, h, d = q.shape
    kvh = k.shape[1]
    causal = jnp.arange(k.shape[0])[None, :] <= pos[:, None]
    with jax.named_scope("indexer"):
        index = index_scores(qi, w, ki)
    with jax.named_scope("select"):
        mask, thr = select_topk(index, causal, topk, thr)
    with jax.named_scope("attend"):
        scores = jnp.einsum("qhgd,khd->hgqk", q.reshape(tq, kvh, h // kvh, d),
                            k, preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out.astype(q.dtype).reshape(tq, h * d)
    with jax.named_scope("index_loss"):
        p = lax.stop_gradient(probs.mean(axis=(0, 1)))
        log_q = index - jax.nn.logsumexp(
            jnp.where(mask, index, -jnp.inf), axis=-1, keepdims=True)
        live = mask & (p > 0)
        kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_q),
                       0.0).sum()
    return out, kl, thr, mask


def _blocks(a, tq: int):
    return a.reshape((a.shape[0] // tq, tq) + a.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dsa_band(static, q, w, qi, k, v, ki):
    """The queries [q0, q0 + Tb) of one sequence against the keys [0, K),
    `static = (topk, scale, query block, q0)`. Returns (outputs (Tb, H*D),
    the index loss's sum over the band, thresholds (Tb,) uint32, selected
    pairs, the selection packed 8 keys a byte (Tb, K / 8))."""
    topk, scale, tq, q0 = static
    pos = q0 + jnp.arange(q.shape[0], dtype=jnp.int32)

    def one(xs):
        out, kl, thr, mask = _dsa_block((topk, scale), *xs, k, v, ki)
        return (out, kl, thr, mask.sum(dtype=jnp.int32),
                jnp.packbits(mask, axis=-1))

    out, kl, thr, n_sel, bits = lax.map(
        one, tuple(_blocks(a, tq) for a in (q, w, qi, pos)))
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
    return flat(out), kl.sum(), flat(thr), n_sel.sum(), flat(bits)


def _dsa_band_fwd(static, q, w, qi, k, v, ki):
    from jax.ad_checkpoint import checkpoint_name
    out, kl, thr, n_sel, bits = _dsa_band(static, q, w, qi, k, v, ki)
    out = checkpoint_name(out, "dsa_out")
    kl = checkpoint_name(kl, "dsa_index_loss")
    thr = checkpoint_name(thr, "dsa_threshold")
    return (out, kl, thr, n_sel, bits), (q, w, qi, k, v, ki, thr)


def _dsa_band_bwd(static, res, cts):
    topk, scale, tq, q0 = static
    q, w, qi, k, v, ki, thr = res
    d_out, d_kl = cts[0], cts[1]
    pos = q0 + jnp.arange(q.shape[0], dtype=jnp.int32)

    def one(carry, xs):
        qb, wb, qib, pb, thrb, db = xs

        def block(qb, wb, qib, k, v, ki):
            return _dsa_block((topk, scale), qb, wb, qib, pb, k, v, ki,
                              thr=thrb)[:2]

        _, vjp = jax.vjp(block, qb, wb, qib, k, v, ki)
        dq, dw, dqi, *dkv = vjp((db, d_kl))
        return tuple(c + g.astype(jnp.float32)
                     for c, g in zip(carry, dkv)), (dq, dw, dqi)

    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope("dsa"):
        zeros = tuple(jnp.zeros(a.shape, jnp.float32) for a in (k, v, ki))
        dkv, dqs = lax.scan(one, zeros, tuple(
            _blocks(a, tq) for a in (q, w, qi, pos, thr, d_out)))
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
    return (*(flat(a) for a in dqs),
            *(g.astype(a.dtype) for g, a in zip(dkv, (k, v, ki))))


_dsa_band.defvjp(_dsa_band_fwd, _dsa_band_bwd)


def dsa_tiling(seq: int, query_block: int, key_bands: int):
    """(queries a block, bands) a sequence of `seq` tokens is walked in:
    `query_block` where it divides the sequence, else the sequence whole;
    the most bands up to `key_bands` that hold whole blocks."""
    tq = query_block if seq % query_block == 0 else seq
    bands = max(b for b in range(1, max(key_bands, 1) + 1)
                if seq % (b * tq) == 0)
    return tq, bands


def _dsa_sequence_xla(static, q, w, qi, k, v, ki):
    """One sequence through the bands of the `xla` lowering: (outputs
    (S, H*D), the index loss's sum, selected pairs, the selection packed
    (S, S/8))."""
    topk, scale, tq, bands, _interpret = static
    s = q.shape[0]
    per = s // bands
    outs = []
    for b in range(bands):
        lo, hi = b * per, (b + 1) * per
        out, kl, _thr, n_sel, bits = _dsa_band(
            (topk, scale, tq, lo), q[lo:hi], w[lo:hi], qi[lo:hi], k[:hi],
            v[:hi], ki[:hi])
        outs.append((out, kl, n_sel,
                     jnp.pad(bits, ((0, 0), (0, (s - hi) // 8)))))
    out, kl, n_sel, bits = zip(*outs)
    return jnp.concatenate(out), sum(kl), sum(n_sel), jnp.concatenate(bits)


# -- the same attention, its scores never in HBM (`pallas_flash`) ----------------
#
# The `xla` lowering above writes the 32 heads' float32 scores of a block
# to HBM and reads them back for every pass of the softmax: 5.4 s a step of
# keye2_ep8.long16k on a v5e (my chip run, PR 35). Here the main attention
# is four kernels (`ops/pallas_kernels.py`, `veles_dsa_attend_*`,
# `veles_dsa_pmean`): the flash recurrence over the selection (int8, (S, S):
# the one thing of that size beside the mean-head probabilities the index
# loss reads, one head's worth), forward; the mean-head probabilities from
# the saved logsumexp; dQ; dK and dV. The indexer's scores and their
# gradient are two more (`veles_dsa_index_fwd`, `_bwd`, ISSUE 36), a block
# of queries a call inside the loops below: the index heads' score tiles
# stay in VMEM, where as plain XLA the (16 heads, 256, keys) float32
# scores, their sign and their cotangent went through HBM, 388 ms of the
# 617 the mechanism cost a step; a shape `pallas_kernels.dsa_index_view`
# refuses traces `index_scores` in their place. The threshold search and
# the index loss stay plain XLA over a block's scores (one head's worth).

def _dsa_bands(static, s: int) -> int:
    """Bands of queries the parts beside the main attention walk a
    sequence in: as `dsa_tiling` says, each a band a kernel takes (whole
    tiles of 128 keys)."""
    from veles_tpu.ops import pallas_kernels as pk
    tq, bands = static[2], static[3]
    return max(b for b in range(1, bands + 1)
               if s % (b * tq) == 0 and pk.dsa_view(s // b, 128))


def _block_index_scores(static, qi, w, ki, q0):
    """`index_scores` of a block of queries, the sequence's from its
    `q0`-th on (an int32 scalar, traced or not), against the keys [0, K),
    right at every causal pair: through the kernels where they take the
    shape (`pallas_kernels.index_scores_pallas`: a tile wholly above the
    diagonal reads 0), differentiable either way."""
    from veles_tpu.ops import pallas_kernels as pk
    with jax.named_scope("indexer"):
        if pk.dsa_index_view(ki.shape[0], *qi.shape[1:]):
            return pk.index_scores_pallas(qi, w, ki, q0, static[4])
        return index_scores(qi, w, ki)


def _dsa_index_pass(static, qi, w, ki):
    """The selection (S, S) int8 of one sequence: a band of queries
    against the keys up to the band's end, a block of queries at a time."""
    topk, tq = static[0], static[2]
    s = qi.shape[0]
    bands = _dsa_bands(static, s)
    per = s // bands
    masks = []
    for b in range(bands):
        lo, hi = b * per, (b + 1) * per
        keys, kib = jnp.arange(hi)[None, :], ki[:hi]

        def one(xs, keys=keys, kib=kib):
            qib, wb, pb = xs
            index = _block_index_scores(static, qib, wb, kib, pb[0])
            with jax.named_scope("select"):
                mask, _ = select_topk(index, keys <= pb[:, None], topk)
            return mask.astype(jnp.int8)

        mask = lax.map(one, tuple(_blocks(a, tq) for a in (
            qi[lo:hi], w[lo:hi], jnp.arange(lo, hi, dtype=jnp.int32))))
        masks.append(jnp.pad(mask.reshape(per, hi), ((0, 0), (0, s - hi))))
    return jnp.concatenate(masks)


def _dsa_index_loss(static, qh, kh, lse, mask, qi, w, ki, d_kl=None):
    """The index loss's sum over one sequence or, given its cotangent
    `d_kl`, its gradient by (qi, w, ki). A band of queries at a time
    against the keys up to the band's end: the mean-head probabilities of
    a band (`veles_dsa_pmean`) are the one float32 array of (queries,
    keys) there is, 256 MB of it at 16,384 tokens in four bands; the
    index scores are formed again a block at a time beside them, and in
    the gradient once more (`jax.vjp`: the kernels keep no scores)."""
    from veles_tpu.ops import pallas_kernels as pk
    _topk, scale, tq, _bands, interpret = static
    s = qi.shape[0]
    bands = _dsa_bands(static, s)
    per = s // bands
    total = jnp.zeros((), jnp.float32)
    dki = jnp.zeros(ki.shape, jnp.float32)
    dqis, dws = [], []
    for b in range(bands):
        lo, hi = b * per, (b + 1) * per
        with jax.named_scope("index_loss"):
            p = pk.dsa_pmean_pallas(
                qh[:, lo:hi], kh[:, :hi], lse[:, lo:hi, None],
                mask[lo:hi, :hi], scale=scale, q0=lo, interpret=interpret)
        kib = ki[:hi]

        def kl_of(qib, wb, mb, pb, q0b):
            keep = mb != 0
            index = _block_index_scores(static, qib, wb, kib, q0b)
            with jax.named_scope("index_loss"):
                log_q = index - jax.nn.logsumexp(
                    jnp.where(keep, index, -jnp.inf), axis=-1, keepdims=True)
                live = keep & (pb > 0)
                return jnp.where(live, pb * (jnp.log(jnp.where(
                    live, pb, 1.0)) - log_q), 0.0).sum()

        def grad_of(carry, xs):
            qib, wb, mb, pb, q0b = xs
            keep = mb != 0
            index, vjp = jax.vjp(
                lambda *a: _block_index_scores(static, *a, q0b), qib, wb, kib)
            with jax.named_scope("index_loss"):
                # d/dI of sum_s p (log p - I + logsumexp_S(I))
                soft = jax.nn.softmax(jnp.where(keep, index, -jnp.inf),
                                      axis=-1)
                d_index = d_kl * (soft * pb.sum(axis=-1, keepdims=True) - pb)
            with jax.named_scope("indexer"):
                dqib, dwb, dkib = vjp(d_index)
            return carry + dkib.astype(jnp.float32), (dqib, dwb)

        xs = tuple(_blocks(a, tq) for a in (
            qi[lo:hi], w[lo:hi], mask[lo:hi, :hi], p)) \
            + (jnp.arange(lo, hi, tq, dtype=jnp.int32),)
        if d_kl is None:
            total = total + lax.map(lambda x: kl_of(*x), xs).sum()
        else:
            dkib, (dqib, dwb) = lax.scan(
                grad_of, jnp.zeros(kib.shape, jnp.float32), xs)
            dki = dki.at[:hi].add(dkib)
            dqis.append(dqib.reshape((-1,) + dqib.shape[2:]))
            dws.append(dwb.reshape((-1,) + dwb.shape[2:]))
    if d_kl is None:
        return total
    return jnp.concatenate(dqis), jnp.concatenate(dws), dki.astype(ki.dtype)


def _heads_first(a, heads: int):
    """(S, H*D) or (S, H, D) -> (H, S, D)."""
    return a.reshape(a.shape[0], heads, -1).transpose(1, 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dsa_sequence_pallas(static, q, w, qi, k, v, ki):
    """One sequence through the kernels; what `_dsa_sequence_xla`
    returns."""
    return _dsa_sequence_pallas_fwd(static, q, w, qi, k, v, ki)[0]


def _dsa_sequence_pallas_fwd(static, q, w, qi, k, v, ki):
    from jax.ad_checkpoint import checkpoint_name

    from veles_tpu.ops import pallas_kernels as pk
    _topk, scale, _tq, _bands, interpret = static
    s = q.shape[0]
    mask = _dsa_index_pass(static, qi, w, ki)
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))
    with jax.named_scope("attend"):
        out, lse = pk.dsa_attend_forward_pallas(
            qh, kh, vh, mask, scale=scale, interpret=interpret)
        out = out.transpose(1, 0, 2).reshape(s, -1)
        lse = lse[:, 0, :]      # (H, S): what the backward keeps
    kl = _dsa_index_loss(static, qh, kh, lse, mask, qi, w, ki)
    keep = mask != 0
    out = checkpoint_name(out, "dsa_out")
    kl = checkpoint_name(kl, "dsa_index_loss")
    lse = checkpoint_name(lse, "dsa_lse")
    # the selection, 8 keys a byte (32 MB a block of the model at 16,384
    # tokens): what the step state shows, and what the backward unpacks
    # in the place of index scores formed a second time
    bits = checkpoint_name(jnp.packbits(keep, axis=-1), "dsa_selected")
    return ((out, kl, keep.sum(dtype=jnp.int32), bits),
            (q, w, qi, k, v, ki, bits, out, lse))


def _dsa_sequence_pallas_bwd(static, res, cts):
    from veles_tpu.ops import pallas_kernels as pk
    _topk, scale, _tq, _bands, interpret = static
    d_out, d_kl = cts[0], cts[1]
    q, w, qi, k, v, ki, bits, out, lse = res
    h = q.shape[1]
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope("dsa"):
        with jax.named_scope("select"):
            mask = jnp.unpackbits(bits, axis=-1).astype(jnp.int8)
        qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))
        dqi, dw, dki = _dsa_index_loss(static, qh, kh, lse, mask, qi, w, ki,
                                       d_kl)
        with jax.named_scope("attend"):
            do = _heads_first(d_out, h)
            di = (do.astype(jnp.float32)
                  * _heads_first(out, h).astype(jnp.float32)
                  ).sum(axis=-1, keepdims=True)
            dq, dk, dv = pk.dsa_attend_backward_pallas(
                qh, kh, vh, do, lse[..., None], di, mask, scale=scale,
                interpret=interpret)
    return (dq.transpose(1, 0, 2), dw, dqi, dk.transpose(1, 0, 2),
            dv.transpose(1, 0, 2), dki)


_dsa_sequence_pallas.defvjp(_dsa_sequence_pallas_fwd,
                            _dsa_sequence_pallas_bwd)

#: the `dsa` op's lowerings (`ops/variants.py`): one sequence's (outputs,
#: index loss sum, selected pairs, packed selection)
DSA_LOWERINGS = {"xla": _dsa_sequence_xla,
                 "pallas_flash": _dsa_sequence_pallas}


def indexed_attention(p, h, *, n_heads: int, kv_heads: int, head_dim: int,
                      index_heads: int, index_dim: int, topk: int,
                      rope_theta: float, query_block: int = 256,
                      key_bands: int = 4, norm_eps: float = 1e-6,
                      lowering: str = "xla", interpret: bool = False):
    """Grouped-query attention over the `topk` keys a learned indexer
    selects for every query (section comments above): h (N, S, C) ->
    (y (N, S, C), {`index_loss`: mean over the tokens of KL(mean-head
    attention || softmax of the index scores, both over the selected
    keys), whose gradient reaches the indexer alone; `pairs_selected`
    int32; `selected` (N * S, S / 8) uint8, the selection packed as
    `numpy.packbits` packs}). `p`: `w_q` (C, H*D), `w_k`, `w_v` (C,
    Hkv*D), `q_norm`, `k_norm` (D,) the RMS scales of a head, `w_o`
    (H*D, C); the indexer's `idx_w_q` (C, Hi*Di), `idx_w_k` (C, Di),
    `idx_k_norm`, `idx_k_bias` (Di,) its key's LayerNorm, `idx_w_w`
    (C, Hi). Query head j reads key-value head j // (H / Hkv); rotary
    embedding over the whole head (and the whole indexer head), two-halves
    layout. The indexer reads `stop_gradient(h)`. `lowering` names one of
    `DSA_LOWERINGS` (the caller resolves it: `pallas_flash` takes
    sequences `pallas_kernels.dsa_view` admits)."""
    from veles_tpu.ops.lm import (apply_rope, layer_norm, mm, rms_norm,
                                  rope_inv_freq, rope_tables)
    n, s, _ = h.shape
    with jax.named_scope("qkv"):
        cos, sin = rope_tables(s, rope_inv_freq(head_dim, rope_theta))
        q = mm(h, p["w_q"]).reshape(n, s, n_heads, head_dim)
        k = mm(h, p["w_k"]).reshape(n, s, kv_heads, head_dim)
        v = mm(h, p["w_v"]).reshape(n, s, kv_heads, head_dim)
        q = apply_rope(rms_norm(q, p["q_norm"], norm_eps), cos, sin)
        k = apply_rope(rms_norm(k, p["k_norm"], norm_eps), cos, sin)
    with jax.named_scope("indexer"):
        cos, sin = rope_tables(s, rope_inv_freq(index_dim, rope_theta))
        hs = lax.stop_gradient(h)
        qi = apply_rope(mm(hs, p["idx_w_q"]).reshape(
            n, s, index_heads, index_dim), cos, sin)
        ki = apply_rope(layer_norm(mm(hs, p["idx_w_k"]), p["idx_k_norm"],
                                   p["idx_k_bias"], norm_eps), cos, sin)
        w = jnp.matmul(hs, p["idx_w_w"], preferred_element_type=jnp.float32
                       ) * (index_heads ** -0.5 * index_dim ** -0.5)
    tq, bands = dsa_tiling(s, query_block, key_bands)
    one_sequence = functools.partial(
        DSA_LOWERINGS[lowering],
        (topk, head_dim ** -0.5, tq, bands, interpret))
    # a sequence at a time: attention does not cross sequences
    outs = [one_sequence(q[i], w[i], qi[i], k[i], v[i], ki[i])
            for i in range(n)]
    out, kl, n_sel, bits = (jnp.stack(a) for a in zip(*outs))
    with jax.named_scope("attend"):
        y = mm(out.reshape(n, s, n_heads * head_dim), p["w_o"])
    return y, {"index_loss": kl.sum() / (n * s),
               "pairs_selected": n_sel.sum(),
               "selected": bits.reshape(n * s, s // 8)}


# -- grouped-query attention behind an output gate ----------------------------------

def _grouped_core_xla(q, k, v, scale: float):
    """Causal softmax attention, plain XLA: q (N, S, H, D), k and v (N, S,
    Hkv, D), query head j reading key-value head j // (H / Hkv) -> (N, S,
    H, D). Blocked as `_latent_core_xla`: a block of queries meets the
    keys up to its own end, and its float32 scores pass through HBM."""
    n, s, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(n, s, kv, h // kv, d)
    block = LATENT_QUERY_BLOCK if s % LATENT_QUERY_BLOCK == 0 else s
    outs = []
    for lo in range(0, s, block):
        hi = lo + block
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype),
                               v[:, :hi],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).astype(v.dtype).reshape(n, s, h, d)


def gated_attention(p, h, *, n_heads: int, kv_heads: int, head_dim: int,
                    rotary_dim: int, rope_theta: float,
                    norm_eps: float = 1e-6, norm_offset: float = 0.0,
                    flash=None):
    """Grouped-query attention whose query projection carries an output
    gate (Qwen3-Next's full-attention layer): h (N, S, C) -> (N, S, C),
    causal. `w_q` (C, H 2D) gives a head its query and its gate side by
    side, `w_k` and `w_v` (C, Hkv D); q and k pass through an RMSNorm over
    the head with a learned scale (`norm_offset` 1: zero-centred); the
    rotary embedding turns the first `rotary_dim` of a head, two-halves
    layout, positions from 0; softmax at D^-1/2, H / Hkv queries a
    key-value head; out = (attn * sigmoid(gate)) `w_o`. The core is the
    blocked XLA form, or `flash`, the registry's `flash_attn` lowering,
    which is handed every query head's own copy of its key-value head."""
    from veles_tpu.ops.lm import (apply_rope, mm, rms_norm, rope_inv_freq,
                                  rope_tables)
    n, s, _ = h.shape
    qg = mm(h, p["w_q"]).reshape(n, s, n_heads, 2 * head_dim)
    q, gate = qg[..., :head_dim], qg[..., head_dim:]
    k = mm(h, p["w_k"]).reshape(n, s, kv_heads, head_dim)
    v = mm(h, p["w_v"]).reshape(n, s, kv_heads, head_dim)
    cos, sin = rope_tables(s, rope_inv_freq(rotary_dim, rope_theta))

    def turned(x, scale):
        x = rms_norm(x, scale, norm_eps, offset=norm_offset)
        return jnp.concatenate([apply_rope(x[..., :rotary_dim], cos, sin),
                                x[..., rotary_dim:]], axis=-1)

    q, k = turned(q, p["q_norm"]), turned(k, p["k_norm"])
    scale = head_dim ** -0.5
    if flash is None:
        out = _grouped_core_xla(q, k, v, scale)
    else:
        rep = n_heads // kv_heads
        out = flash(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                    scale=scale, causal=True, scope="attn")
    out = out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return mm(out.astype(h.dtype).reshape(n, s, n_heads * head_dim), p["w_o"])
