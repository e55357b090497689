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


#: queries a block of `latent_attention`: a sequence that divides into
#: such blocks is computed block by block, a shorter one whole
LATENT_QUERY_BLOCK = 1024


def latent_attention(p, h, *, n_heads: int, nope: int, rope: int,
                     v_dim: int, cos, sin, scale: float,
                     norm_eps: float = 1e-6):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434,
    section 2.1) over the `n_heads` heads whose up-projections `p` holds:
    h (N, S, C) -> (N, S, C), causal. Queries and keys-values pass
    through low-rank latents with their own norms (`w_dq`, `q_norm`,
    `w_uq`; `w_dkv`, `kv_norm`, `w_ukv`); a head scores on `nope`
    dimensions of its own plus a `rope`-wide rotary part whose key is
    shared by all heads, and reads values of `v_dim`. The down-projections
    are whole whatever the number of heads held, so a share of the heads
    gives its part of the sum `concat(P v) W_O`: nothing is exchanged here.
    Plain XLA: scores and softmax in float32, a block of queries against
    its keys at a time."""
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
    # a block of queries meets the keys up to its own end only: the
    # blocks above the diagonal are never formed (5/8 of the square at
    # four blocks), and only the diagonal block needs the mask
    block = LATENT_QUERY_BLOCK if s % LATENT_QUERY_BLOCK == 0 else s
    outs = []
    for lo in range(0, s, block):
        hi = lo + block
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi, :, :nope],
                             kv[:, :hi, :, :nope],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope[:, lo:hi],
                               k_rope[:, :hi],
                               preferred_element_type=jnp.float32)) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs.astype(h.dtype),
                               kv[:, :hi, :, nope:],
                               preferred_element_type=jnp.float32))
    out = jnp.concatenate(outs, axis=1)
    return mm(out.astype(h.dtype).reshape(n, s, n_heads * v_dim), p["w_o"])
