"""Mixture-of-experts ops: dense golden routing + expert-parallel form.

Absent in the reference (2015-era framework); added because the TPU
build's distributed layer treats expert parallelism as a first-class mesh
axis alongside data/model/sequence. Design follows the standard TPU
recipe: top-1 (switch) routing, capacity-bounded dispatch expressed as
dense einsums with a one-hot dispatch mask (MXU-friendly, no gather
loops), and `lax.all_to_all` to exchange tokens when experts are sharded
over a mesh axis.

`moe_forward` (all experts local) is the golden model; `moe_forward_ep`
(inside shard_map, experts sharded over `axis_name`) must match it —
tested on the virtual 8-device mesh.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.lax import axis_size as _axis_size


def router_probs(x, wr):
    """x: (N, D), wr: (D, E) -> (N, E) softmax router probabilities."""
    return jax.nn.softmax(x @ wr, axis=-1)


def top1_dispatch(probs, capacity: int):
    """Switch-style top-1 routing with per-expert capacity.

    Returns (dispatch, combine):
    - dispatch: (N, E, C) one-hot — token n occupies slot c of expert e;
    - combine:  (N, E, C) = dispatch · router gate (for the weighted sum).
    Tokens beyond an expert's capacity are DROPPED (standard switch
    behavior; the residual path keeps them alive in the layer below).
    """
    n, e = probs.shape
    expert = probs.argmax(axis=-1)                      # (N,)
    onehot = jax.nn.one_hot(expert, e, dtype=probs.dtype)  # (N, E)
    # position of each token within its expert's queue (prefix count)
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot   # (N, E)
    pos = pos.sum(axis=-1).astype(jnp.int32)               # (N,)
    keep = pos < capacity
    slot = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # (N, C)
    dispatch = onehot[:, :, None] * slot[:, None, :] \
        * keep[:, None, None].astype(probs.dtype)
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def expert_ffn(xe, w1, b1, w2, b2):
    """Per-expert 2-layer FFN. xe: (E, C, D), w1: (E, D, H), w2: (E, H, D)."""
    h = jnp.maximum(jnp.einsum("ecd,edh->ech", xe, w1) + b1[:, None, :],
                    0.0)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def moe_forward(x, wr, w1, b1, w2, b2, capacity: Optional[int] = None):
    """Golden dense MoE: all experts resident. x: (N, D) -> (N, D)."""
    n, d = x.shape
    e = wr.shape[1]
    if capacity is None:
        capacity = max(1, (2 * n) // e)
    probs = router_probs(x, wr)
    dispatch, combine = top1_dispatch(probs, capacity)
    xe = jnp.einsum("nd,nec->ecd", x, dispatch)       # gather to slots
    ye = expert_ffn(xe, w1, b1, w2, b2)               # (E, C, D)
    return jnp.einsum("ecd,nec->nd", ye, combine)     # weighted scatter


def moe_forward_ep(x, wr, w1, b1, w2, b2, axis_name: str,
                   capacity: Optional[int] = None):
    """Expert-parallel MoE inside shard_map: each device holds N/n_dev
    tokens and E/n_dev experts (w1/b1/w2/b2 sharded on the expert dim;
    x and wr sharded on tokens / replicated).

    Routing is computed locally over ALL E experts, then a token
    `all_to_all` ships each device's per-expert slot buffers to the
    device owning those experts; the expert FFN runs on local experts;
    a second `all_to_all` returns the results. This is the standard
    expert-parallel exchange, riding ICI.
    """
    n_dev = _axis_size(axis_name)
    n_loc, d = x.shape
    e_total = wr.shape[1]
    e_loc = w1.shape[0]
    assert e_loc * n_dev == e_total, (e_loc, n_dev, e_total)
    if capacity is None:
        capacity = max(1, (2 * n_loc) // e_total)
    probs = router_probs(x, wr)                        # (Nloc, E)
    dispatch, combine = top1_dispatch(probs, capacity)  # (Nloc, E, C)
    xe = jnp.einsum("nd,nec->ecd", x, dispatch)        # (E, C, D) local
    # exchange: split the expert dim across devices; after all_to_all each
    # device holds its OWN experts' slots from every source device:
    # (E, C, D) -> (n_dev·Eloc, C, D) -> a2a -> (n_dev, Eloc, C, D)
    xe = xe.reshape(n_dev, e_loc, capacity, d)
    xe = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)                   # (n_dev, Eloc, C, D)
    xe = xe.transpose(1, 0, 2, 3).reshape(e_loc, n_dev * capacity, d)
    ye = expert_ffn(xe, w1, b1, w2, b2)                # local experts
    ye = ye.reshape(e_loc, n_dev, capacity, d).transpose(1, 0, 2, 3)
    ye = lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)                   # back to sources
    ye = ye.reshape(e_total, capacity, d)
    return jnp.einsum("ecd,nec->nd", ye, combine)
