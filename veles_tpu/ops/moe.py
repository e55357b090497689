"""Mixture-of-experts ops.

Two things live here. First, the top-1 golden model and its
expert-parallel form, what the `znicz/moe.py` unit runs (no benchmark
cell): switch routing with a capacity, dispatch and combine as dense
einsums over a one-hot mask, and `lax.all_to_all` where the experts are
sharded over a mesh axis. `moe_forward` (all experts local) is the golden
model; `moe_forward_ep` (inside shard_map) must match it, tested on the
virtual 8-device mesh.

Second, and two thirds of the file, the held experts' dropless top-k
path, what the three language-model cells run (`znicz/lm.py::BlockSpec`):
a chip routes over ALL the experts and computes the `held` ones
(`held_experts_swiglu`). The (token, slot) pairs are sorted by expert
into ONE buffer of static size, the held ones first; `_take_rows` fills
its rows, three grouped products (`_grouped_product`: `lax.ragged_dot`
or, where the layer table says `grouped="pallas"`, `veles_gmm` /
`veles_tgmm`) run the SwiGLU over it, and `_sum_rows`, the combine, adds
every token's rows up (`veles_seg_sum` over the held rows, or a gather of
a row a pair). The buffer has two sizes under one `lax.cond`
(`_held_swiglu`): `fast_rows` where the held pairs fit them, every pair
there can be where they do not, that one walked in windows of `fast_rows`
past `_WHOLE_BUFFER_MAX`. Which form a product or the combine traces is
the package's one rule: the caller's `kernels` (`variants.kernels_ok`:
the step allows kernels and the platform runs them), then the kernel's
own view of the shape, asked here; `interpret` is read where
`pallas_kernels` is called.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.lax import axis_size as _axis_size

#: `jax.ad_checkpoint.checkpoint_name` of what `held_experts_swiglu`
#: returns, which a surrounding `jax.checkpoint` should save, not
#: recompute, where the backward of what wraps the expert layer asks for
#: the layer's output: a hyper-connection's does, for the gradient of its
#: own mixing weights (`x + y` does not, and nothing is kept for it).
#: T x C of the compute dtype a layer (58.7 MB in xing4_ep8.step) against a
#: third run of the three grouped products: the layer's own backward
#: (`_held_swiglu_bwd`) recomputes them once more whatever is saved
MOE_SAVED = ("moe_held_out",)


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


# -- top-k routing over all experts, the held ones computed (dropless) -----------

def route_topk(scores, bias, k: int):
    """The `k` experts of every token by `scores + bias` and their scores:
    (idx (T, k) int32, picked (T, k)). The bias acts on the SELECTION only
    (DeepSeek-V3's `noaux_tc`, arXiv:2412.19437 section 2.1.2): no gradient
    reaches it, and the weights are made from the unbiased scores."""
    _, idx = lax.top_k(lax.stop_gradient(scores + bias), k)
    return idx, jnp.take_along_axis(scores, idx, axis=1)


def softmax_topk_gates(logits, k: int):
    """Softmax scoring with renormalised top-k gates (Qwen3-MoE's router
    under `norm_topk_prob`): r = softmax(logits) over all experts in
    float32; the `k` highest; gates r_e / (sum of the k). Returns
    (r (T, E), idx (T, k) int32, gates (T, k))."""
    r = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx, picked = route_topk(r, 0.0, k)
    return r, idx, picked / picked.sum(axis=-1, keepdims=True)


def balance_loss(r, idx, batch: int = 1):
    """The auxiliary load-balancing loss of one expert layer (Switch
    Transformer, arXiv:2101.03961, eq. 4, as Qwen3-MoE sums it over the k
    slots): E sum_e (slots_e / S) mean_t r[t, e] over the S tokens of a
    sequence, mean over the `batch` sequences (a sequence is a sample). r
    (T, E) are the router's probabilities, idx (T, k) the selected
    experts, whose counts carry no gradient. k at perfect balance."""
    t, e = r.shape
    r = r.reshape(batch, t // batch, e)
    share = jax.vmap(lambda i: expert_loads(i, e))(
        idx.reshape(batch, t // batch, -1)).astype(jnp.float32) \
        / (t // batch)
    return e * jnp.sum(share * r.mean(axis=1)) / batch


def expert_loads(idx, n_experts: int):
    """Slots each of ALL the experts was given on these tokens: (E,) int32."""
    return (idx[..., None] == jnp.arange(n_experts, dtype=idx.dtype)
            ).sum(axis=(0, 1), dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_rows(h, token_of, pairs, n_live, seg=None):
    """Row r of the sorted buffer: its token's row of h (T, C), zeros past
    the `n_live` live rows. `token_of` (R,) names each sorted row's token,
    `pairs` says the same the other way round, for `_sum_rows`, the
    transpose: each direction of the pair is a GATHER or a product, never
    a scatter (a scatter-add of 6,144 rows of 3,584 measured 2.4 ms on a
    v5e where the gather takes 0.27, chip run of PR 32)."""
    live = (jnp.arange(token_of.shape[0]) < n_live)[:, None]
    return jnp.where(live, jnp.take(h, token_of, axis=0), 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sum_rows(y, token_of, pairs, n_live, seg=None):
    """Token t's sum of its live sorted rows of y (R, C): (T, C), summed
    in float32 and rounded once. `seg` None: `pairs` (T, k) is every
    (token, slot) pair's sorted row, gathered whether held or not (the
    plain form: off a TPU, under GSPMD, a shape with no view). `seg` a
    token tile (`_seg_tile`): `pairs` is `pallas_kernels.seg_sum_plan`'s,
    the rows are in token order and the live ones alone are summed, as
    one-hot products by `veles_seg_sum`, which never reads a slot."""
    if seg is not None:
        from veles_tpu.ops import pallas_kernels as pk
        return pk.seg_sum(y, pairs, n_live, seg, pk._interpret())
    at = jnp.minimum(pairs, y.shape[0] - 1)
    live = (pairs < n_live)[..., None]
    return jnp.where(live, jnp.take(y, at, axis=0), 0).astype(
        jnp.float32).sum(axis=1).astype(y.dtype)


_take_rows.defvjp(
    lambda h, t, p, n, seg: (_take_rows(h, t, p, n, seg), (t, p, n)),
    lambda seg, res, g: (_sum_rows(g, *res, seg), None, None, None))
_sum_rows.defvjp(
    lambda y, t, p, n, seg: (_sum_rows(y, t, p, n, seg), (t, p, n)),
    lambda seg, res, g: (_take_rows(g, *res, seg), None, None, None))


def _grouped_product(sizes, rows: int, like, w, grouped: str,
                     kernels: bool):
    """f(x, w) -> x's rows of group g times w[g] over a sorted buffer of
    `rows` rows whose groups hold `sizes`: `lax.ragged_dot` (a grouped
    product of XLA's own on a TPU, (512, 512, 256) tiles), or, where the
    layer table asks for them (`grouped="pallas"`), kernels may be traced
    and `pallas_kernels.gmm_view` has a view of the shape, the
    `veles_gmm` / `veles_tgmm` pair: one work list for the three products
    and their backward. Neither writes the rows past the last group."""
    if kernels and grouped == "pallas":
        from veles_tpu.ops import pallas_kernels as pk
        tile = pk.gmm_view(rows, w.shape[1], w.shape[2], like.dtype.itemsize)
        if tile:
            items = pk.gmm_items(sizes, rows, tile)
            return lambda x, w: pk.grouped_matmul(x, w, *items,
                                                  pk._interpret())
    return lambda x, w: lax.ragged_dot(x, w, sizes)


def _seg_tile(kernels: bool, rows: int, h):
    """The token tile `_sum_rows` sums `rows` sorted rows into h's tokens
    with as `veles_seg_sum`, or None where it gathers the slots: kernels
    may not be traced, or `pallas_kernels.seg_sum_view` has no view of
    (rows, tokens, width)."""
    if not kernels:
        return None
    from veles_tpu.ops import pallas_kernels as pk
    return pk.seg_sum_view(rows, *h.shape, h.dtype.itemsize)


def _held_rows_swiglu(rows: int, h, gates, w_gate, w_up, w_down, order,
                      sizes, grouped: str = "ragged_dot",
                      kernels: bool = False, window=None):
    """The grouped SwiGLU over the first `rows` rows of the sorted
    buffer: (T, C). Differentiable in h, gates and the weights. `grouped`
    and `kernels` are `held_experts_swiglu`'s. With
    `window` = (first row, every pair's sorted row (T, k) or None where
    the combine asks for none) over the `rows` rows from `first` on
    instead (`_in_windows`): what the pairs sorted there add to their
    tokens."""
    t, k = gates.shape
    seg = _seg_tile(kernels, rows, h)

    def rows_of():
        """The pairs sorted into the rows at hand. (Sliced where it is
        read, twice and in this order, as before there were windows: a
        step that walks none lowers to the module it lowered to.)"""
        return order[:rows] if window is None \
            else lax.dynamic_slice(order, (window[0],), (rows,))

    if window is None:
        n_live = jnp.minimum(sizes.sum(), rows)
        token_of = (rows_of() // k).astype(jnp.int32)
        if not seg:
            pairs = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    else:
        first, pairs = window
        n_live = jnp.clip(sizes.sum() - first, 0, rows)
        token_of = (rows_of() // k).astype(jnp.int32)
        # the groups' rows inside the window
        ends = jnp.cumsum(sizes)
        sizes = jnp.clip(jnp.minimum(ends, first + rows)
                         - jnp.maximum(ends - sizes, first), 0)
        if not seg:
            # a pair sorted before the window is as dead as one sorted after
            pairs = jnp.where(pairs < first, rows, pairs - first)
    if seg:
        from veles_tpu.ops import pallas_kernels as pk
        pairs = pk.seg_sum_plan(rows_of(), n_live, k, t, seg)
    live = (jnp.arange(rows) < n_live)[:, None]
    xs = _take_rows(h, token_of, pairs, n_live, seg)
    dot = _grouped_product(sizes, rows, h, w_gate, grouped, kernels)
    # rows past the last group are no expert's: whatever a grouped product
    # leaves there must not reach the sum or, through it, a gradient
    a = jnp.where(live, dot(xs, w_gate), 0)
    b = jnp.where(live, dot(xs, w_up), 0)
    y = jnp.where(live, dot((jax.nn.silu(a) * b).astype(h.dtype), w_down), 0)
    # (masked BEFORE the gate multiplies it: the gate's gradient is a sum
    # over y, and 0 x whatever-lies-there is not 0 if it is not finite)
    gate_of = jnp.where(live, jnp.take(gates.reshape(-1), rows_of()
                                       )[:, None], 0)
    return _sum_rows(y * gate_of.astype(y.dtype), token_of, pairs, n_live,
                     seg)


#: the most bytes of sorted rows (`all_rows` x C) the whole-buffer branch
#: of `_held_swiglu` computes at once: keye2_ep8's 131,072 rows of 2,048
#: bfloat16, the largest a chip has run whole (PR 35). A larger buffer is
#: walked a window of `fast_rows` rows at a time: at 32,768 tokens under
#: top-10 the 327,680 rows whole were 6.7 GB of the backward pass's
#: temporaries (five arrays of 1.25 GB and the combine's gather of 2 GB,
#: compiled for a described v5e, PR 41), for a branch that balanced
#: routing never takes
_WHOLE_BUFFER_MAX = 512 << 20


def _windows(sizes_of: Tuple[int, int], h) -> int:
    """Windows of `fast_rows` rows the whole-buffer branch is walked in:
    0 where the buffer is computed whole."""
    fast_rows, all_rows = sizes_of
    if all_rows * h.shape[1] * h.dtype.itemsize <= _WHOLE_BUFFER_MAX:
        return 0
    return -(-all_rows // fast_rows)


def _in_windows(sizes_of, grouped, kernels, h, order, sizes, n_windows: int,
                k: int):
    """(part(first row, h, gates, the three weights) -> what the window of
    `fast_rows` rows from `first` on adds (T, C), the windows that hold a
    live row). Every pair's sorted row is found once, outside the walk,
    where the combine gathers by it."""
    fast_rows = sizes_of[0]
    slot_of = None if _seg_tile(kernels, fast_rows, h) else \
        jnp.argsort(order).astype(jnp.int32).reshape(-1, k)
    padded = jnp.pad(order, (0, max(
        n_windows * fast_rows - order.shape[0], 0)))

    def part(first, *diff):
        return _held_rows_swiglu(
            fast_rows, *diff, order=padded, sizes=sizes, grouped=grouped,
            kernels=kernels, window=(first, slot_of))

    return part, (sizes.sum() + fast_rows - 1) // fast_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_swiglu(sizes_of: Tuple[int, int], grouped: str, kernels: bool,
                 h, gates, w_gate, w_up, w_down, order, sizes):
    """`_held_rows_swiglu` on `sizes_of[0]` rows where the held pairs fit
    them, on `sizes_of[1]` where they do not: one `lax.cond` forward and
    one backward, each branch the same code at another static size. The
    backward recomputes its branch from the inputs, so that no branch's
    intermediates cross the `cond` (autodiff through it would write the
    untaken branch's residuals as zeros, at the large size). A whole
    buffer past `_WHOLE_BUFFER_MAX` is walked in windows of `fast_rows`
    rows, as many as hold a live row, summed in float32."""
    fast_rows, all_rows = sizes_of
    run = functools.partial(_held_rows_swiglu, h=h, gates=gates,
                            w_gate=w_gate, w_up=w_up, w_down=w_down,
                            order=order, sizes=sizes, grouped=grouped,
                            kernels=kernels)
    n_windows = _windows(sizes_of, h)

    def walk():
        part, n = _in_windows(sizes_of, grouped, kernels, h, order, sizes,
                              n_windows, gates.shape[1])
        diff = (h, gates, w_gate, w_up, w_down)
        return lax.fori_loop(
            0, n, lambda i, acc: acc + part(
                i * fast_rows, *diff).astype(jnp.float32),
            jnp.zeros(h.shape, jnp.float32)).astype(h.dtype)

    return lax.cond(sizes.sum() <= fast_rows, lambda: run(fast_rows),
                    walk if n_windows else lambda: run(all_rows))


def _held_swiglu_fwd(sizes_of, grouped, kernels, h, gates, w_gate, w_up,
                     w_down, order, sizes):
    args = (h, gates, w_gate, w_up, w_down, order, sizes)
    return _held_swiglu(sizes_of, grouped, kernels, *args), args


def _held_swiglu_bwd(sizes_of, grouped, kernels, args, dy):
    *diff, order, sizes = args

    def grads_at(rows: int):
        def branch():
            _, vjp = jax.vjp(lambda *a: _held_rows_swiglu(
                rows, *a, order=order, sizes=sizes, grouped=grouped,
                kernels=kernels), *diff)
            return vjp(dy)
        return branch

    def walk():
        part, n = _in_windows(sizes_of, grouped, kernels, diff[0], order,
                              sizes, n_windows, diff[1].shape[1])

        def more(i, acc):
            _, vjp = jax.vjp(lambda *a: part(i * sizes_of[0], *a), *diff)
            return tuple(a + g.astype(jnp.float32)
                         for a, g in zip(acc, vjp(dy)))

        acc = lax.fori_loop(0, n, more, tuple(
            jnp.zeros(a.shape, jnp.float32) for a in diff))
        return tuple(a.astype(d.dtype) for a, d in zip(acc, diff))

    n_windows = _windows(sizes_of, diff[0])
    grads = lax.cond(sizes.sum() <= sizes_of[0], grads_at(sizes_of[0]),
                     walk if n_windows else grads_at(sizes_of[1]))
    return (*grads, None, None)


_held_swiglu.defvjp(_held_swiglu_fwd, _held_swiglu_bwd)


def held_experts_swiglu(h, idx, gates, w_gate, w_up, w_down,
                        held: Tuple[int, int],
                        fast_rows: Optional[int] = None,
                        grouped: str = "ragged_dot", kernels: bool = False):
    """The held experts' part of a top-k expert layer, nothing dropped:
    sum over the (token, slot) pairs whose expert is one of
    `held = (first, count)` of gate x SwiGLU_expert(token). h (T, C), idx
    and gates (T, k), the weights (count, C, H) and (count, H, C).

    The pairs are sorted by expert, the held ones first, and the three
    products run as grouped products over the `count` groups
    (`grouped`, the layer table's word: `ragged_dot`, a grouped-matmul
    kernel of XLA's on a TPU, which passes over the row tiles past the last
    group; `pallas`, the `veles_gmm` / `veles_tgmm` pair of
    `pallas_kernels`, a group's whole matrix a grid step). `kernels` says
    whether Pallas kernels may be traced at all (`variants.kernels_ok`:
    the step allows them and the platform runs them); each kernel then
    asks its own view of the shape. Shapes are static, so the sorted
    buffer has `fast_rows` rows where the held pairs fit them (what a
    balanced router gives, sized by the caller: everything beside the
    products, the gathers, masks and activations, costs by the buffer's
    rows, and at 32,768 rows that was 18 ms a layer and step on a v5e
    against 0.5 ms a product, chip run of PR 32) and T x min(k, count)
    rows, every pair there can be, where they do not: a router that sends
    every token to held experts is computed like any other, more slowly.
    With `kernels`, whatever forms the products, the combine (every
    token's sum of its held rows) and the transpose of the rows' gather in
    the backward run as `veles_seg_sum` over the buffer's rows where
    `pallas_kernels.seg_sum_view` takes the shape, and cost by the rows
    too; else they gather a row a (token, slot) pair (`_sum_rows`).
    Returns (y (T, C), pairs not computed: 0 by this construction,
    counted from the buffer's bound all the same)."""
    t, k = idx.shape
    first, count = held
    all_rows = t * min(k, count)
    local = idx.reshape(-1) - first
    is_held = (local >= 0) & (local < count)
    group = jnp.where(is_held, local, count)            # the rest sort last
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = (group[:, None] == jnp.arange(count, dtype=group.dtype)
             ).sum(axis=0, dtype=jnp.int32)
    total = sizes.sum()
    args = (h, gates, w_gate, w_up, w_down, order, sizes)
    if fast_rows is None or fast_rows >= all_rows:
        y = _held_rows_swiglu(all_rows, *args, grouped, kernels)
    else:
        y = _held_swiglu((int(fast_rows), all_rows), grouped, kernels, *args)
    return (checkpoint_name(y, MOE_SAVED[0]),
            total - jnp.minimum(total, all_rows))
