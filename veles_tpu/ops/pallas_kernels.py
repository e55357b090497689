"""Pallas TPU kernels for ops where manual fusion/control beats stock XLA.

SURVEY.md §7 lists the candidates: LRN forward and backward (one
streaming pass each in the layout the convs emit — the kernel pair the
AlexNet cells run), the fused SGD/momentum update (single
read-modify-write over params), and flash-attention-style blocks (the
ring already handles cross-chip; this kernel is the intra-chip tile
loop). Since ISSUE 34 also the four one-pass sides of a hyper-connection
over the residual streams of the sparse-expert language model
(`veles_hc_*`, what `xing4_ep8.step` runs at 12 sites a step, each
traced and lowered once), and since ISSUE 35 attention over selected keys
(`veles_dsa_*`) and the held experts' grouped products (`veles_gmm`,
`veles_tgmm`), what `keye2_ep8.long16k` runs; since ISSUE 38 the flash
kernels take keys and values of different widths in the dtype they are
given, and are the core of `xing4_ep8.step`'s latent attention; since
ISSUE 42 the operand stage of the chunked Gated DeltaNet
(`veles_gdn_chunk_fwd`, `veles_gdn_chunk_bwd`: a chunk's (64, 64) float32
algebra in VMEM, what `qwen3next_ep16.seq8k`'s three linear layers run
twelve and six times a step); since ISSUE 43 the held experts' combine
(`veles_seg_sum`: every token's sum of its held rows as one-hot products
by token tile, what all three language-model cells run twice a layer).

Every kernel has a lax twin in ops.xla / ops.attention /
ops.linear_attention — these are
drop-in replacements gated by `available()`. Interpret mode is something
a test ASKS for (`_FORCE_INTERPRET`, which `variants.pallas_interpret()`
sets), never something the program falls into: off a TPU an unasked kernel
call fails in the compiler, and a backend that cannot initialise raises.
tests/test_chip_compile.py compiles every kernel for a described v5e.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: THE interpret switch: tests set it on the CPU, here or through
#: `variants.pallas_interpret()`; `_interpret()` reads it where a kernel is
#: called, `variants.pallas_ok()` where one is chosen
_FORCE_INTERPRET = False

# ---------------------------------------------------------------------------
# Hardware bounds and block seeds. The SGD, LRN+maxpool and flash blockings
# are PARAMETERS fed from the template config spaces in ops/templates.py
# (the budgeted autotuner searches them), and their constants here are the
# seeds of those spaces; the LRN kernels' blocks follow from the shape
# alone (`lrn_view`), and their constants are bounds the chip was measured
# against. None is a per-call-site magic number (velint rule
# `pallas-magic-number` keeps it that way).
# ---------------------------------------------------------------------------

#: VPU/MXU lane width — hardware-fixed, NOT a tuning axis
_LANE = 128
#: f32 min sublane tile: the floor every row blocking is clamped to
_MIN_ROW_TILE = 8
#: the LRN view kernels (ISSUE 27): the most rows of the channels-in-lanes
#: view one in-kernel slab holds; independent (C, 128) slabs of the
#: batch-in-lanes view one loop iteration holds; the channel multiple
#: that view asks for (a bfloat16 tile is 16 channels x 128 samples);
#: the most lanes of batch one of its blocks spans (the whole batch at
#: 1024 a chip: one contiguous DMA a block)
_LRN_SLAB_ROWS_MAX = 512
_LRN_SLABS_IN_FLIGHT = 10
_LRN_BATCH_VIEW_C = 16
_LRN_LANE_MAX = 1024
#: the hyper-connection sides (ISSUE 34). A grid step holds a tile of
#: tokens' rows: the largest multiple of 128 tokens (a token's maps are
#: mixed by Sinkhorn with the tokens in the LANES) that divides the tokens
#: and whose rows in the widest of the four kernels, the post side's
#: backward (three stream-sized blocks in, one out), counted in float32
#: whatever the compute dtype (one rule a shape) and double-buffered, fit
#: _HC_BLOCK_BUDGET: from the row's bytes. On a v5e 128, 256 and 512
#: tokens ran the pre side's forward at 0.485, 0.506 and 0.476 ms and left
#: the other three where they were (my chip run, PR 34), so one tile serves
#: all four, and _HC_TOKEN_TILE_MAX only bounds what the in-kernel
#: temporaries of a narrow model's tile take. The kernels ask for _HC_VMEM_LIMIT of scoped VMEM, over the
#: compiler's default of 16 MB and well inside a v5e's 128 MiB (25.7 MB of
#: blocks at 128 tokens of 4 x 3584 bfloat16 features; the rest is the
#: in-kernel temporaries'). A slab is the most lanes of one stream a
#: kernel's inner step spans.
_HC_TOKEN_TILE_MAX = 512
_HC_LANE_SLAB = 512
_HC_VMEM_LIMIT = 64 << 20
_HC_BLOCK_BUDGET = 52 << 20
#: attention over selected keys (ISSUE 35): queries and keys a grid step of
#: the four `veles_dsa_*` kernels holds (shrunk to divide the sequence,
#: `flash_fit_block`): a (512, 1024) float32 score tile is 2 MB, its
#: probabilities and the int8 selection tile 1.5 MB more, the operands'
#: double buffers under 2 MB; the kernels ask for _DSA_VMEM_LIMIT
_DSA_BLK_Q = 512
_DSA_BLK_K = 1024
_DSA_VMEM_LIMIT = 48 << 20
#: the indexer's scores (ISSUE 36): queries and keys a grid step of
#: `veles_dsa_index_fwd` / `_bwd` holds at most (a block of 256 queries of
#: a `lax.map` is one row of tiles), all the index heads of them at once:
#: the heads' float32 score tiles follow one another in VMEM and leave it
#: as their weighted sum. On a v5e the last band of keye2_ep8.long16k in
#: ONE call (4,096 queries x 16,384 keys, 16 heads of 64) took 1.99 ms
#: forward and 5.08 backward at (512, 512), 2.11 / 5.22 at (512, 1024),
#: 2.09 / 5.14 at (256, 1024), 2.13 / 5.65 at (1024, 1024), 2.07 / 5.76 at
#: (512, 2048) (my chip run, PR 36): the tile hardly matters, a smaller
#: one wastes less above the diagonal and compiles in a third of the time.
#: The backward keeps the keys' whole gradient beside them (16,384 x 64
#: float32, its lanes padded to 128: 8 MB a buffer), so the pair asks for
#: more scoped VMEM than the other `dsa` kernels
_DSA_INDEX_BLK_Q = 512
_DSA_INDEX_BLK_K = 512
_DSA_INDEX_VMEM_LIMIT = 64 << 20
#: grouped products over a sorted buffer (ISSUE 35): rows of the buffer a
#: grid step of `veles_gmm` / `veles_tgmm` holds, against one group's WHOLE
#: weight matrix (XLA's own grouped product walks (512, 512, 256) tiles,
#: four accumulating steps and three passes over the rows a product of
#: 2048 x 768: 0.81 ms for 16,384 live rows of 24,576 on a v5e, 32 % of
#: the peak, where `veles_gmm` took 0.49 at 512 rows, 0.43 at 256 and 0.60
#: at 1,024, and another 512-row tile 7 us, the peak's time; my chip runs,
#: PR 35); the blocks' bytes a shape may ask for, double buffers and the
#: float32 result counted, and the scoped VMEM the kernels ask for
_GMM_ROW_TILE = 512
_GMM_BLOCK_BUDGET = 48 << 20
_GMM_VMEM_LIMIT = 64 << 20
#: the held experts' combine (ISSUE 43): tokens a group of `veles_seg_sum`
#: owns and rows of the buffer a grid step holds at most. The one-hot
#: product of an item is 2 x tokens x rows x width operations whatever the
#: rows' owners, so the work grows with BOTH tiles (live rows x token tile
#: + tokens x row tile) while a grid step costs a third of a microsecond:
#: on a v5e a call over 61,440 rows of 2,048 bfloat16 (20,516 live) for
#: 32,768 tokens took 0.575 ms at 256 tokens x 256 rows, 0.561 at 128 x
#: 128, 0.541 at 128 x 256, 0.610 at 512 x 256, 0.715 at 512 x 512; over
#: 6,144 rows of 3,584 for 8,192 tokens 0.239 / 0.241 / 0.233 / 0.255 /
#: 0.303 (my chip runs, PR 43): the tile hardly matters under 512
_SEG_SUM_TOKEN_TILE = 256
_SEG_SUM_ROW_TILE = 256
#: rows one step of the walk gathers that puts the buffer's LIVE rows in
#: token order before the kernel (`_rows_in_token_order`): XLA's gather of
#: rows takes 33-46 ns a row on a v5e whatever their width and whether
#: anybody reads them, so one `jnp.take` of all 61,440 rows of 2,048
#: bfloat16 took 2.84 ms where the 20,516 live ones, 2,048 a step, took
#: 1.20 (4,096 a step 1.26, 12,288 1.26; of 49,152 rows 2.27 against 0.98,
#: of 131,072 rows 5.99 against 1.55, of 6,144 rows 0.24 against 0.23; my
#: chip runs, PR 43), 0.37 ms of that the zeros the walk starts from
_SEG_SUM_TAKE_ROWS = 2048
#: the chunked Gated DeltaNet's operand stage (ISSUE 42): the bytes of
#: blocks, double-buffered, a grid step of `veles_gdn_chunk_fwd` / `_bwd`
#: may hold (`gdn_view` turns them into chunk-heads a step), and the scoped
#: VMEM the kernels ask for: the blocks and the float32 (128, 128)
#: matrices of the pair of chunk-heads in flight, 64 KB each
#: (32 chunk-heads a step at heads of 128; 16 and 64 ran no differently).
#: Pairs of chunk-heads one iteration of a step's loop walks: they are
#: independent, and their chains of small products interleave. On a v5e
#: a call over 8,192 chunk-heads took 6.16 ms forward and 8.20 backward
#: at 1 pair an iteration, 5.95 / 7.60 at 2, 5.68 / 7.17 at 4 (my chip
#: run, PR 42); more is more code for the compiler and little more gain
_GDN_BLOCK_BUDGET = 12 << 20
_GDN_VMEM_LIMIT = 32 << 20
_GDN_PAIRS_IN_FLIGHT = 4
#: fused-SGD row blocking seed (the pre-search hand-written value)
_SGD_ROW_TILE = 8
#: fused LRN+maxpool sample tile seed: SAMPLES per VMEM block (each
#: "row" of this kernel's grid is one sample's whole (H, W, C) band —
#: the pooling windows never cross it). 1 is what the v5e compiler
#: admits at AlexNet-L1 (55x55x96): 2 samples need 27.76M of scoped
#: VMEM in the backward against the 16M limit
_LRN_POOL_ROW_TILE = 1
#: flash-attention block seeds (tuned by hand on v5e 2026-07-29; the
#: search explores the full blk_q x blk_k x kv_order space around them),
#: and the scoped VMEM the three kernels ask for: at (512, 1024) the
#: backward holds four float32 (queries, keys) tiles of 2 MB and their
#: rounded copies beside the operands' double buffers, over the compiler's
#: default of 16 MB
_FLASH_BLK_Q = 512
_FLASH_BLK_K = 1024
_FLASH_VMEM_LIMIT = 48 << 20


def flash_fit_block(s: int, blk: int) -> int:
    """The block size `flash_attention_pallas` ACTUALLY runs for a
    requested `blk` at sequence length `s`: shrink to the largest
    power-of-two divisor of S so any S % 128 == 0 sequence works (e.g.
    S=4608 gets blk_k=512). Shared by the kernel wrapper, the search's
    bench-alias key and the static VMEM footprint model
    (ops/templates.py) — the pruned geometry IS the traced geometry."""
    blk = min(blk, s)
    while blk > 128 and s % blk:
        blk //= 2
    return blk


def available() -> bool:
    """True when the default backend can run compiled Pallas TPU kernels.
    A backend that fails to initialise RAISES here: answering "no TPU"
    would turn a broken chip into a quiet CPU/interpret run."""
    return jax.devices()[0].platform == "tpu"


#: the fixed name of every kernel this module compiles: what a profiler
#: trace calls the operation, through every recompile (a reduction that
#: follows a kernel looks for these)
KERNEL_NAMES = {
    "_sgd_kernel": "veles_sgd_update",
    "_lrn_fwd_kernel": "veles_lrn_fwd",
    "_lrn_bwd_kernel": "veles_lrn_bwd",
    "_lrn_pool_fwd_kernel": "veles_lrn_maxpool_fwd",
    "_lrn_pool_bwd_kernel": "veles_lrn_maxpool_bwd",
    "_flash_kernel": "veles_flash_fwd",
    "_flash_dq_kernel": "veles_flash_dq",
    "_flash_dkv_kernel": "veles_flash_dkv",
    "_hc_pre_fwd_kernel": "veles_hc_pre_fwd",
    "_hc_pre_bwd_kernel": "veles_hc_pre_bwd",
    "_hc_post_fwd_kernel": "veles_hc_post_fwd",
    "_hc_post_bwd_kernel": "veles_hc_post_bwd",
    "_dsa_fwd_kernel": "veles_dsa_attend_fwd",
    "_dsa_pmean_kernel": "veles_dsa_pmean",
    "_dsa_dq_kernel": "veles_dsa_attend_dq",
    "_dsa_dkv_kernel": "veles_dsa_attend_dkv",
    "_dsa_index_fwd_kernel": "veles_dsa_index_fwd",
    "_dsa_index_bwd_kernel": "veles_dsa_index_bwd",
    "_gmm_kernel": "veles_gmm",
    "_tgmm_kernel": "veles_tgmm",
    "_seg_sum_kernel": "veles_seg_sum",
    "_gdn_chunk_fwd_kernel": "veles_gdn_chunk_fwd",
    "_gdn_chunk_bwd_kernel": "veles_gdn_chunk_bwd",
}


def _interpret() -> bool:
    return _FORCE_INTERPRET


def _kernel_jit(fn):
    """One trace and one lowering for every site of a step: the arrays
    are the arguments, every keyword is static."""
    return jax.jit(fn, static_argnames=tuple(
        name for name, p in inspect.signature(fn).parameters.items()
        if p.kind is p.KEYWORD_ONLY))


def _vmem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


#: a masked score: exp(_NEG - m) is 0 for every finite m
_NEG = -1e30


# ---------------------------------------------------------------------------
# fused SGD + momentum + weight decay (one VMEM pass over 3 buffers)
# ---------------------------------------------------------------------------


def _sgd_kernel(p_ref, g_ref, v_ref, scal_ref, p_out, v_out):
    lr = scal_ref[0]
    mom = scal_ref[1]
    wd = scal_ref[2]
    g = g_ref[:] + wd * p_ref[:]
    v_new = mom * v_ref[:] - lr * g
    v_out[:] = v_new
    p_out[:] = p_ref[:] + v_new


def sgd_update_pallas(p, g, v, lr, momentum=0.0, weight_decay=0.0,
                      row_tile: int = _SGD_ROW_TILE):
    """Returns (p_new, v_new). Shapes arbitrary; computed as a flattened
    (rows, 128) grid with one row-block per program. `row_tile` is the
    row blocking (a searched tuning axis — ops/templates.py); the
    scalars may be traced (the fused step passes a scheduled lr)."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    cols = _LANE
    rows = -(-n // cols)
    row_tile = max(_MIN_ROW_TILE, int(row_tile))
    padded = rows + ((-rows) % row_tile)

    def flat(a):
        a = a.ravel()
        a = jnp.pad(a, (0, padded * cols - n))
        return a.reshape(padded, cols).astype(jnp.float32)

    p2, g2, v2 = flat(p), flat(g), flat(v)
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(momentum, jnp.float32),
                      jnp.asarray(weight_decay, jnp.float32)])
    grid = (padded // row_tile,)
    spec = pl.BlockSpec((row_tile, cols), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    p_new, v_new = pl.pallas_call(
        _sgd_kernel,
        out_shape=(jax.ShapeDtypeStruct((padded, cols), jnp.float32),) * 2,
        grid=grid,
        in_specs=[spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(spec, spec),
        interpret=_interpret(),
        name=KERNEL_NAMES["_sgd_kernel"],
    )(p2, g2, v2, scal)
    return (p_new.ravel()[:n].reshape(shape).astype(dtype),
            v_new.ravel()[:n].reshape(shape).astype(dtype))


# ---------------------------------------------------------------------------
# LRN forward + backward: one streaming pass each, in the layout the
# convs emit (ISSUE 27)
#
# The v5e compiler holds a conv activation whose channel count is no
# multiple of 128 with the BATCH in the lanes (AlexNet LRN1:
# bf16[1024,55,55,96]{0,3,2,1:T(8,128)(2,1)}, 16 channels x 128 samples
# a tile) and one whose channel count is with the channels in the lanes
# and the batch next ({3,0,2,1}, LRN2). A kernel that flattens the
# logical NHWC array to (N*H*W, C) makes XLA relayout every operand and
# result (eight copies of 595 / 382 MB in AlexNet's step), pad C to 128
# lanes and pad/slice the rows. So the kernels take the activation
# through the VIEW whose row-major order is that physical layout (the
# transposes below compile to bitcasts), in blocks that divide it
# exactly, and walk each VMEM block slab by slab so that the float32
# intermediates live in registers and the pass stays bound by HBM.
# ---------------------------------------------------------------------------


def _band_window_sum(a, band, axis: int, exact: bool):
    """±half window sum along `axis` of a float32 slab as a product with
    the 0/1 band on the MXU, accumulated in float32. The chip's rolls
    and masks cost the VPU more than the pass's DMA takes (measured, PR
    27: 2.19 / 3.39 ms against 1.95 / 2.82 at LRN1, 3.1 / 5.1 against
    1.37 / 1.88 at LRN2). The MXU takes bfloat16: a slab of a bfloat16
    activation goes in rounded once (as XLA's banded matmul rounds x²);
    a float32 activation (`exact`) goes in as two bfloat16 pieces, which
    carry 16 bits of it."""
    def dot(v):
        # bfloat16 operands are exact in one MXU pass: no matmul-precision
        # setting of the caller's has a say
        return jnp.dot(*((band, v) if axis == 0 else (v, band)),
                       precision=lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)

    hi = a.astype(jnp.bfloat16)
    if not exact:
        return dot(hi)
    return dot(hi) + dot((a - hi.astype(jnp.float32)).astype(jnp.bfloat16))


# s^(−β) via sqrt/rsqrt products instead of exp/log — the SAME routine
# the XLA lowering uses, imported so both lowerings share numerics
from veles_tpu.ops.xla import _lrn_band  # noqa: E402
from veles_tpu.ops.xla import _pow_neg_quarters as _pow_neg  # noqa: E402


def _lrn_fwd_math(x, *, wsum, k: float, alpha: float, beta: float):
    x = x.astype(jnp.float32)
    return x * _pow_neg(k + alpha * wsum(x * x), beta)


def _lrn_bwd_math(x, err, *, wsum, k: float, alpha: float, beta: float):
    """err_x = g·d − 2αβ·x·W(g·x·d/s), d = s^(−β), all in float32."""
    x = x.astype(jnp.float32)
    err = err.astype(jnp.float32)
    s = k + alpha * wsum(x * x)
    # s^(−β−1) the same way as d: where 4β is an integer both are products
    # of ONE t = s^(−1/4) (the common terms merge), and nothing divides
    d, d_over_s = _pow_neg(s, beta), _pow_neg(s, beta + 1.0)
    return err * d - (2.0 * alpha * beta) * x * wsum(err * x * d_over_s)


def _walk_batch_lanes(ins, out, math):
    """Block (R, C, NB) of the batch-in-lanes view: one (C, 128) slab —
    every channel of 128 samples at one pixel — at a time, band @ slab.
    An iteration takes enough rows for _LRN_SLABS_IN_FLIGHT slabs: the
    slabs are independent, and with too few of them the MXU's latency
    shows (measured: 2 an iteration ran at half the rate of 8)."""
    *ins, band = ins
    exact = ins[0].dtype.itemsize > 2
    lanes = out.shape[2] // _LANE
    rows = _largest_divisor(out.shape[0], 1,
                            max(1, _LRN_SLABS_IN_FLIGHT // lanes))

    def step(i, carry):
        wsum = functools.partial(_band_window_sum, band=band[...], axis=0,
                                 exact=exact)
        for r in range(rows):
            for j in range(lanes):
                at = (i * rows + r, slice(None), pl.ds(j * _LANE, _LANE))
                out[at] = math(*(a[at] for a in ins),
                               wsum=wsum).astype(out.dtype)
        return carry

    lax.fori_loop(0, out.shape[0] // rows, step, 0)


def _walk_channel_lanes(ins, out, math):
    """Block (rows, C) of the channels-in-lanes view: one slab of rows at
    a time, slab @ band. The band stays in the MXU while a slab's rows
    stream through it, so a slab is as tall as the chip rewards (128
    rows ran at 0.53 of the roofline, 256 at 0.68, 512 at 0.71): the
    most whole tiles of rows, up to _LRN_SLAB_ROWS_MAX, that divide the
    block."""
    *ins, band = ins
    exact = ins[0].dtype.itemsize > 2
    rows = _largest_divisor(out.shape[0], _sublanes(out.dtype.itemsize),
                            _LRN_SLAB_ROWS_MAX)

    def step(i, carry):
        wsum = functools.partial(_band_window_sum, band=band[...], axis=1,
                                 exact=exact)
        at = (pl.ds(pl.multiple_of(i * rows, rows), rows), slice(None))
        out[at] = math(*(a[at] for a in ins), wsum=wsum).astype(out.dtype)
        return carry

    lax.fori_loop(0, out.shape[0] // rows, step, 0)


def _lrn_fwd_kernel(*refs, walk, **scalars):
    *ins, y_ref = refs
    walk(ins, y_ref, functools.partial(_lrn_fwd_math, **scalars))


def _lrn_bwd_kernel(*refs, walk, **scalars):
    *ins, out_ref = refs
    walk(ins, out_ref, functools.partial(_lrn_bwd_math, **scalars))


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublanes, 128) tile: 8 of float32, 16 of bfloat16."""
    return _MIN_ROW_TILE * 4 // itemsize


def lrn_view_vmem_bytes(block: Tuple[int, ...], itemsize: int) -> int:
    """Scoped-VMEM bytes of a view block in the backward: 2 inputs + 1
    output, double-buffered, on whole tiles — a bfloat16 tile of the
    batch-in-lanes view is 16 channels x 128 samples. The float32 work
    is done slab by slab and adds nothing a block's size moves."""
    sub = _sublanes(itemsize)
    tiles = -(-block[-2] // sub) * -(-block[-1] // _LANE)
    return 2 * 3 * int(np.prod(block[:-2])) * tiles * sub * _LANE * itemsize


def _largest_divisor(n: int, unit: int, cap: int) -> Optional[int]:
    """The largest multiple of `unit` that divides n and is <= cap."""
    for d in range(min(cap, n) // unit * unit, 0, -unit):
        if n % d == 0:
            return d
    return None


def lrn_view(shape, itemsize: int):
    """How the one-pass kernels take an NHWC activation: (walk, view
    shape, block), or None where no lane-dense view of it divides into
    whole blocks (the caller then traces the XLA closed form).

    - batch in lanes, (H·W, C, N): C is no multiple of 128 but of 16, N
      is a multiple of 128 — AlexNet's LRN1 (96 channels) at 1024 a
      chip, and at 256 a chip under shard_map;
    - channels in lanes, (H·W·N, C) with rows ordered H, W, N: C is a
      multiple of 128 — LRN2 (256 channels)."""
    from veles_tpu.analysis.resources import SCOPED_VMEM_LIMIT
    if len(shape) != 4:
        return None
    n, h, w, c = shape
    budget = SCOPED_VMEM_LIMIT // 2
    if c % _LANE == 0:
        sub = _sublanes(itemsize)
        rows = _largest_divisor(
            h * w * n, sub,
            budget // lrn_view_vmem_bytes((sub, c), itemsize) * sub)
        return rows and (_walk_channel_lanes, (h * w * n, c), (rows, c))
    if n % _LANE == 0 and c % _LRN_BATCH_VIEW_C == 0:
        nb = _largest_divisor(n, _LANE, _LRN_LANE_MAX)
        r = _largest_divisor(
            h * w, 1, budget // lrn_view_vmem_bytes((1, c, nb), itemsize))
        return r and (_walk_batch_lanes, (h * w, c, n), (r, c, nb))
    return None


def _lrn_view_call(kernel, args, view, k, alpha, beta, n: int):
    """One pass over the activation in the view `lrn_view` picked. The
    transposes name the physical order the compiler already holds, so
    they cost nothing; blocks divide the view exactly (no pad, no
    slice). Scalars are compile-time constants (lets the pow decompose
    into sqrt/rsqrt — see _pow_neg); the (C, C) 0/1 band the walks
    multiply by is fetched once (its block index never moves)."""
    walk, vshape, block = view
    nb, h, w, c = args[0].shape
    if walk is _walk_batch_lanes:
        perm, back, mid = (1, 2, 3, 0), (3, 0, 1, 2), (h, w, c, nb)
        grid = (vshape[0] // block[0], vshape[2] // block[2])
        spec = pl.BlockSpec(block, lambda i, j: (i, 0, j),
                            memory_space=pltpu.VMEM)
    else:
        perm, back, mid = (1, 2, 0, 3), (2, 0, 1, 3), (h, w, nb, c)
        grid = (vshape[0] // block[0],)
        spec = pl.BlockSpec(block, lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    views = [jnp.transpose(a, perm).reshape(vshape) for a in args]
    band = _lrn_band(c, n).astype(jnp.bfloat16)
    out = pl.pallas_call(
        functools.partial(kernel, walk=walk, k=float(k),
                          alpha=float(alpha), beta=float(beta)),
        # under shard_map (the dp step) the result varies over the mesh
        # axes its operand varies over
        out_shape=jax.ShapeDtypeStruct(vshape, views[0].dtype,
                                       vma=jax.typeof(views[0]).vma),
        grid=grid,
        in_specs=[spec] * len(views) + [
            pl.BlockSpec(band.shape, lambda *_: (0, 0),
                         memory_space=pltpu.VMEM)],
        out_specs=spec,
        interpret=_interpret(),
        name=KERNEL_NAMES[kernel.__name__],
    )(*views, band)
    return jnp.transpose(out.reshape(mid), back)


def _lrn_kernel_call(kernel, args, k, alpha, beta, n: int):
    x = args[0]
    view = lrn_view(x.shape, x.dtype.itemsize)
    if not view:
        raise ValueError(
            f"the LRN kernels have no lane-dense view of {x.dtype} "
            f"{tuple(x.shape)} (pallas_kernels.lrn_view): call lrn_pallas, "
            "which traces the XLA closed form for such a shape")
    return _lrn_view_call(kernel, args, view, k, alpha, beta, n)


def lrn_forward_pallas(x, k: float = 2.0, alpha: float = 1e-4,
                       beta: float = 0.75, n: int = 5):
    """The forward kernel; a shape `lrn_view` has no view of raises."""
    return _lrn_kernel_call(_lrn_fwd_kernel, (x,), k, alpha, beta, n)


def lrn_backward_pallas(x, err_y, k: float = 2.0, alpha: float = 1e-4,
                        beta: float = 0.75, n: int = 5):
    """The backward kernel; a shape `lrn_view` has no view of raises."""
    return _lrn_kernel_call(_lrn_bwd_kernel, (x, err_y), k, alpha, beta, n)


def lrn_pallas(x, k: float = 2.0, alpha: float = 1e-4, beta: float = 0.75,
               n: int = 5):
    """Differentiable LRN, forward AND backward one streaming Pallas
    pass each over the activation in the layout the convs emit — the
    registry's `pallas_one_pass`. What it traces follows the input: the
    batch-in-lanes or the channels-in-lanes view where `lrn_view` finds
    one, and the XLA closed form (`banded_matmul`) for any other shape
    (a serving ring batch that is no multiple of 128, a narrow test
    array)."""
    if not lrn_view(x.shape, x.dtype.itemsize):
        from veles_tpu.ops import xla as ox
        return ox.lrn_forward(x, k, alpha, beta, n)
    return _lrn_pallas_vjp(x, k, alpha, beta, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _lrn_pallas_vjp(x, k, alpha, beta, n):
    return lrn_forward_pallas(x, k, alpha, beta, n)


def _lrn_fwd_rule(x, k, alpha, beta, n):
    return lrn_forward_pallas(x, k, alpha, beta, n), x


def _lrn_bwd_rule(k, alpha, beta, n, x, g):
    return (lrn_backward_pallas(x, g, k, alpha, beta, n),)


_lrn_pallas_vjp.defvjp(_lrn_fwd_rule, _lrn_bwd_rule)


# ---------------------------------------------------------------------------
# fused LRN + maxpool: one VMEM pass over the shared activation
# (searched cross-op fusion, ops/templates.py `lrn_maxpool`). LRN and the
# pooling that follows it both stream the SAME activation rows — composed
# they read it from HBM twice (and write the LRN intermediate once);
# fused, each (row_tile, H, W, C) sample band is loaded once, normalized
# and pooled in VMEM, and only the pooled output returns to HBM.
# ---------------------------------------------------------------------------


def _window_sum_last(a, half: int):
    """±half across-channel window sum over the LAST axis of an N-d
    block (the fused pair's blocks are whole samples of any channel
    width, so pads and slices)."""
    zeros = [(0, 0)] * (a.ndim - 1)
    out = a
    for d in range(1, half + 1):
        out = out + jnp.pad(a[..., d:], zeros + [(0, d)]) \
            + jnp.pad(a[..., :-d], zeros + [(d, 0)])
    return out


def _pool_out_hw(h: int, w: int, ky: int, kx: int, sy: int, sx: int):
    """Ceil-mode pooled extent (edge windows truncate — the one
    geometry every maxpool golden/lowering/unit shares)."""
    oh = -(-(h - ky) // sy) + 1 if h > ky else 1
    ow = -(-(w - kx) // sx) + 1 if w > kx else 1
    return oh, ow


def _pool_canvas_hw(h: int, w: int, ky: int, kx: int, sy: int, sx: int):
    """(hp, wp): the padded spatial extent on which every ceil-mode
    window is fully resident."""
    oh, ow = _pool_out_hw(h, w, ky, kx, sy, sx)
    return (oh - 1) * sy + ky, (ow - 1) * sx + kx


def _lane_blocks(c: int):
    """Channel (lane) blocking of the pooling canvases: Mosaic's strided
    loads/stores need a base ref whose last dim is at most one 128-lane
    tile ("The last dim size is not 128 in original base memref"), so a
    C > 128 canvas is kept as C/128 lane blocks. Returns (n_blocks,
    block_width)."""
    if c <= _LANE:
        return 1, c
    if c % _LANE:
        raise ValueError(
            f"lrn_maxpool_pallas: {c} channels is neither <= {_LANE} nor "
            f"a multiple of it (the strided pooling taps need 128-lane "
            "channel blocks)")
    return c // _LANE, _LANE


def _canvas_fill(ref, value, h: int, w: int, fill):
    """Write an (nt, h, w, C) value into the top-left of the lane-blocked
    (n_blocks, nt, hp, wp, cb) canvas, `fill` everywhere else."""
    ref[...] = jnp.full(ref.shape, fill, jnp.float32)
    cb = ref.shape[-1]
    for j in range(ref.shape[0]):
        ref[j, :, :h, :w, :] = value[..., j * cb:(j + 1) * cb]


def _canvas_taps(ky: int, kx: int, sy: int, sx: int, oh: int, ow: int):
    """Index tuples (after the lane-block axis) of the ky·kx strided
    window taps, in window scan order (the order ties break by, matching
    the goldens' argmax). Strided REF indexing — a strided slice of an
    in-register VALUE lowers to a gather the v5e compiler refuses
    ("Only 2D gather is supported")."""
    return [(slice(None), pl.ds(dy, oh, stride=sy),
             pl.ds(dx, ow, stride=sx), slice(None))
            for dy in range(ky) for dx in range(kx)]


def _canvas_load(ref, idx):
    parts = [ref[(j,) + idx] for j in range(ref.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def _lrn_pool_fwd_kernel(x_ref, y_ref, yp_ref, *, half: int, k: float,
                         alpha: float, beta: float, ky: int, kx: int,
                         sy: int, sx: int):
    x = x_ref[...].astype(jnp.float32)
    s = k + alpha * _window_sum_last(x * x, half)
    _canvas_fill(yp_ref, x * _pow_neg(s, beta), x.shape[1], x.shape[2],
                 -jnp.inf)
    out = None
    for idx in _canvas_taps(ky, kx, sy, sx, y_ref.shape[1],
                            y_ref.shape[2]):
        sl = _canvas_load(yp_ref, idx)
        out = sl if out is None else jnp.maximum(out, sl)
    y_ref[...] = out.astype(y_ref.dtype)


def _lrn_pool_bwd_kernel(x_ref, g_ref, dx_ref, yp_ref, gp_ref, *,
                         half: int, k: float, alpha: float, beta: float,
                         ky: int, kx: int, sy: int, sx: int):
    """One-pass backward of the composed pair: recompute the LRN output,
    route the pooled error to each window's FIRST max (the goldens' and
    select_and_scatter's tie rule — equality routing alone would send a
    tied window's gradient to every tied element, e.g. post-ReLU zeros),
    then the closed-form LRN backward — all on the resident block. The
    routing accumulates through strided stores into a zeroed canvas."""
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    h, w = x.shape[1], x.shape[2]
    s = k + alpha * _window_sum_last(x * x, half)
    d = _pow_neg(s, beta)
    _canvas_fill(yp_ref, x * d, h, w, -jnp.inf)
    taps = _canvas_taps(ky, kx, sy, sx, g.shape[1], g.shape[2])
    m = None
    for idx in taps:
        sl = _canvas_load(yp_ref, idx)
        m = sl if m is None else jnp.maximum(m, sl)
    n_taps = ky * kx
    win = None
    for lin, idx in enumerate(taps):
        cand = jnp.where(_canvas_load(yp_ref, idx) == m, jnp.int32(lin),
                         jnp.int32(n_taps))
        win = cand if win is None else jnp.minimum(win, cand)
    gp_ref[...] = jnp.zeros(gp_ref.shape, jnp.float32)
    cb = gp_ref.shape[-1]
    for lin, idx in enumerate(taps):
        routed = jnp.where(win == lin, g, 0.0)
        for j in range(gp_ref.shape[0]):
            gp_ref[(j,) + idx] = gp_ref[(j,) + idx] \
                + routed[..., j * cb:(j + 1) * cb]
    g_lrn = _canvas_load(gp_ref, (slice(None), slice(0, h), slice(0, w),
                                  slice(None)))
    tsum = _window_sum_last(g_lrn * x * d / s, half)
    dx_ref[...] = (g_lrn * d
                   - (2.0 * alpha * beta) * x * tsum).astype(dx_ref.dtype)


def _lrn_pool_call(kernel, args, out_hwc, k, alpha, beta, n: int,
                   ksize, stride, row_tile: Optional[int],
                   io_dtype: str, n_canvas: int):
    """Common wrapper: grid over SAMPLE tiles (each program owns
    `row_tile` whole (H, W, C) bands, so both the channel window and the
    pooling windows stay in-block). `row_tile`/`io_dtype` are the
    searched axes (ops/templates.py): samples a block, and whether the
    blocks move in the caller's dtype ("native") or float32 ("f32").
    `n_canvas` f32 VMEM scratch canvases hold the padded LRN output (and,
    backward, the routed error) for the strided window taps."""
    x = args[0]
    nb, h, w, c = x.shape
    blk_dt = jnp.float32 if io_dtype == "f32" else x.dtype
    rt = max(1, int(row_tile if row_tile is not None
                    else _LRN_POOL_ROW_TILE))
    rt = min(rt, max(nb, 1))
    pad = (-nb) % rt
    xs = []
    for a in args:
        a = a.astype(blk_dt)
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        xs.append(a)
    in_specs = [pl.BlockSpec((rt,) + a.shape[1:],
                             lambda i: (i, 0, 0, 0),
                             memory_space=pltpu.VMEM) for a in xs]
    ky, kx, sy, sx = (int(ksize[0]), int(ksize[1]), int(stride[0]),
                      int(stride[1]))
    hp, wp = _pool_canvas_hw(h, w, ky, kx, sy, sx)
    n_cb, cb = _lane_blocks(c)
    out = pl.pallas_call(
        functools.partial(kernel, half=n // 2, k=float(k),
                          alpha=float(alpha), beta=float(beta),
                          ky=ky, kx=kx, sy=sy, sx=sx),
        out_shape=jax.ShapeDtypeStruct((nb + pad,) + out_hwc, blk_dt),
        grid=((nb + pad) // rt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rt,) + out_hwc, lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((n_cb, rt, hp, wp, cb), jnp.float32)]
        * n_canvas,
        interpret=_interpret(),
        name=KERNEL_NAMES[kernel.__name__],
    )(*xs)
    return out[:nb].astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7,
                                                    8))
def lrn_maxpool_pallas(x, k: float = 2.0, alpha: float = 1e-4,
                       beta: float = 0.75, n: int = 5,
                       ksize=(3, 3), stride=(2, 2),
                       row_tile: Optional[int] = None,
                       io_dtype: str = "native"):
    """Differentiable fused LRN→maxpool: ONE row-streaming Pallas pass
    per direction over the shared (N, H, W, C) activation (fwd:
    normalize + pool in VMEM; bwd: recompute + first-max error routing +
    closed-form LRN backward). Ceil-mode pooling geometry, max flavor
    only (maxabs pairs stay composed). Gated by the COMPOSED
    ops.reference golden (`lrn_maxpool_forward/backward`) through the
    equivalence ledger before the search may time it."""
    oh, ow = _pool_out_hw(x.shape[1], x.shape[2], ksize[0], ksize[1],
                          stride[0], stride[1])
    return _lrn_pool_call(_lrn_pool_fwd_kernel, (x,),
                          (oh, ow, x.shape[3]), k, alpha, beta, n,
                          ksize, stride, row_tile, io_dtype, 1)


def _lrn_pool_fwd_rule(x, k, alpha, beta, n, ksize, stride, row_tile,
                       io_dtype):
    return lrn_maxpool_pallas(x, k, alpha, beta, n, ksize, stride,
                              row_tile, io_dtype), x


def _lrn_pool_bwd_rule(k, alpha, beta, n, ksize, stride, row_tile,
                       io_dtype, x, g):
    return (_lrn_pool_call(_lrn_pool_bwd_kernel, (x, g),
                           tuple(x.shape[1:]), k, alpha, beta, n,
                           ksize, stride, row_tile, io_dtype, 2),)


lrn_maxpool_pallas.defvjp(_lrn_pool_fwd_rule, _lrn_pool_bwd_rule)


# ---------------------------------------------------------------------------
# blocked (flash-style) attention: tile over KV inside one chip. Three
# kernels (forward, dQ, dK/dV) that take their operands in the dtype they
# are given, bfloat16 products under float32 scores, softmax and
# accumulators where the model computes in bfloat16, keys of one width and
# values of another (latent attention: 192 and 128), and under `causal`
# pass over the tiles above the diagonal, their operands not fetched (the
# index maps stay on the last tile that is needed). Each is ONE
# module-level `jax.jit` that every site of a step calls (PR 33's lesson);
# since ISSUE 38 the six latent-attention sites of `xing4_ep8.step` do.
# ---------------------------------------------------------------------------


def flash_view(seq: int, key_dim: int, value_dim: int) -> bool:
    """Whether the kernels take a sequence of `seq` tokens under keys of
    `key_dim` and values of `value_dim` in a compiled step: whole tiles of
    128 keys, a key in halves of the MXU's depth, values in whole lanes."""
    return (seq % _LANE == 0 and key_dim % (_LANE // 2) == 0
            and value_dim % _LANE == 0)


def _flash_dot(a, b, ca: int, cb: int):
    """a . b over axis `ca` of a and `cb` of b, accumulated in float32.
    bfloat16 operands at the MXU's own precision whatever the ambient
    default says (under "highest" Mosaic refuses them); float32 ones, the
    search's and the goldens', at the ambient one."""
    return lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        precision=lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None,
        preferred_element_type=jnp.float32)


def _flash_scores(q, kb, scale, causal, q0, k0):
    """The float32 scores of a tile whose first query and key are the
    sequence's `q0`-th and `k0`-th; -1e30 above the diagonal."""
    s = _flash_dot(q, kb, 1, 1) * scale
    if causal:
        q_idx = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_idx <= q_idx, s, _NEG)
    return s


def _flash_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                  reverse_kv: bool = False, dropped: bool = False):
    """Grid (B·H, q_blocks, k_blocks) with KV innermost: each step streams
    ONE (blk_k, d) K/V tile through VMEM (O(blk) footprint — long-context
    safe) and folds it into the online-softmax scratch; the last KV step
    writes the normalized output block plus the per-row logsumexp (the
    backward's softmax residual). `reverse_kv` visits KV tiles
    last-to-first (the index map streams tile nk−1−t at step t) — the
    online softmax is order-invariant, so numerics match to fp rounding;
    the axis exists for the search to probe prefetch locality. With
    `dropped` (the searched `drop` fusion axis, ops/templates.py) a
    pre-scaled dropout mask streams as a fourth input blocked like the
    output and multiplies the OUTPUT block in the same final write — the
    composed path's extra HBM round trip over the attention output
    disappears. The probabilities are rounded to the values' dtype before
    their product, as the XLA forms round them."""
    if dropped:
        mk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    # the KV tile actually resident this step (≠ ki under reverse_kv)
    kt = (nk - 1 - ki) if reverse_kv else ki
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        vb = v_ref[0]
        s = _flash_scores(q_ref[0], k_ref[0], scale, causal, qi * blk_q,
                          kt * blk_k)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            # a row whose visited tiles are ALL masked so far has
            # m_new == -1e30, where exp(s - m_new) = 1, not 0 — only
            # reachable under reverse_kv (forward order always sees the
            # k_idx == q_idx entry first); guard is free under fwd
            p = jnp.where(s <= -1e29, 0.0, p)
        a = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * a + p.sum(axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * a + _flash_dot(p.astype(vb.dtype), vb,
                                                 1, 0)

    if causal:
        # a KV tile whose first key is beyond this Q tile's last query is
        # fully masked — skip its two dots entirely (~half the grid at
        # large S; this is the hot path the kernel exists for)
        pl.when(kt * blk_k <= qi * blk_q + blk_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        o = acc_scr[:] / l_scr[:]
        if dropped:
            o = o * mk_ref[0].astype(jnp.float32)
        o_ref[0] = o.astype(o_ref.dtype)
        # the queries' logsumexps leave as a ROW, the queries in the lanes:
        # as the column they are computed in, (B·H, S, 1) pads to 128
        # lanes in HBM, 128 times what a surrounding `jax.checkpoint` keeps
        col = m_scr[:] + jnp.log(l_scr[:])
        eye = lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 0) \
            == lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 1)
        lse_ref[0] = jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                     dq_ref, dq_scr, *, scale: float, causal: bool):
    """dQ with the SAME grid/streaming as the forward (KV innermost):
    recompute P = exp(S·scale − lse) per tile from the saved logsumexp,
    dS = P ⊙ (dO·Vᵀ − D), dQ += dS·K·scale, dS rounded to the keys' dtype
    before the product. O(blk) VMEM footprint."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        kb = k_ref[0]
        s = _flash_scores(q_ref[0], kb, scale, causal, qi * blk_q,
                          ki * blk_k)
        p = jnp.exp(s - lse_ref[0])                       # (blk_q, blk_k)
        dp = _flash_dot(do_ref[0], v_ref[0], 1, 1)        # (blk_q, blk_k)
        ds = p * (dp - di_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + _flash_dot(ds.astype(kb.dtype), kb, 1, 0)

    if causal:
        pl.when(ki * blk_k <= qi * blk_q + blk_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *,
                      scale: float, causal: bool):
    """dK/dV with the transposed streaming order — grid (B·H, k_blocks,
    q_blocks), Q innermost: each KV tile stays VMEM-resident while Q/dO
    tiles stream past. dV += Pᵀ·dO, dK += dSᵀ·Q·scale, P and dS rounded to
    the operands' dtype before their products."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q, do = q_ref[0], do_ref[0]
        s = _flash_scores(q, k_ref[0], scale, causal, qi * blk_q,
                          ki * blk_k)
        p = jnp.exp(s - lse_ref[0])
        dv_scr[:] = dv_scr[:] + _flash_dot(p.astype(do.dtype), do, 0, 0)
        dp = _flash_dot(do, v_ref[0], 1, 1)
        ds = p * (dp - di_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + _flash_dot(ds.astype(q.dtype), q, 0, 0)

    if causal:
        # a Q tile entirely BEFORE this KV tile contributes nothing
        pl.when(qi * blk_q + blk_q - 1 >= ki * blk_k)(compute)
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_FLASH_VMEM_LIMIT)


@_kernel_jit
def flash_forward_pallas(q, k, v, mask=None, *, scale: float, causal: bool,
                         blk_q: int, blk_k: int, kv_order: str = "fwd",
                         interpret: bool = False):
    """q and k (B·H, S, D), v (B·H, S, Dv) -> (out (B·H, S, Dv) in q's
    dtype, the queries' logsumexps (B·H, 1, S) float32; the backward
    kernels take them as (B·H, S, 1)). `kv_order` "rev" streams KV tiles
    last-to-first (searched axis). `mask` (the output's shape, pre-scaled
    0-or-1/keep) applies dropout to the output block inside the kernel's
    final write (searched `drop` axis)."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    rev = kv_order == "rev"
    nk = s // blk_k

    def tile(i, t):
        """The KV tile of step t of query tile i: under `causal` no later
        than the last one the queries need."""
        kt = nk - 1 - t if rev else t
        return jnp.minimum(kt, (i * blk_q + blk_q - 1) // blk_k) \
            if causal else kt

    row = lambda b, i, t: (b, i, 0)  # noqa: E731
    kv = lambda b, i, t: (b, tile(i, t), 0)  # noqa: E731
    in_specs = [_vmem((1, blk_q, d), row), _vmem((1, blk_k, d), kv),
                _vmem((1, blk_k, dv), kv)]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(_vmem((1, blk_q, dv), row))
        args.append(mask)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          reverse_kv=rev, dropped=mask is not None),
        out_shape=(jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)),
        grid=(bh, s // blk_q, nk),
        in_specs=in_specs,
        out_specs=(_vmem((1, blk_q, dv), row),
                   _vmem((1, 1, blk_q), lambda b, i, t: (b, 0, i))),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, dv), jnp.float32),  # unnormalized out
        ],
        compiler_params=_flash_params(), interpret=interpret,
        name=KERNEL_NAMES["_flash_kernel"],
    )(*args)


@_kernel_jit
def flash_dq_pallas(q, k, v, do, lse, di, *, scale: float, causal: bool,
                    blk_q: int, blk_k: int, interpret: bool = False):
    """`do` (B·H, S, Dv) the outputs' cotangent, `lse` and `di` (B·H, S, 1)
    float32 the logsumexps and `do`'s row sums against the outputs ->
    dq (B·H, S, D) in q's dtype."""
    bh, s, d = q.shape
    dv = v.shape[-1]

    def tile(i, t):
        return jnp.minimum(t, (i * blk_q + blk_q - 1) // blk_k) \
            if causal else t

    row = lambda b, i, t: (b, i, 0)  # noqa: E731
    kv = lambda b, i, t: (b, tile(i, t), 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // blk_q, s // blk_k),
        in_specs=[_vmem((1, blk_q, d), row), _vmem((1, blk_k, d), kv),
                  _vmem((1, blk_k, dv), kv), _vmem((1, blk_q, dv), row),
                  _vmem((1, blk_q, 1), row), _vmem((1, blk_q, 1), row)],
        out_specs=_vmem((1, blk_q, d), row),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_flash_params(), interpret=interpret,
        name=KERNEL_NAMES["_flash_dq_kernel"],
    )(q, k, v, do, lse, di)


@_kernel_jit
def flash_dkv_pallas(q, k, v, do, lse, di, *, scale: float, causal: bool,
                     blk_q: int, blk_k: int, interpret: bool = False):
    """-> (dk (B·H, S, D), dv (B·H, S, Dv)) in the operands' dtypes, on the
    transposed grid: KV outer, Q inner (the grid indices (b, t, i) name
    the (kv, q) block pair)."""
    bh, s, d = q.shape
    dv = v.shape[-1]

    def tile(t, i):
        """The Q tile of step i of KV tile t: under `causal` no earlier
        than the first one that meets the keys."""
        return jnp.maximum(i, (t * blk_k) // blk_q) if causal else i

    row = lambda b, t, i: (b, tile(t, i), 0)  # noqa: E731
    kv = lambda b, t, i: (b, t, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, dv), v.dtype)),
        grid=(bh, s // blk_k, s // blk_q),
        in_specs=[_vmem((1, blk_q, d), row), _vmem((1, blk_k, d), kv),
                  _vmem((1, blk_k, dv), kv), _vmem((1, blk_q, dv), row),
                  _vmem((1, blk_q, 1), row), _vmem((1, blk_q, 1), row)],
        out_specs=(_vmem((1, blk_k, d), kv), _vmem((1, blk_k, dv), kv)),
        scratch_shapes=[pltpu.VMEM((blk_k, d), jnp.float32),
                        pltpu.VMEM((blk_k, dv), jnp.float32)],
        compiler_params=_flash_params(), interpret=interpret,
        name=KERNEL_NAMES["_flash_dkv_kernel"],
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_attn(qf, kf, vf, mf, static, scope):
    """(B·H, S, D) operands, `mf` the pre-scaled dropout mask of the
    dropout-fused form or None, `static` the kernels' keywords as a sorted
    tuple of pairs, `scope` the `jax.named_scope` the backward opens (a
    custom_vjp's backward is traced outside the forward's)."""
    return _flash_attn_fwd(qf, kf, vf, mf, static, scope)[0]


def _flash_attn_fwd(qf, kf, vf, mf, static, scope):
    from jax.ad_checkpoint import checkpoint_name

    from veles_tpu.ops.attention import FLASH_SAVED
    out, lse = flash_forward_pallas(qf, kf, vf, mf, **dict(static))
    out = checkpoint_name(out, FLASH_SAVED[0])
    lse = checkpoint_name(lse[:, 0, :], FLASH_SAVED[1])
    return out, (qf, kf, vf, mf, out, lse)


def _flash_attn_bwd(static, scope, res, g):
    qf, kf, vf, mf, out, lse = res
    kw = {k: v for k, v in static if k != "kv_order"}
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        # grad wrt the UNMASKED attention output is dO = g ⊙ mask (dropout
        # backward); the softmax-jacobian diagonal D = rowsum(dO ⊙ O)
        # equals rowsum(g ⊙ O·mask), so the MASKED output the forward
        # saved feeds it directly — no unmasked residual needed. A tiny
        # elementwise reduce, XLA fuses it, no kernel needed
        do = g if mf is None else (g * mf).astype(g.dtype)
        di = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1, keepdims=True)              # (bh, s, 1)
        args = (qf, kf, vf, do, lse[..., None], di)
        dq = flash_dq_pallas(*args, **kw)
        dk, dv = flash_dkv_pallas(*args, **kw)
    # the mask is RNG output, nothing upstream consumes its gradient
    return dq, dk, dv, None if mf is None else jnp.zeros_like(mf)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def flash_attention_pallas(q, k, v, scale: Optional[float] = None,
                           causal: bool = False, blk_q: int = _FLASH_BLK_Q,
                           blk_k: int = _FLASH_BLK_K,
                           kv_order: str = "fwd", drop_mask=None,
                           scope: Optional[str] = None):
    """Intra-chip blocked attention, DIFFERENTIABLE (custom-VJP pair of
    Pallas kernels). q/k: (B, S, H, D), v: (B, S, H, Dv) -> (B, S, H, Dv),
    in the operands' dtype (a caller that wants float32 products casts).
    Requires S % 128 == 0 (pad upstream). Grid (B·H, S/blk_q, S/blk_k), KV
    innermost, so the (S, S) score matrix never materializes — O(S·D)
    memory instead of O(S²). The backward is recompute-based: the forward
    saves only the output and the per-row logsumexp (named `FLASH_SAVED`
    for a surrounding `jax.checkpoint`); dQ streams KV tiles (same grid as
    forward), dK/dV streams Q tiles on the transposed grid. Forward block
    defaults tuned on v5e (2026-07-29: 22 ms vs 51 ms for the XLA einsum
    path at B1·S16384·H8·D64 causal — 2.3× — while small-S workloads
    should just use ops.attention). `blk_q`/`blk_k`/`kv_order` are the
    searched tuning axes (ops/templates.py); kv_order applies to the
    forward's KV streaming (the backward keeps its own fixed orders).
    `drop_mask` ((B, S, H, Dv), pre-scaled 0-or-1/keep — the dropout
    registry op's output) fuses the dropout over the attention output
    into the kernel's final write (the searched `drop` axis; gated by
    the composed `ops.reference.attn_dropout_forward` golden). `scope`
    names the `jax.named_scope` the backward's operations stand under
    (the caller's own, which a custom-VJP backward does not inherit)."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    blk_q, blk_k = flash_fit_block(s, blk_q), flash_fit_block(s, blk_k)
    assert s % blk_q == 0 and s % blk_k == 0, \
        f"seq len {s} must be divisible by 128 (got blocks {blk_q},{blk_k})"

    def heads_first(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    static = dict(scale=float(scale), causal=bool(causal), blk_q=blk_q,
                  blk_k=blk_k, kv_order=kv_order, interpret=_interpret())
    out = _flash_attn(
        heads_first(q), heads_first(k), heads_first(v),
        None if drop_mask is None
        else heads_first(jnp.asarray(drop_mask)).astype(q.dtype),
        tuple(sorted(static.items())), scope)
    return out.reshape(b, h, s, -1).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# hyper-connection sides (ISSUE 34): one Sinkhorn-mixed hyper-connection
# around a sub-layer (ops/lm.py) as four kernels tiled over tokens, each
# ONE pass over the rows it touches. A grid step holds a tile of tokens'
# rows of the streams (T, n*C) in VMEM and walks them a slab of lanes at a
# time; every product and every sum is float32, the streams are rounded
# once on the way out.
#
# Passes over the streams a connection (one pass = the (T, n*C) array once;
# an array of ONE stream, h, y, dh, dy, is a quarter at n = 4):
#   pre  forward   x in, h out                          1.25
#   post forward   x, y in, the streams out             2.25
#   the same two recomputed under the block's jax.checkpoint (the block's
#   last post side feeds nothing the backward reads, and goes)  <= 3.5
#   post backward  dout, x, y in, dx, dy out            3.5
#   pre  backward  x, gx, dh in, dx out                 3.25
# 13.75 of the 16 the issue allows (37 as XLA traced the same equations),
# plus six (T, kp) float32 arrays of per-token maps, 1.7 % of a pass each.
#
# The per-token maps live in HBM with the tokens in SUBLANES, (T, kp)
# float32, so that a token's coefficient is a column that broadcasts along
# the lanes of its row. Column layout, k = 2n + n*n: Hpre_i at i, Hpost_j
# at n + j, Hres[j, i] at 2n + j*n + i; in `raw` (the maps before their
# affine pairs and squashings) column k holds the token's rsqrt. The n x n
# mixing map is turned inside the kernel to tokens-in-LANES, n slabs of
# (n, tile), where the 20 Sinkhorn iterations are dense elementwise steps
# (a row's sum is a sum over a slab's sublanes, a column's a sum over the
# slabs), and turned back.
#
# Each of the four entry points is ONE module-level `jax.jit`: the 12 sites
# of a step share its trace and the step's module holds its kernel body
# once, called a site (PR 33 inlined them: 72 bodies traced and lowered by
# Python, 15 s of `setup_s` that no compile clock held).
# ---------------------------------------------------------------------------


def hc_maps_width(n: int) -> Tuple[int, int]:
    """(k, kp): the maps of a token, 2n + n*n numbers, and the columns of
    the arrays that carry them: one more for the token's rsqrt, on whole
    bfloat16 tiles of 16 rows of P^T."""
    k = 2 * n + n * n
    return k, -(-(k + 1) // 16) * 16


def hc_view(tokens: int, c: int, n: int) -> Optional[int]:
    """The token tile the four kernels take streams (tokens, n*c) with
    (the block comment at _HC_TOKEN_TILE_MAX has the rule), or None where they
    take none (the caller then traces the XLA form): a stream is whole
    lanes and a multiple of 128 tokens that fits divides the tokens. A
    rule of the shape alone: what a unit reports is what it traces in any
    compute dtype."""
    if c % _LANE or n < 2:
        return None
    return _largest_divisor(tokens, _LANE, min(
        _HC_TOKEN_TILE_MAX, _HC_BLOCK_BUDGET // (2 * 4 * (3 * n + 2) * c)))


def _fold_lanes(a):
    """(rows, L) -> (rows, 128): its 128-lane groups added, whole
    registers at a time; the one reduction across lanes comes after the
    last slab."""
    out = a[:, :_LANE]
    for j in range(1, a.shape[1] // _LANE):
        out = out + a[:, j * _LANE:(j + 1) * _LANE]
    return out


def _lane_sum(a):
    return jnp.sum(a, axis=1, keepdims=True)


def _place(cols, rows: int, width: int):
    """(rows, width) float32 whose column k is `cols[k]` (rows, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), jnp.float32)
    for k, col in cols.items():
        out = jnp.where(lane == k, col, out)
    return out


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _hc_dot(a, b, dims):
    """A product on the MXU accumulated in float32. bfloat16 operands are
    exact in one pass, whatever matmul precision the caller has set; a
    float32 caller's setting holds."""
    exact = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    return lax.dot_general(
        a, b, (dims, ((), ())),
        precision=lax.Precision.DEFAULT if exact else None,
        preferred_element_type=jnp.float32)


def _res_slabs(t_ref, n: int):
    """The n x n map of a tile from the turned scratch (kp, tile): slab j
    is row j of every token's matrix, (n, tile)."""
    return [t_ref[(2 + j) * n:(3 + j) * n, :] for j in range(n)]


def _sinkhorn_slabs(m, iters: int, eps: float):
    """`ops.lm.sinkhorn` on slabs: every iterate, 2 * iters + 1 lists of n
    slabs (n, tile); rows before columns."""
    out = [m]
    for _ in range(iters):
        m = [s / (jnp.sum(s, axis=0, keepdims=True) + eps) for s in m]
        out.append(m)
        d = functools.reduce(jnp.add, m) + eps
        m = [s / d for s in m]
        out.append(m)
    return out


def _sinkhorn_slabs_transpose(its, g, iters: int, eps: float):
    """The cotangent of the first iterate from `g`, that of the last:
    y = m / d, d = sum m + eps gives dm = (dy - sum(dy y)) / d."""
    for step in reversed(range(iters)):
        prev, y = its[2 * step + 1], its[2 * step + 2]
        d = functools.reduce(jnp.add, prev) + eps
        t = functools.reduce(jnp.add, [a * b for a, b in zip(g, y)])
        g = [(a - t) / d for a in g]
        prev, y = its[2 * step], its[2 * step + 1]
        g = [(a - jnp.sum(a * b, axis=0, keepdims=True))
             / (jnp.sum(p, axis=0, keepdims=True) + eps)
             for a, b, p in zip(g, y, prev)]
    return g


def _hc_pre_fwd_kernel(x_ref, pt_ref, aff_ref, raw_ref, m_ref, h_ref, t_ref,
                       *, n: int, slab: int, iters: int, eps: float,
                       clamp: Tuple[float, float], norm_eps: float):
    """One read of a tile's rows: u = x P (rows, kp) on the MXU, the sum
    of squares, raw = u rsqrt(mean x^2 + eps) (the norm is a per-token
    scalar: applied after the product it is the same equation with no
    normed copy of x), the three maps (`m_ref`) and h = sum_i Hpre_i x_i
    from the rows still in VMEM."""
    rows, width = x_ref.shape
    c, (k, _) = width // n, hc_maps_width(n)
    u = _hc_dot(x_ref[...], pt_ref[...], ((1,), (1,)))
    ssq = jnp.zeros((rows, _LANE), jnp.float32)
    for j in range(width // slab):
        xf = x_ref[:, j * slab:(j + 1) * slab].astype(jnp.float32)
        ssq = ssq + _fold_lanes(xf * xf)
    r = lax.rsqrt(_lane_sum(ssq) / width + norm_eps)
    lane = lax.broadcasted_iota(jnp.int32, u.shape, 1)
    raw = jnp.where(lane == k, r, u * r)
    raw_ref[...] = raw
    lin = raw * aff_ref[0:1, :] + aff_ref[1:2, :]
    s = _sigmoid(lin)
    for j in range(c // slab):
        acc = s[:, 0:1] * x_ref[:, j * slab:(j + 1) * slab].astype(
            jnp.float32)
        for i in range(1, n):
            at = slice(i * c + j * slab, i * c + (j + 1) * slab)
            acc = acc + s[:, i:i + 1] * x_ref[:, at].astype(jnp.float32)
        h_ref[:, j * slab:(j + 1) * slab] = acc.astype(h_ref.dtype)
    t_ref[...] = lin.T
    res = _sinkhorn_slabs(
        [jnp.exp(jnp.clip(z, clamp[0], clamp[1])) for z in _res_slabs(
            t_ref, n)], iters, eps)[-1]
    for j in range(n):
        t_ref[(2 + j) * n:(3 + j) * n, :] = res[j]
    m_ref[...] = jnp.where(lane < n, s, jnp.where(
        lane < 2 * n, 2.0 * s, jnp.where(lane < k, t_ref[...].T, 0.0)))


def _hc_pre_bwd_kernel(x_ref, gx_ref, dh_ref, raw_ref, dm_ref, pt_ref,
                       aff_ref, dx_ref, dlin_ref, dpt_ref, t_ref, *, n: int,
                       slab: int, iters: int, eps: float,
                       clamp: Tuple[float, float]):
    """One read of x and dh: dHpre_i = <dh, x_i> joins the cotangent
    `dm_ref` the post side sent to the maps; back through the sigmoids
    and, with the tokens in the lanes, through the Sinkhorn iterations
    (run again from `raw_ref`) into `dlin_ref`, the cotangent of a raw + b
    (a's and b's gradients are sums of it); dx_i = gx_i + Hpre_i dh +
    ((draw r) P^T)_i - x_i r^2 <draw, raw> / (n C), `gx_ref` being what
    the post side's backward sent to the same x (added here, in float32,
    where autodiff would add two stream-sized arrays in a pass of its
    own); dP^T += (draw r)^T x, float32, over the grid."""
    rows, width = x_ref.shape
    c, (k, kp) = width // n, hc_maps_width(n)
    raw, a = raw_ref[...], aff_ref[0:1, :]
    r = raw[:, k:k + 1]
    lin = raw * a + aff_ref[1:2, :]
    s = _sigmoid(lin)
    acc = [jnp.zeros((rows, _LANE), jnp.float32) for _ in range(n)]
    for j in range(c // slab):
        dh = dh_ref[:, j * slab:(j + 1) * slab].astype(jnp.float32)
        for i in range(n):
            at = slice(i * c + j * slab, i * c + (j + 1) * slab)
            acc[i] = acc[i] + _fold_lanes(
                dh * x_ref[:, at].astype(jnp.float32))
    dm = dm_ref[...] + _place({i: _lane_sum(acc[i]) for i in range(n)},
                              rows, kp)
    lane = lax.broadcasted_iota(jnp.int32, raw.shape, 1)
    dsig = jnp.where(lane < n, dm, jnp.where(lane < 2 * n, 2.0 * dm, 0.0)) \
        * s * (1.0 - s)
    t_ref[...] = lin.T
    z = _res_slabs(t_ref, n)
    its = _sinkhorn_slabs([jnp.exp(jnp.clip(v, clamp[0], clamp[1]))
                           for v in z], iters, eps)
    t_ref[...] = dm.T
    g = _sinkhorn_slabs_transpose(its, _res_slabs(t_ref, n), iters, eps)
    for j in range(n):
        inside = (z[j] >= clamp[0]) & (z[j] <= clamp[1])
        t_ref[(2 + j) * n:(3 + j) * n, :] = jnp.where(
            inside, g[j] * its[0][j], 0.0)
    dlin = jnp.where(lane < 2 * n, dsig,
                     jnp.where(lane < k, t_ref[...].T, 0.0))
    dlin_ref[...] = dlin
    draw = dlin * a
    cx = r * r * _lane_sum(draw * raw) / width
    w = (draw * r).astype(x_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dpt_ref[...] = jnp.zeros(dpt_ref.shape, dpt_ref.dtype)

    dpt_ref[...] += _hc_dot(w, x_ref[...], ((0,), (0,)))
    for i in range(n):
        for j in range(c // slab):
            at = slice(i * c + j * slab, i * c + (j + 1) * slab)
            dx = gx_ref[:, at].astype(jnp.float32) \
                + s[:, i:i + 1] * dh_ref[:, j * slab:(j + 1) * slab].astype(
                    jnp.float32) \
                + _hc_dot(w, pt_ref[:, at], ((1,), (0,))) \
                - cx * x_ref[:, at].astype(jnp.float32)
            dx_ref[:, at] = dx.astype(dx_ref.dtype)


def _hc_post_fwd_kernel(x_ref, y_ref, m_ref, out_ref, *, n: int, slab: int):
    """out_j = sum_i Hres[j, i] x_i + Hpost_j y."""
    c, m = y_ref.shape[1], m_ref[...]
    for l in range(c // slab):
        y = y_ref[:, l * slab:(l + 1) * slab].astype(jnp.float32)
        xs = [x_ref[:, i * c + l * slab:i * c + (l + 1) * slab].astype(
            jnp.float32) for i in range(n)]
        for j in range(n):
            acc = m[:, n + j:n + j + 1] * y
            for i in range(n):
                col = 2 * n + j * n + i
                acc = acc + m[:, col:col + 1] * xs[i]
            out_ref[:, j * c + l * slab:j * c + (l + 1) * slab] = \
                acc.astype(out_ref.dtype)


def _hc_post_bwd_kernel(g_ref, x_ref, y_ref, m_ref, dx_ref, dy_ref, dm_ref,
                        *, n: int, slab: int):
    """One read of dout, x and y: dx_i = sum_j Hres[j, i] dout_j, dy =
    sum_j Hpost_j dout_j, and per token dHpost_j = <dout_j, y>, dHres[j,
    i] = <dout_j, x_i> in `m_ref`'s columns."""
    rows, c, m = y_ref.shape[0], y_ref.shape[1], m_ref[...]
    acc = {col: jnp.zeros((rows, _LANE), jnp.float32)
           for col in range(n, 2 * n + n * n)}
    for l in range(c // slab):
        at = lambda i: slice(i * c + l * slab,  # noqa: E731
                             i * c + (l + 1) * slab)
        gs = [g_ref[:, at(j)].astype(jnp.float32) for j in range(n)]
        y = y_ref[:, at(0)].astype(jnp.float32)
        dy = m[:, n:n + 1] * gs[0]
        for j in range(1, n):
            dy = dy + m[:, n + j:n + j + 1] * gs[j]
        dy_ref[:, at(0)] = dy.astype(dy_ref.dtype)
        for j in range(n):
            acc[n + j] = acc[n + j] + _fold_lanes(gs[j] * y)
        for i in range(n):
            xi = x_ref[:, at(i)].astype(jnp.float32)
            dx = m[:, 2 * n + i:2 * n + i + 1] * gs[0]
            for j in range(1, n):
                col = 2 * n + j * n + i
                dx = dx + m[:, col:col + 1] * gs[j]
            dx_ref[:, at(i)] = dx.astype(dx_ref.dtype)
            for j in range(n):
                col = 2 * n + j * n + i
                acc[col] = acc[col] + _fold_lanes(gs[j] * xi)
    dm_ref[...] = _place({col: _lane_sum(a) for col, a in acc.items()},
                         rows, m.shape[1])


def _hc_call(kernel, args, moves, outs, out_moves, tile: int,
             interpret: bool, scratch=(), **scalars):
    """One of the four over the token tiles. `moves` / `out_moves` say
    whether an operand moves with the tile (True) or stays (False: P^T,
    the affine pair, the dP^T accumulator). No result takes an input's
    buffer: asked for (dx in the place of the cotangent it is made
    from), XLA copied the input first and the step's temporaries grew by
    one stream-sized array (compiled for a described v5e, PR 33)."""
    tokens = args[0].shape[0]

    def spec(shape, moving):
        return pl.BlockSpec((tile if moving else shape[0], shape[1]),
                            (lambda t: (t, 0)) if moving
                            else (lambda t: (0, 0)),
                            memory_space=pltpu.VMEM)

    vma = jax.typeof(args[0]).vma       # as the LRN kernels: under shard_map
    return pl.pallas_call(
        functools.partial(kernel, **scalars),
        out_shape=[jax.ShapeDtypeStruct(s, d, vma=vma) for s, d in outs],
        grid=(tokens // tile,),
        in_specs=[spec(a.shape, mv) for a, mv in zip(args, moves)],
        out_specs=[spec(s, mv) for (s, _), mv in zip(outs, out_moves)],
        scratch_shapes=list(scratch),
        # the dP^T block stays over the whole grid and is added to
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_HC_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAMES[kernel.__name__],
    )(*args)


def _hc_geometry(x, n: int) -> Tuple[int, int]:
    """(token tile, lane slab) of streams the kernels take; streams they
    have no view of are the caller's fault."""
    tokens, width = x.shape
    c = width // n
    tile = hc_view(tokens, c, n)
    if not tile:
        raise ValueError(
            f"the hyper-connection kernels take no {x.dtype} streams "
            f"{tuple(x.shape)} of {n} (pallas_kernels.hc_view): "
            "znicz.lm.BlockSpec.lowerings names the XLA form for such a "
            "shape")
    return tile, _largest_divisor(c, _LANE, _HC_LANE_SLAB)


@_kernel_jit
def hc_pre_forward_pallas(x, pt, aff, *, n: int, iters: int, eps: float,
                          clamp: Tuple[float, float], norm_eps: float,
                          interpret: bool = False):
    """x (T, n*C), pt = [P_pre P_post P_res]^T padded to (kp, n*C) in x's
    dtype, aff (2, kp) float32 (row 0: the maps' scalars a by column, row
    1: their biases b) -> (raw (T, kp) float32, the maps m (T, kp)
    float32, h (T, C))."""
    tile, slab = _hc_geometry(x, n)
    tokens, width = x.shape
    kp = pt.shape[0]
    return _hc_call(
        _hc_pre_fwd_kernel, (x, pt, aff), (True, False, False),
        [((tokens, kp), jnp.float32), ((tokens, kp), jnp.float32),
         ((tokens, width // n), x.dtype)], (True, True, True), tile,
        interpret, scratch=[pltpu.VMEM((kp, tile), jnp.float32)], n=n,
        slab=slab, iters=iters, eps=eps, clamp=clamp, norm_eps=norm_eps)


@_kernel_jit
def hc_pre_backward_pallas(x, gx, dh, raw, dm, pt, aff, *, n: int,
                           iters: int, eps: float,
                           clamp: Tuple[float, float],
                           interpret: bool = False):
    """`gx` (T, n*C): the cotangent x has from the post side, added in;
    `dm` (T, kp): the maps'. -> (dx (T, n*C), dlin (T, kp) float32, dP^T
    (kp, n*C) float32)."""
    tile, slab = _hc_geometry(x, n)
    return _hc_call(
        _hc_pre_bwd_kernel, (x, gx, dh, raw, dm, pt, aff),
        (True, True, True, True, True, False, False),
        [(x.shape, x.dtype), (raw.shape, jnp.float32),
         (pt.shape, jnp.float32)], (True, True, False), tile, interpret,
        scratch=[pltpu.VMEM((pt.shape[0], tile), jnp.float32)], n=n,
        slab=slab, iters=iters, eps=eps, clamp=clamp)


@_kernel_jit
def hc_post_forward_pallas(x, y, m, *, n: int, interpret: bool = False):
    """x (T, n*C), y (T, C), the maps m (T, kp) float32 -> the streams."""
    tile, slab = _hc_geometry(x, n)
    return _hc_call(
        _hc_post_fwd_kernel, (x, y, m), (True, True, True),
        [(x.shape, x.dtype)], (True,), tile, interpret, n=n, slab=slab)[0]


@_kernel_jit
def hc_post_backward_pallas(g, x, y, m, *, n: int, interpret: bool = False):
    """-> (dx (T, n*C), dy (T, C), dm (T, kp) float32)."""
    tile, slab = _hc_geometry(x, n)
    return _hc_call(
        _hc_post_bwd_kernel, (g, x, y, m), (True, True, True, True),
        [(x.shape, x.dtype), (y.shape, y.dtype), (m.shape, jnp.float32)],
        (True, True, True), tile, interpret, n=n, slab=slab)


# ---------------------------------------------------------------------------
# grouped-query attention over selected keys (ISSUE 35): the main attention
# of `ops.attention.indexed_attention` as four kernels. A query attends to
# the keys its row of `mask` (T, T) int8 names (the indexer's selection,
# causal already); query head h reads key-value head h // (H / Hkv). The
# flash recurrence of the kernels above with bfloat16 operands, float32
# scores and accumulators, and the selection in the place of the causal
# rule: a tile above the diagonal holds no selected key and is passed over,
# its operands not fetched (the index maps stay on the last tile that is
# needed). Nothing (T, T)-sized exists in float32 but the mean-head
# probabilities the index loss reads (`veles_dsa_pmean`, one head's worth).
# Each entry point is ONE module-level `jax.jit` (PR 33's lesson).
# ---------------------------------------------------------------------------

def dsa_view(seq: int, head_dim: int) -> bool:
    """Whether the kernels take a sequence of `seq` tokens and heads of
    `head_dim`: whole (8, 128) tiles of keys and of a head."""
    return seq % _LANE == 0 and head_dim % _LANE == 0


def _dsa_scores(q, kb, keep, scale):
    return jnp.where(keep, _dsa_dot(q, kb, 1, 1) * scale, _NEG)


def _dsa_dot(a, b, ca: int, cb: int):
    """a . b over axis `ca` of a and `cb` of b, accumulated in float32; at
    the MXU's own precision whatever the ambient default says (under
    "highest" Mosaic refuses bfloat16 operands)."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _dsa_fwd_kernel(q_ref, k_ref, v_ref, mk_ref, o_ref, lse_ref, m_scr,
                    l_scr, acc_scr, *, scale: float):
    """Grid (H, q tiles, k tiles), keys innermost: the online softmax over
    the selected keys of a tile of queries."""
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * blk_k <= qi * blk_q + blk_q - 1)
    def _():
        keep = mk_ref[...].astype(jnp.int32) != 0
        s = _dsa_scores(q_ref[0], k_ref[0], keep, scale)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        # (a row none of whose keys so far is selected has m_new = _NEG,
        # where exp(s - m_new) would be 1)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        a = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * a + p.sum(axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * a + _dsa_dot(
            p.astype(v_ref.dtype), v_ref[0], 1, 0)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)
        # the queries' logsumexps leave as a ROW, the queries in the lanes:
        # as the column they are computed in, (H, T, 1) pads to 128 lanes
        # in HBM, 256 MB a layer of what the backward keeps
        col = m_scr[:] + jnp.log(l_scr[:])
        eye = lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 0) \
            == lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 1)
        lse_ref[0] = jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _dsa_pmean_kernel(q_ref, k_ref, lse_ref, mk_ref, p_ref, *, scale: float,
                      inv_heads: float, q0: int):
    """Grid (q tiles, k tiles, H), heads innermost: the mean over the
    heads of the attention probabilities of one tile, added up in the
    output block, which stays. The first query is the sequence's `q0`-th."""
    qi, ki, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(h == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(ki * blk_k <= q0 + qi * blk_q + blk_q - 1)
    def _():
        keep = mk_ref[...].astype(jnp.int32) != 0
        s = _dsa_scores(q_ref[0], k_ref[0], keep, scale)
        p_ref[...] += jnp.where(keep, jnp.exp(s - lse_ref[0]), 0.0) \
            * inv_heads


def _dsa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, mk_ref,
                   dq_ref, dq_scr, *, scale: float):
    """Grid (H, q tiles, k tiles), keys innermost: P from the saved
    logsumexp, dS = P (dO V^T - D), dQ += dS K scale."""
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(ki * blk_k <= qi * blk_q + blk_q - 1)
    def _():
        keep = mk_ref[...].astype(jnp.int32) != 0
        kb = k_ref[0]
        s = _dsa_scores(q_ref[0], kb, keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0]), 0.0)
        dp = _dsa_dot(do_ref[0], v_ref[0], 1, 1)
        ds = p * (dp - di_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + _dsa_dot(ds.astype(kb.dtype), kb, 1, 0)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dsa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, mk_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float):
    """Grid (Hkv, k tiles, heads of the group, q tiles): a tile of keys and
    values stays while the queries of every head that reads it stream
    past. dV += P^T dO, dK += dS^T Q scale."""
    ki, g, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ng, nq = pl.num_programs(2), pl.num_programs(3)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when((g == 0) & (qi == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(qi * blk_q + blk_q - 1 >= ki * blk_k)
    def _():
        keep = mk_ref[...].astype(jnp.int32) != 0
        q, do = q_ref[0], do_ref[0]
        s = _dsa_scores(q, k_ref[0], keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0]), 0.0)
        dv_scr[:] = dv_scr[:] + _dsa_dot(p.astype(do.dtype), do, 0, 0)
        dp = _dsa_dot(do, v_ref[0], 1, 1)
        ds = p * (dp - di_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + _dsa_dot(ds.astype(q.dtype), q, 0, 0)

    @pl.when((g == ng - 1) & (qi == nq - 1))
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dsa_blocks(seq: int) -> Tuple[int, int]:
    return flash_fit_block(seq, _DSA_BLK_Q), flash_fit_block(seq, _DSA_BLK_K)


def _dsa_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_DSA_VMEM_LIMIT)


@_kernel_jit
def dsa_attend_forward_pallas(q, k, v, mask, *, scale: float,
                              interpret: bool = False):
    """q (H, T, D), k and v (Hkv, T, D), mask (T, T) int8 -> (the heads'
    outputs (H, T, D) in q's dtype, the logsumexp of every query's selected
    scores (H, 1, T) float32; the other kernels take it as (H, T, 1))."""
    h, t, d = q.shape
    group = h // k.shape[0]
    bq, bk = _dsa_blocks(t)

    def last(i):                # the last tile of keys a tile of queries needs
        return (i * bq + bq - 1) // bk

    kv = _vmem((1, bk, d), lambda b, i, j: (b // group,
                                            jnp.minimum(j, last(i)), 0))
    return pl.pallas_call(
        functools.partial(_dsa_fwd_kernel, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((h, 1, t), jnp.float32)),
        grid=(h, t // bq, t // bk),
        in_specs=[_vmem((1, bq, d), lambda b, i, j: (b, i, 0)), kv, kv,
                  _vmem((bq, bk), lambda b, i, j: (
                      i, jnp.minimum(j, last(i))))],
        out_specs=(_vmem((1, bq, d), lambda b, i, j: (b, i, 0)),
                   _vmem((1, 1, bq), lambda b, i, j: (b, 0, i))),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dsa_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES["_dsa_fwd_kernel"],
    )(q, k, v, mask)


@_kernel_jit
def dsa_pmean_pallas(q, k, lse, mask, *, scale: float, q0: int = 0,
                     interpret: bool = False):
    """The queries q (H, Tq, D), the sequence's from its `q0`-th on, with
    their logsumexps (H, Tq, 1), against the keys k (Hkv, Tk, D), mask
    (Tq, Tk) -> (Tq, Tk) float32: the mean over the H heads of every
    query's attention probabilities, 0 where a key is not selected."""
    h, t, d = q.shape
    tk = k.shape[1]
    group = h // k.shape[0]
    bq, bk = flash_fit_block(t, _DSA_BLK_Q), flash_fit_block(tk, _DSA_BLK_K)
    return pl.pallas_call(
        functools.partial(_dsa_pmean_kernel, scale=scale,
                          inv_heads=1.0 / h, q0=q0),
        out_shape=jax.ShapeDtypeStruct((t, tk), jnp.float32),
        grid=(t // bq, tk // bk, h),
        in_specs=[_vmem((1, bq, d), lambda i, j, b: (b, i, 0)),
                  _vmem((1, bk, d), lambda i, j, b: (b // group, j, 0)),
                  _vmem((1, bq, 1), lambda i, j, b: (b, i, 0)),
                  _vmem((bq, bk), lambda i, j, b: (i, j))],
        out_specs=_vmem((bq, bk), lambda i, j, b: (i, j)),
        compiler_params=_dsa_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES["_dsa_pmean_kernel"],
    )(q, k, lse, mask)


@_kernel_jit
def dsa_attend_backward_pallas(q, k, v, do, lse, di, mask, *, scale: float,
                               interpret: bool = False):
    """`do` (H, T, D) the outputs' cotangent, `di` (H, T, 1) float32 its
    row sums against the outputs -> (dq (H, T, D), dk, dv (Hkv, T, D)), in
    the operands' dtypes."""
    h, t, d = q.shape
    hkv = k.shape[0]
    group = h // hkv
    bq, bk = _dsa_blocks(t)

    def last(i):
        return (i * bq + bq - 1) // bk

    row = lambda b, i, j: (b, i, 0)  # noqa: E731
    kv = _vmem((1, bk, d), lambda b, i, j: (b // group,
                                            jnp.minimum(j, last(i)), 0))
    dq = pl.pallas_call(
        functools.partial(_dsa_dq_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((h, t, d), q.dtype),
        grid=(h, t // bq, t // bk),
        in_specs=[_vmem((1, bq, d), row), kv, kv, _vmem((1, bq, d), row),
                  _vmem((1, bq, 1), row), _vmem((1, bq, 1), row),
                  _vmem((bq, bk), lambda b, i, j: (
                      i, jnp.minimum(j, last(i))))],
        out_specs=_vmem((1, bq, d), row),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dsa_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES["_dsa_dq_kernel"],
    )(q, k, v, do, lse, di, mask)

    def first(j):               # the first tile of queries a tile of keys meets
        return (j * bk) // bq

    qrow = lambda b, j, g, i: (b * group + g,  # noqa: E731
                               jnp.maximum(i, first(j)), 0)
    krow = lambda b, j, g, i: (b, j, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dsa_dkv_kernel, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((hkv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((hkv, t, d), v.dtype)),
        grid=(hkv, t // bk, group, t // bq),
        in_specs=[_vmem((1, bq, d), qrow), _vmem((1, bk, d), krow),
                  _vmem((1, bk, d), krow), _vmem((1, bq, d), qrow),
                  _vmem((1, bq, 1), qrow), _vmem((1, bq, 1), qrow),
                  _vmem((bq, bk), lambda b, j, g, i: (
                      jnp.maximum(i, first(j)), j))],
        out_specs=(_vmem((1, bk, d), krow), _vmem((1, bk, d), krow)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_dsa_params(("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES["_dsa_dkv_kernel"],
    )(q, k, v, do, lse, di, mask)
    return dq, dk, dv


# -- the indexer's scores (ISSUE 36) -------------------------------------------
# I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]) and its gradient, the index
# heads' scores of a (queries, keys) tile in VMEM only: nothing of shape
# (heads, queries, keys) reaches HBM, forward or backward (as plain XLA the
# float32 score tensor, its sign and its cotangent did, 388 ms a step of
# keye2_ep8.long16k). The queries are the sequence's from its `q0`-th on,
# an int32 the kernels read from SMEM (a block of a `lax.map` knows its
# place only as it runs): a tile wholly above the diagonal is passed over,
# its operands not fetched: its scores are written 0 (every reader masks
# them by `causal`), its cotangent is not read.

def dsa_index_view(seq: int, index_heads: int, index_dim: int) -> bool:
    """Whether `veles_dsa_index_fwd` / `_bwd` take the keys of (a band of)
    a sequence of `seq` tokens under `index_heads` index heads of
    `index_dim`: whole 128-lane tiles of keys, the heads' weights in one
    tile's lanes, a head's width in whole sublanes."""
    return (seq % _LANE == 0 and 0 < index_heads <= _LANE
            and index_dim % _MIN_ROW_TILE == 0)


def _dsa_index_below(q0_ref, blk_q: int, blk_k: int):
    """Whether this grid step's (queries, keys) tile holds a causal pair."""
    i, j = pl.program_id(0), pl.program_id(1)
    return j * blk_k <= q0_ref[0] + i * blk_q + blk_q - 1


def _dsa_index_fwd_kernel(q0_ref, qi_ref, w_ref, ki_ref, o_ref):
    """Grid (q tiles, k tiles): the heads one after another, each one
    product, relu, times its column of `w`, added in the heads' order."""
    below = _dsa_index_below(q0_ref, *o_ref.shape)

    @pl.when(below)
    def _():
        kb, w = ki_ref[...], w_ref[...]
        acc = None
        for h in range(qi_ref.shape[0]):
            t = jnp.maximum(_dsa_dot(qi_ref[h], kb, 1, 1), 0.0) \
                * w[:, h:h + 1]
            acc = t if acc is None else acc + t
        o_ref[...] = acc

    @pl.when(jnp.logical_not(below))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _dsa_index_bwd_kernel(q0_ref, qi_ref, w_ref, ki_ref, g_ref, dqi_ref,
                          dw_ref, dki_ref, dqi_scr):
    """Grid (q tiles, k tiles), keys innermost, BOTH sequential: the keys'
    gradient (K, Di) float32 is one block that stays for the whole grid,
    so one kernel forms a head's score once and spends it on all three
    gradients (three products a head and tile; cut in two in the manner of
    `veles_dsa_attend_dq` / `_dkv` it would be four). With m = dI (s > 0):
    dqi_j += (m w_j) ki, dki += (m w_j)^T qi_j, dw_j += sum_s m s."""
    i, j, nj = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    blk_q, blk_k = g_ref.shape

    @pl.when((i == 0) & (j == 0))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j == 0)
    def _():
        dqi_scr[...] = jnp.zeros_like(dqi_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(_dsa_index_below(q0_ref, blk_q, blk_k))
    def _():
        kb, w, d = ki_ref[...], w_ref[...], g_ref[...]
        head = lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw = jnp.zeros_like(w)
        dk = jnp.zeros((blk_k, kb.shape[1]), jnp.float32)
        for h in range(qi_ref.shape[0]):
            qh = qi_ref[h]
            s = _dsa_dot(qh, kb, 1, 1)
            m = jnp.where(s > 0, d, 0.0)
            dw = dw + jnp.where(
                head == h, (m * s).sum(axis=1, keepdims=True), 0.0)
            g = (m * w[:, h:h + 1]).astype(kb.dtype)
            dqi_scr[h] = dqi_scr[h] + _dsa_dot(g, kb, 1, 0)
            dk = dk + _dsa_dot(g, qh, 0, 0)
        dw_ref[...] += dw
        rows = pl.ds(pl.multiple_of(j * blk_k, blk_k), blk_k)
        dki_ref[rows, :] += dk

    @pl.when(j == nj - 1)
    def _():
        dqi_ref[...] = dqi_scr[...].astype(dqi_ref.dtype)


def _dsa_index_call(kernel, q0, qi, w, ki, more, out_shape, out_blocks,
                    scratch, interpret: bool):
    """One of the two kernels over (Tq / bq, K / bk) tiles: qi (Hi, Tq,
    Di) a row of tiles, w (Tq, Hi) likewise, ki (K, Di) a tile of keys and
    every array of `more` a (queries, keys) tile, both of which stay on the
    last tile a row of tiles needs; `out_blocks` name the results' blocks
    as "heads", "rows", "pairs" or "keys" (whole, resident)."""
    hi, tq, di = qi.shape
    bq = flash_fit_block(tq, _DSA_INDEX_BLK_Q)
    bk = flash_fit_block(ki.shape[0], _DSA_INDEX_BLK_K)
    nk = ki.shape[0] // bk

    def last(i, q0_ref):
        return jnp.minimum((q0_ref[0] + i * bq + bq - 1) // bk, nk - 1)

    blocks = {
        "heads": _vmem((hi, bq, di), lambda i, j, q0_ref: (0, i, 0)),
        "rows": _vmem((bq, hi), lambda i, j, q0_ref: (i, 0)),
        "pairs": _vmem((bq, bk), lambda i, j, q0_ref: (i, j)),
        "keys": _vmem(ki.shape, lambda i, j, q0_ref: (0, 0)),
    }
    key_tile = _vmem((bk, di), lambda i, j, q0_ref: (
        jnp.minimum(j, last(i, q0_ref)), 0))
    pair_tile = _vmem((bq, bk), lambda i, j, q0_ref: (
        i, jnp.minimum(j, last(i, q0_ref))))
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tq // bq, nk),
            in_specs=[blocks["heads"], blocks["rows"], key_tile]
            + [pair_tile] * len(more),
            out_specs=tuple(blocks[b] for b in out_blocks),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_DSA_INDEX_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAMES[kernel.__name__],
    )(jnp.asarray(q0, jnp.int32).reshape(1), qi, w, ki, *more)


@_kernel_jit
def dsa_index_forward_pallas(qi, w, ki, q0, *, interpret: bool = False):
    """qi (Hi, Tq, Di) heads first, w (Tq, Hi) float32, ki (K, Di), q0 an
    int32 scalar -> the index scores (Tq, K) float32 of the queries [q0,
    q0 + Tq) against the keys [0, K); 0 in the tiles above the diagonal."""
    return _dsa_index_call(
        _dsa_index_fwd_kernel, q0, qi, w, ki, (),
        (jax.ShapeDtypeStruct((qi.shape[1], ki.shape[0]), jnp.float32),),
        ("pairs",), [], interpret)[0]


@_kernel_jit
def dsa_index_backward_pallas(qi, w, ki, q0, d_index, *,
                              interpret: bool = False):
    """The same operands and the scores' cotangent (Tq, K) float32 -> (dqi
    (Hi, Tq, Di) in qi's dtype, dw (Tq, Hi) float32, dki (K, Di) float32,
    summed over all the queries before anything rounds it)."""
    return _dsa_index_call(
        _dsa_index_bwd_kernel, q0, qi, w, ki, (d_index,),
        (jax.ShapeDtypeStruct(qi.shape, qi.dtype),
         jax.ShapeDtypeStruct(w.shape, jnp.float32),
         jax.ShapeDtypeStruct(ki.shape, jnp.float32)),
        ("heads", "rows", "keys"),
        [pltpu.VMEM(qi.shape[:1] + (flash_fit_block(
            qi.shape[1], _DSA_INDEX_BLK_Q), qi.shape[2]), jnp.float32)],
        interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def index_scores_pallas(qi, w, ki, q0=0, interpret: bool = False):
    """`ops.attention.index_scores` of the queries [q0, q0 + Tq) against
    the keys [0, K) through the two kernels: qi (Tq, Hi, Di), w (Tq, Hi)
    float32, ki (K, Di), q0 an int32 scalar (traced or not) -> (Tq, K)
    float32, right at every causal pair (a tile wholly above the diagonal
    reads 0, and hands no gradient on); differentiable in qi, w and ki."""
    return dsa_index_forward_pallas(jnp.transpose(qi, (1, 0, 2)), w, ki, q0,
                                    interpret=interpret)


def _index_scores_fwd(qi, w, ki, q0, interpret):
    return index_scores_pallas(qi, w, ki, q0, interpret), (qi, w, ki, q0)


def _index_scores_bwd(interpret, res, d_index):
    qi, w, ki, q0 = res
    dqi, dw, dki = dsa_index_backward_pallas(
        jnp.transpose(qi, (1, 0, 2)), w, ki, q0, d_index,
        interpret=interpret)
    return (dqi.transpose(1, 0, 2), dw.astype(w.dtype), dki.astype(ki.dtype),
            None)


index_scores_pallas.defvjp(_index_scores_fwd, _index_scores_bwd)


# ---------------------------------------------------------------------------
# grouped products over a sorted buffer (ISSUE 35): what `ops.moe`'s held
# experts multiply by. Rows [lo_g, hi_g) of x (R, A) are group g's, in
# order; w is (G, A, B). `veles_gmm`: y[rows of g] = x[rows of g] @ w[g]
# (or @ w[g]^T, the product the backward needs for x); `veles_tgmm`:
# dw[g] = x[rows of g]^T @ dy[rows of g]. The work is a LIST of (group,
# row tile) items, a tile that two groups share standing once for each
# (`gmm_items`; the scheme of Gale et al., MegaBlocks, arXiv:2211.15841,
# as jax's `megablox` walks it), read from SMEM by the index maps: a grid
# step holds a tile of rows and the group's whole matrix, so a matrix is
# fetched once a group and no step accumulates over the inner dimension.
# The grid is as long as the list can get (row tiles + groups - 1); past
# the list's end a step computes nothing and its blocks stay where they
# are, so a buffer's empty tail costs a third of a microsecond a tile.
# Rows of y that are no group's are NOT written: the caller masks them.
# Each entry point is ONE module-level `jax.jit`.
# ---------------------------------------------------------------------------

def gmm_view(rows: int, a: int, b: int, itemsize: int) -> Optional[int]:
    """The row tile the grouped-product kernels take a buffer of `rows`
    rows against (G, a, b) matrices with, or None where they have no view
    of the shape: whole 128-lane tiles of both widths, a row tile of whole
    sublane tiles that divides the rows, and the widest kernel's blocks
    (the transposed product: a float32 accumulator and a double-buffered
    result of a whole matrix) within _GMM_BLOCK_BUDGET."""
    if a % _LANE or b % _LANE:
        return None
    tile = _largest_divisor(rows, _sublanes(itemsize), _GMM_ROW_TILE)
    if not tile:
        return None
    blocks = 4 * a * b + 2 * itemsize * (a * b + tile * (a + b))
    return tile if blocks <= _GMM_BLOCK_BUDGET else None


def gmm_items(sizes, rows: int, tile: int):
    """The work list of a buffer of `rows` rows whose groups hold `sizes`
    (G,) int32 rows in order: (group of item i, row tile of item i, first
    row of every group, the row past its last, the list's length (1,)),
    the first two (rows // tile + G - 1,) long and past the list's end
    standing on its last item. A group gets the tiles its rows touch; an
    EMPTY group one tile of which it keeps no row, so that the transposed
    product writes its zeros."""
    g, tiles = sizes.shape[0], rows // tile
    hi = jnp.minimum(jnp.cumsum(sizes), rows).astype(jnp.int32)
    lo = jnp.concatenate([jnp.zeros((1,), jnp.int32), hi[:-1]])
    first = jnp.minimum(lo // tile, tiles - 1)
    last = jnp.minimum(jnp.maximum(hi - 1, lo) // tile, tiles - 1)
    count = last - first + 1
    start = jnp.cumsum(count) - count
    n = count.sum()
    at = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), count,
                       total_repeat_length=at.shape[0])
    tile_of = first[group] + at - start[group]
    end = jnp.minimum(at, n - 1)        # the items past the end: the last
    return (group[end], tile_of[end].astype(jnp.int32), lo, hi,
            n.astype(jnp.int32)[None])


def _gmm_rows_kept(tile_ref, lo_ref, hi_ref, i, g, shape):
    """Which rows of item i's tile are group g's, as a mask of `shape`."""
    row = tile_ref[i] * shape[0] + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo_ref[g]) & (row < hi_ref[g])


def _gmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, n_ref, x_ref, w_ref,
                y_ref, *, transposed: bool):
    """Grid (items,): one tile of rows by its group's whole matrix. The
    rows of the tile that are another group's keep what the block holds
    (that group's item wrote it, or will)."""
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _():
        y = _dsa_dot(x_ref[...], w_ref[0], 1, 1 if transposed else 0)
        keep = _gmm_rows_kept(tile_ref, lo_ref, hi_ref, i, group_ref[i],
                              y.shape)
        y_ref[...] = jnp.where(keep, y, y_ref[...].astype(jnp.float32)
                               ).astype(y_ref.dtype)


def _tgmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, n_ref, x_ref, dy_ref,
                 dw_ref, acc, *, mask_x: bool):
    """Grid (items,): a group's items follow one another, their products
    added up in `acc` and written with the group's last. The rows of
    another group are zeroed in the narrower operand (`mask_x` says
    which)."""
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _():
        g = group_ref[i]

        @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
        def _():
            acc[...] = jnp.zeros_like(acc)

        x, dy = x_ref[...], dy_ref[...]
        narrow = x if mask_x else dy
        keep = _gmm_rows_kept(tile_ref, lo_ref, hi_ref, i, g, narrow.shape)
        narrow = jnp.where(keep, narrow.astype(jnp.float32), 0.0
                           ).astype(narrow.dtype)
        x, dy = (narrow, dy) if mask_x else (x, narrow)
        acc[...] += _dsa_dot(x, dy, 0, 0)

        @pl.when((i == n - 1) | (group_ref[jnp.minimum(
            i + 1, group_ref.shape[0] - 1)] != g))
        def _():
            dw_ref[0] = acc[...].astype(dw_ref.dtype)


def _gmm_call(kernel, items, arrays, in_blocks, out_shape, out_block,
              scratch, interpret: bool):
    """One grouped-product kernel over the work list `items`; a block is
    (shape, what of an item names it: a function of (group, tile))."""
    def spec(block):
        shape, of = block
        return pl.BlockSpec(shape, lambda i, group, tile, *_: of(
            group[i], tile[i]))

    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(items), grid=(items[0].shape[0],),
            in_specs=[spec(b) for b in in_blocks],
            out_specs=spec(out_block), scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GMM_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAMES[getattr(kernel, "func", kernel).__name__],
    )(*items, *arrays)


def _gmm_tile(rows: int, a: int, b: int, dtype) -> int:
    """The kernels' row tile; a shape they have no view of is the
    caller's fault."""
    tile = gmm_view(rows, a, b, jnp.dtype(dtype).itemsize)
    if not tile:
        raise ValueError(
            f"the grouped-product kernels take no {rows} rows against "
            f"({a}, {b}) matrices of {dtype} (pallas_kernels.gmm_view): "
            "call lax.ragged_dot for such a shape, as ops.moe does")
    return tile


@_kernel_jit
def gmm_pallas(x, w, group, tile_of, lo, hi, n, *, transposed: bool = False,
               interpret: bool = False):
    """x (R, A) and w (G, A, B) -> (R, B) in x's dtype; `transposed`: x
    (R, B) -> (R, A). The items are `gmm_items`' at the tile `gmm_view`
    names."""
    rows, inner = x.shape
    _, a, b = w.shape
    out = a if transposed else b
    tile = _gmm_tile(rows, a, b, x.dtype)
    return _gmm_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        (group, tile_of, lo, hi, n), (x, w),
        [((tile, inner), lambda g, t: (t, 0)),
         ((1, a, b), lambda g, t: (g, 0, 0))],
        jax.ShapeDtypeStruct((rows, out), x.dtype),
        ((tile, out), lambda g, t: (t, 0)), [], interpret)


@_kernel_jit
def tgmm_pallas(x, dy, group, tile_of, lo, hi, n, *, groups: int,
                interpret: bool = False):
    """x (R, A) and dy (R, B) -> (groups, A, B) in x's dtype: every
    group's x^T dy over its rows, zeros for a group without rows."""
    rows, a = x.shape
    b = dy.shape[1]
    tile = _gmm_tile(rows, a, b, x.dtype)
    return _gmm_call(
        functools.partial(_tgmm_kernel, mask_x=a < b),
        (group, tile_of, lo, hi, n), (x, dy),
        [((tile, a), lambda g, t: (t, 0)), ((tile, b), lambda g, t: (t, 0))],
        jax.ShapeDtypeStruct((groups, a, b), x.dtype),
        ((1, a, b), lambda g, t: (g, 0, 0)),
        [pltpu.VMEM((a, b), jnp.float32)], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def grouped_matmul(x, w, group, tile_of, lo, hi, n, interpret=False):
    """`lax.ragged_dot(x, w, sizes)` on the work list `gmm_items(sizes,
    ...)` gives, but for the rows that are no group's, which are not
    written; differentiable in x and w."""
    return gmm_pallas(x, w, group, tile_of, lo, hi, n, interpret=interpret)


def _grouped_matmul_fwd(x, w, group, tile_of, lo, hi, n, interpret):
    items = (group, tile_of, lo, hi, n)
    return gmm_pallas(x, w, *items, interpret=interpret), (x, w, items)


def _grouped_matmul_bwd(interpret, res, dy):
    x, w, items = res
    return (gmm_pallas(dy, w, *items, transposed=True, interpret=interpret),
            tgmm_pallas(x, dy, *items, groups=w.shape[0],
                        interpret=interpret).astype(w.dtype),
            None, None, None, None, None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# ---------------------------------------------------------------------------
# the held experts' combine (ISSUE 43): every token's sum of its live rows of
# a sorted buffer, what `ops.moe._sum_rows` gathered a row a (token, slot)
# pair for, held or not. With the rows in TOKEN order (`seg_sum_plan`: one
# sort of the buffer's rows by the pair sorted there, the dead rows last;
# `_rows_in_token_order`: the live rows gathered, a chunk at a time) a
# tile of tokens owns a run of rows, and its sums are one-hot products:
# out[tile] = onehot(row's token - tile's first)^T @ y[rows], 0 and 1 exact
# in any dtype, accumulated in float32 and rounded once. That is the grouped
# transposed product's shape of work with the token tiles as groups, so the
# work list is `gmm_items`' and the call `_gmm_call`'s; the one-hot is built
# in VMEM from the rows' token ids. Rows that are no tile's (past `n_live`)
# are SELECTED away before the product: they hold whatever a grouped
# product left there, and 0 x NaN is NaN.
# ---------------------------------------------------------------------------

def _seg_sum_row_tile(rows: int) -> Optional[int]:
    return _largest_divisor(rows, _LANE, _SEG_SUM_ROW_TILE)


def seg_sum_view(rows: int, tokens: int, width: int,
                 itemsize: int) -> Optional[int]:
    """The token tile `veles_seg_sum` sums a buffer of `rows` rows of
    `width` into `tokens` tokens with, or None where it has no view of
    the shape: whole 128-lane tiles of the width, a row tile of whole lane
    tiles (the rows are the one-hot's lanes) that divides the rows, a
    token tile of whole sublane tiles that divides the tokens, and the
    blocks (double-buffered rows and sums, the float32 accumulator and
    the product's float32 temporaries) within _GMM_BLOCK_BUDGET."""
    if width % _LANE:
        return None
    row_tile = _seg_sum_row_tile(rows)
    tile = _largest_divisor(tokens, _sublanes(itemsize), _SEG_SUM_TOKEN_TILE)
    if not row_tile or not tile:
        return None
    blocks = width * ((2 * itemsize + 4) * row_tile + (2 * itemsize + 8) * tile)
    return tile if blocks <= _GMM_BLOCK_BUDGET else None


def seg_sum_plan(pairs, n_live, k: int, tokens: int, tile: int):
    """What `seg_sum` needs of a sorted buffer whose row r holds the
    (token, slot) pair `pairs[r]` (token-major ids, (R,) int32), the first
    `n_live` rows live: (the buffer's rows in token order, the dead ones
    last (R,); their tokens as (R // row tile, 1, row tile), `tokens` for
    a dead row; `gmm_items`' work list with the tiles of `tile` tokens as
    groups)."""
    rows = pairs.shape[0]
    row_tile = _seg_sum_row_tile(rows)
    at = jnp.arange(rows, dtype=jnp.int32)
    key, perm = lax.sort_key_val(
        jnp.where(at < n_live, pairs.astype(jnp.int32), tokens * k), at)
    tok = key // k
    sizes = (tok[:, None] // tile == jnp.arange(
        tokens // tile, dtype=jnp.int32)).sum(axis=0, dtype=jnp.int32)
    return (perm, tok.reshape(-1, 1, row_tile),
            *gmm_items(sizes, rows, row_tile))


def _seg_sum_kernel(group_ref, tile_ref, lo_ref, hi_ref, n_ref, tok_ref,
                    y_ref, out_ref, acc):
    """Grid (items,): a token tile's items follow one another, their
    one-hot products added up in `acc` and written with the tile's last."""
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _():
        g = group_ref[i]

        @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
        def _():
            acc[...] = jnp.zeros_like(acc)

        y = y_ref[...]
        keep = _gmm_rows_kept(tile_ref, lo_ref, hi_ref, i, g,
                              (y.shape[0], 1))
        y = jnp.where(keep, y.astype(jnp.float32), 0.0).astype(y.dtype)
        tokens = acc.shape[0]
        hot = tok_ref[0] - g * tokens == lax.broadcasted_iota(
            jnp.int32, (tokens, y.shape[0]), 0)
        # (a float32 buffer's rows go through the product whole)
        acc[...] += lax.dot_general(
            hot.astype(y.dtype), y, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST if y.dtype == jnp.float32
            else lax.Precision.DEFAULT, preferred_element_type=jnp.float32)

        @pl.when((i == n - 1) | (group_ref[jnp.minimum(
            i + 1, group_ref.shape[0] - 1)] != g))
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)


def _rows_in_token_order(y, perm, n_live):
    """y's rows `perm[j]` for every j below `n_live`, a chunk of
    _SEG_SUM_TAKE_ROWS rows a step of a walk as long as the live rows
    are; zeros past the chunk that holds the last of them, which the
    kernel reads no more than it reads the dead rows of that chunk."""
    rows = perm.shape[0]
    chunk = _largest_divisor(rows, _LANE, _SEG_SUM_TAKE_ROWS)

    def more(i, out):
        at = lax.dynamic_slice(perm, (i * chunk,), (chunk,))
        return lax.dynamic_update_slice(out, jnp.take(y, at, axis=0),
                                        (i * chunk, 0))

    return lax.fori_loop(0, (n_live + chunk - 1) // chunk, more,
                         jnp.zeros_like(y))


@_kernel_jit
def seg_sum_pallas(y, tok, group, tile_of, lo, hi, n, *, tile: int,
                   interpret: bool = False):
    """y (R, C), its rows in token order, and `seg_sum_plan`'s tokens and
    items over tiles of `tile` tokens -> (tokens, C) in y's dtype: every
    token's sum of its rows inside its tile's [lo, hi), zeros for a token
    without one."""
    c, row_tile, tokens = y.shape[1], tok.shape[-1], tile * lo.shape[0]
    return _gmm_call(
        _seg_sum_kernel, (group, tile_of, lo, hi, n), (tok, y),
        [((None, 1, row_tile), lambda g, t: (t, 0, 0)),
         ((row_tile, c), lambda g, t: (t, 0))],
        jax.ShapeDtypeStruct((tokens, c), y.dtype),
        ((tile, c), lambda g, t: (g, 0)),
        [pltpu.VMEM((tile, c), jnp.float32)], interpret)


def seg_sum(y, plan, n_live, tile: int, interpret: bool = False):
    """Every token's sum of its live rows of the sorted buffer y (R, C):
    (tokens, C) in y's dtype, summed in float32 and rounded once. `plan`
    is `seg_sum_plan`'s for the buffer at `tile` tokens a group."""
    perm, *items = plan
    return seg_sum_pallas(_rows_in_token_order(y, perm, n_live), *items,
                          tile=tile, interpret=interpret)


# ---------------------------------------------------------------------------
# the operand stage of the chunked Gated DeltaNet (ISSUE 42): what
# `ops.linear_attention.gated_delta_chunked` forms for every chunk before
# the chain along the sequence walks them. A chunk and head's (C, C)
# float32 matrices (the decay matrix, K K^T, the ten products of the
# inverse, Q K^T, and in the backward their cotangents) passed through HBM
# one by one as XLA traced the same equations, 0.5 GB a pass at 16,384
# chunk-heads a layer with their 64 lanes padded to 128; here they live and
# die in VMEM, and what crosses HBM is the stage's interface: q, k, v, the
# cumulative log-decay and beta in, w, u0, kd, attn and qg out
# (`veles_gdn_chunk_fwd`), and the inputs with five cotangents in, five
# gradients out (`veles_gdn_chunk_bwd`, which forms the inverse again: the
# stage keeps no residual but its inputs).
#
# TWO chunk-heads stand side by side as one block-diagonal matrix of 128
# rows (C = 64): their tokens' rows follow one another in the sublanes
# (`(B, C, d)` read as `(B / 2, 2 C, d)`: a bitcast), so every (2C, 2C)
# product is one whole tile of the 128-deep array and every float32 matrix
# fills its registers' lanes; what the two chunks have no business with,
# the off-diagonal blocks, is masked out where it arises (K K^T, Q K^T) and
# stays zero through the inverse. The inverse is `linear_attention.
# _inverse_of`'s, step for step: the 16-row diagonal blocks by a product of
# powers, larger blocks by substitution; float32 matrices whose products
# read them in ONE bfloat16 pass and accumulate in float32, which is what
# a TPU's default precision gives the XLA form's float32 products. A
# per-token scalar (gamma, beta) arrives as a ROW, the tokens in the lanes
# (a column would pad to 128 lanes in HBM), and is turned to a column
# under the identity's mask.
# ---------------------------------------------------------------------------


def gdn_view(chunk_heads: int, chunk: int, key_dim: int, value_dim: int,
             scan_dtype, dtype) -> Optional[int]:
    """The chunk-heads a grid step of the two `veles_gdn_chunk_*` kernels
    holds, or None where they take none (the caller then traces the XLA
    form): two chunks fill the 128 lanes side by side (a chunk of 64, an
    even number of chunk-heads), keys and values are whole lanes, gates
    and decays are float32 and the products' operands bfloat16 (the
    precision the kernels' products are written for). The step's blocks
    are counted for the wider kernel, the backward (seven operands of a
    head's width in, three out, the chunk's square cotangent padded to
    the lanes), double-buffered, within _GDN_BLOCK_BUDGET; whole tiles of
    8 pairs' rows of the per-token scalars, or the whole array."""
    if (chunk * 2 != _LANE or chunk_heads % 2 or key_dim % _LANE
            or value_dim % _LANE or jnp.dtype(scan_dtype) != jnp.float32
            or jnp.dtype(dtype) != jnp.bfloat16):
        return None
    a_head = 2 * 2 * chunk * (7 * key_dim + 3 * value_dim + _LANE)
    cap = _GDN_BLOCK_BUDGET // a_head
    if chunk_heads <= cap:
        return chunk_heads
    return _largest_divisor(chunk_heads, 2 * _MIN_ROW_TILE, cap)


def _gdn_dot(a, b, ca: int = 1, cb: int = 0):
    """a . b over axis `ca` of a and `cb` of b: the operands read in one
    bfloat16 pass, the sum float32."""
    return lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        (((ca,), (cb,)), ((), ())), precision=lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _gdn_inverse(a, r, c, chunk: int, block: int):
    """(I + a)^-1 of a block-diagonal, strictly lower-triangular float32
    `a` whose blocks hold `chunk` rows (`r`, `c`: its row and column
    numbers): `linear_attention._inverse_of`, step for step."""
    eye = (r == c).astype(jnp.float32)
    d = jnp.where((r // block) == (c // block), a, 0.0)
    inv, power, reach = eye - d, d, 2
    while reach < block:
        power = _gdn_dot(power, power)
        inv = _gdn_dot(inv, eye + power)
        reach *= 2
    size = block
    while size < chunk:
        low = ((r // (2 * size)) == (c // (2 * size))) \
            & ((r // size) % 2 == 1) & ((c // size) % 2 == 0)
        inv = inv - _gdn_dot(_gdn_dot(inv, jnp.where(low, a, 0.0)), inv)
        size *= 2
    return inv


def _gdn_chunk_algebra(k, g_row, b_row, *, chunk: int, block: int):
    """What forward and backward both form of one pair of chunk-heads:
    k (2C, dk), the cumulative log-decay and beta as rows (1, 2C) ->
    ((row numbers, column numbers, which entries lie inside one chunk,
    which of them strictly below the diagonal), (gamma, beta and the
    chunk's last gamma as columns (2C, 1)), the decay matrix, K K^T,
    T = (I + A)^-1), the matrices float32."""
    rows = k.shape[0]
    r = lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    c = lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    same = (r // chunk) == (c // chunk)
    lower, strict = same & (c <= r), same & (c < r)
    eye = r == c

    def column(row):
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    g_col, b_col = column(g_row), column(b_row)
    g_last = jnp.sum(jnp.where(c == r // chunk * chunk + chunk - 1, g_row,
                               0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(lower, g_col - g_row, -jnp.inf))
    kk = _gdn_dot(k, k, 1, 1)
    a = jnp.where(strict, b_col * decay * kk, 0.0)
    t = _gdn_inverse(a, r, c, chunk, block)
    return (r, c, same, strict), (g_col, b_col, g_last), decay, kk, t


def _gdn_walk(pairs: int, pair):
    """`pair(p)` for every pair of a grid step's chunk-heads, as many an
    iteration as _GDN_PAIRS_IN_FLIGHT allows: pairs are independent, and
    one pair's chain of small products leaves the matrix unit waiting."""
    fly = _largest_divisor(pairs, 1, _GDN_PAIRS_IN_FLIGHT)

    def step(i, carry):
        for j in range(fly):
            pair(i * fly + j)
        return carry

    lax.fori_loop(0, pairs // fly, step, 0)


def _gdn_chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u0_ref,
                          kd_ref, attn_ref, qg_ref, *, chunk: int,
                          block: int):
    """Grid (blocks of chunk-heads,); a step walks its pairs of
    chunk-heads one after the other."""
    op = w_ref.dtype

    def pair(p):
        q, k, v = q_ref[p], k_ref[p], v_ref[p]
        at = pl.ds(p, 1)
        _, (g_col, b_col, g_last), decay, _, t = _gdn_chunk_algebra(
            k, g_ref[at, :], b_ref[at, :], chunk=chunk, block=block)
        t = t.astype(op)
        grow = jnp.exp(g_col)
        kf = k.astype(jnp.float32)
        w_ref[p] = _gdn_dot(t, (b_col * grow * kf).astype(op)).astype(op)
        u0_ref[p] = _gdn_dot(
            t, (b_col * v.astype(jnp.float32)).astype(op)).astype(op)
        kd_ref[p] = (jnp.exp(g_last - g_col) * kf).astype(op)
        attn = decay * _gdn_dot(q, k, 1, 1)
        # the two chunks' squares leave one under the other, as they are
        # laid out: off the diagonal the blocks are zero
        attn_ref[p] = sum(attn[:, i * chunk:(i + 1) * chunk]
                          for i in range(attn.shape[1] // chunk)).astype(op)
        qg_ref[p] = (grow * q.astype(jnp.float32)).astype(op)

    _gdn_walk(q_ref.shape[0], pair)


def _gdn_chunk_bwd_kernel(q_ref, k_ref, v_ref, dw_ref, du0_ref, dkd_ref,
                          dattn_ref, dqg_ref, g_ref, b_ref, dq_ref, dk_ref,
                          dv_ref, dg_ref, db_ref, *, chunk: int, block: int):
    """The stage's transpose, a pair of chunk-heads at a time: T and the
    decay matrix formed again, d A = -T^T (d T) T^T inside."""
    op = dq_ref.dtype
    f32 = jnp.float32

    def pair(p):
        q, k, v = q_ref[p], k_ref[p], v_ref[p]
        at = pl.ds(p, 1)
        (r, c, same, strict), (g_col, b_col, g_last), decay, kk, t = \
            _gdn_chunk_algebra(k, g_ref[at, :], b_ref[at, :], chunk=chunk,
                               block=block)
        qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
        grow, tail = jnp.exp(g_col), jnp.exp(g_last - g_col)
        dw, du0 = dw_ref[p], du0_ref[p]
        d_kd, d_qg = dkd_ref[p].astype(f32), dqg_ref[p].astype(f32)
        # w = T kb, u0 = T vb
        t_op = t.astype(op)
        d_t = _gdn_dot(dw, (b_col * grow * kf).astype(op), 1, 1) \
            + _gdn_dot(du0, (b_col * vf).astype(op), 1, 1)
        d_kb, d_vb = _gdn_dot(t_op, dw, 0, 0), _gdn_dot(t_op, du0, 0, 0)
        # T = (I + A)^-1, A = beta decay K K^T strictly below the diagonal
        m = jnp.where(strict, -_gdn_dot(_gdn_dot(t, d_t, 0, 0), t, 1, 1),
                      0.0)
        x = m * decay * kk
        d_kk = (m * (b_col * decay)).astype(op)
        # attn = decay Q K^T: its cotangent arrives one chunk under the
        # other and is laid side by side; the decay's zeros mask the rest
        d_attn = jnp.concatenate(
            [dattn_ref[p].astype(f32)] * (decay.shape[1] // chunk), axis=1)
        qk = _gdn_dot(q, k, 1, 1)
        d_qk = (d_attn * decay).astype(op)
        d_decay = b_col * x + d_attn * (decay * qk)     # times the decay
        s_kb = jnp.sum(d_kb * kf, axis=1, keepdims=True)
        s_kd = tail * jnp.sum(d_kd * kf, axis=1, keepdims=True)
        s_qg = jnp.sum(d_qg * qf, axis=1, keepdims=True)
        dq_ref[p] = (grow * d_qg + _gdn_dot(d_qk, k)).astype(op)
        dk_ref[p] = (b_col * grow * d_kb + tail * d_kd
                     + _gdn_dot(d_kk, k) + _gdn_dot(d_kk, k, 0, 0)
                     + _gdn_dot(d_qk, q, 0, 0)).astype(op)
        dv_ref[p] = (b_col * d_vb).astype(op)
        d_beta = jnp.sum(x, axis=1, keepdims=True) + grow * s_kb \
            + jnp.sum(d_vb * vf, axis=1, keepdims=True)
        d_gamma = jnp.sum(d_decay, axis=1, keepdims=True) \
            + grow * (b_col * s_kb + s_qg) - s_kd
        eye = r == c
        # columns back to rows; a chunk's last token also takes the sum
        # of what its tail decays carried
        db_ref[at, :] = jnp.sum(jnp.where(eye, d_beta, 0.0), axis=0,
                                keepdims=True)
        dg_ref[at, :] = jnp.sum(
            jnp.where(eye, d_gamma, 0.0)
            + jnp.where(same & (c % chunk == chunk - 1), s_kd, 0.0)
            - d_decay, axis=0, keepdims=True)

    _gdn_walk(q_ref.shape[0], pair)


def _gdn_call(kernel, mats, vecs, outs, *, chunk: int, inverse_block: int,
              interpret: bool):
    """One of the two kernels over (B, C, d) arrays `mats` and (B, C)
    float32 rows `vecs`; `outs`: a (trailing shape, dtype) a result, (C, d)
    for an array a chunk-head and (C,) for a row."""
    b = mats[0].shape[0]
    pack = _LANE // chunk
    k, v = mats[1:3]
    g = gdn_view(b, chunk, k.shape[-1], v.shape[-1], vecs[0].dtype, v.dtype)
    if not g:
        raise ValueError(
            f"the Gated DeltaNet kernels take no {b} chunk-heads of {chunk} "
            f"tokens, keys of {k.shape[-1]}, values of {v.shape[-1]} in "
            f"{v.dtype} under {vecs[0].dtype} decays (pallas_kernels."
            "gdn_view): ops.linear_attention traces the XLA form for such "
            "a shape")

    def paired(shape):
        """(B, C, ...) -> (B / 2, 2 C, ...): two chunk-heads' rows one
        under the other, a bitcast; a row of scalars likewise."""
        return (b // pack, pack * shape[1]) + tuple(shape[2:])

    def spec(shape):
        shape = paired(shape)
        return _vmem((g // pack,) + shape[1:],
                     lambda i: (i,) + (0,) * (len(shape) - 1))

    args = [a.reshape(paired(a.shape)) for a in (*mats, *vecs)]
    shapes = [(b,) + tuple(s) for s, _ in outs]
    res = pl.pallas_call(
        functools.partial(kernel, chunk=chunk, block=inverse_block),
        out_shape=tuple(jax.ShapeDtypeStruct(paired(s), dt)
                        for s, (_, dt) in zip(shapes, outs)),
        grid=(b // g,),
        in_specs=[spec(a.shape) for a in (*mats, *vecs)],
        out_specs=tuple(spec(s) for s in shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_GDN_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAMES[kernel.__name__],
    )(*args)
    return tuple(a.reshape(s) for a, s in zip(res, shapes))


@_kernel_jit
def gdn_chunk_forward_pallas(q, k, v, gamma, beta, *, inverse_block: int,
                             interpret: bool = False):
    """q and k (B, C, dk), v (B, C, dv) of B chunk-heads in the products'
    dtype, gamma (the cumulative log-decay inside a chunk) and beta (B, C)
    float32 -> (w (B, C, dk), u0 (B, C, dv), kd (B, C, dk), attn (B, C, C),
    qg (B, C, dk)) in v's dtype: `linear_attention`'s `operands` but for
    what is a function of gamma's last column alone."""
    _, c, dk = q.shape
    dv, op = v.shape[-1], v.dtype
    return _gdn_call(
        _gdn_chunk_fwd_kernel, (q, k, v), (gamma, beta),
        [((c, dk), op), ((c, dv), op), ((c, dk), op), ((c, c), op),
         ((c, dk), op)],
        chunk=c, inverse_block=inverse_block, interpret=interpret)


@_kernel_jit
def gdn_chunk_backward_pallas(q, k, v, gamma, beta, dw, du0, dkd, dattn, dqg,
                              *, inverse_block: int, interpret: bool = False):
    """The inputs of `gdn_chunk_forward_pallas` and its five results'
    cotangents -> the cotangents of (q, k, v) in their dtype and of
    (gamma, beta) float32."""
    _, c, dk = q.shape
    dv = v.shape[-1]
    return _gdn_call(
        _gdn_chunk_bwd_kernel, (q, k, v, dw, du0, dkd, dattn, dqg),
        (gamma, beta),
        [((c, dk), q.dtype), ((c, dk), k.dtype), ((c, dv), v.dtype),
         ((c,), jnp.float32), ((c,), jnp.float32)],
        chunk=c, inverse_block=inverse_block, interpret=interpret)
