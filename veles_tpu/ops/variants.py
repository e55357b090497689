"""Lowering-variant registry: every tunable op's candidate lowerings.

The largest early gains came from swapping op lowerings — banded-matmul
LRN, the s2d conv stem — yet each variant was a hand-flipped class
attribute exercised only by one-off scripts when a chip happened to be
up. This module makes the choice systematic, the same way VELES solved
kernel selection with its per-backend unit registry (SURVEY.md §4) and
TorchInductor solves it with autotuned lowering choice plus a persistent
cache (Ansel et al., PAPERS.md):

- every tunable op registers its NAMED candidate lowerings here, each
  carrying an equivalence contract against `ops.reference` (enforced by
  tests/test_variants_autotune.py: fwd AND bwd, Pallas via interpret
  mode on CPU);
- units consult `resolve()` at fused-step trace time; nothing else
  chooses a lowering (the class attributes that once did are gone — a
  per-instance constructor argument such as `MaxPooling(lowering=...)`
  is the unit's `variant_override`);
- the autotuner (`ops.autotune`, `tools/autotune.py`, `--autotune`)
  times candidates in-graph and persists the winner; `selection_table()`
  goes into the supervisor's exit report and the benchmark prints the
  step's `variant_table()` as its `variants:` line, so a measured number
  always names the lowerings that produced it.

Adding a variant is ONE `register()` call (see docs/AUTOTUNE.md) — it is
then automatically equivalence-tested, tunable, cacheable and reported.

This module imports no jax at module scope on purpose: the resilience
supervisor (import-light by design) reads `selection_table()` for its
exit report.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "Variant", "register_op", "register", "ops", "variants_for", "get",
    "has", "select", "selected", "effective", "clear_selection",
    "selection_table", "resolve", "pallas_ok", "kernels_ok",
    "pallas_interpret",
    "grad_reduce_apply", "grad_reduce_config",
    "grad_reduce_geometry", "grad_reduce_local_request",
    "grad_reduce_resid_len", "grad_reduce_bytes", "q8_encode",
    "q8_decode", "GRAD_REDUCE_LOCAL_ENV", "serve_forward_apply",
    "serve_forward_config", "serve_prepare_params", "serve_param_bytes",
]


@dataclass(frozen=True)
class Variant:
    """One candidate lowering for a tunable op.

    `apply` is the canonical callable for the op's documented signature
    (see the per-op sections below); `pallas` marks lowerings that need a
    compiled Pallas path (gated by `pallas_ok()`, interpret mode on CPU);
    `tunable=False` marks resolution-only pseudo-variants (e.g. dropout
    "auto") the autotuner must not time as candidates; `generated=True`
    marks template-materialized candidates (ops.templates) — search-
    produced points whose name encodes their config."""

    op: str
    name: str
    apply: Callable[..., Any]
    pallas: bool = False
    tunable: bool = True
    generated: bool = False
    #: stateful lowerings carry a per-shard residual through the caller's
    #: state (grad_reduce error feedback: apply(flat, axis, resid) ->
    #: (slice, new_resid)); consumers that can't host the slot must not
    #: select one
    stateful: bool = False
    doc: str = ""


@dataclass
class _OpSpec:
    op: str
    default: str
    fallback: str           # non-pallas stand-in when pallas is unusable
    doc: str = ""
    variants: Dict[str, Variant] = field(default_factory=dict)


_OPS: Dict[str, _OpSpec] = {}
#: global op -> variant-name selection (autotuner / tools write it)
_selection: Dict[str, str] = {}
_lock = threading.Lock()


def register_op(op: str, default: str, fallback: Optional[str] = None,
                doc: str = "") -> None:
    _OPS[op] = _OpSpec(op=op, default=default,
                       fallback=fallback or default, doc=doc)


def register(variant: Variant) -> Variant:
    spec = _OPS.get(variant.op)
    if spec is None:
        raise KeyError(f"unknown tunable op {variant.op!r}; register_op "
                       f"first (known: {sorted(_OPS)})")
    spec.variants[variant.name] = variant
    return variant


def ops() -> List[str]:
    return sorted(_OPS)


def variants_for(op: str) -> List[Variant]:
    return list(_spec(op).variants.values())


def _spec(op: str) -> _OpSpec:
    try:
        return _OPS[op]
    except KeyError:
        raise KeyError(f"unknown tunable op {op!r} "
                       f"(registered: {sorted(_OPS)})") from None


def _lookup(op: str, name: Any) -> Optional[Variant]:
    """Registered variant, or a template point materialized on demand —
    the path a persisted generated-winner name takes in a fresh process
    (ops.templates names are parseable back into their config)."""
    spec = _spec(op)
    v = spec.variants.get(name)
    if v is None and isinstance(name, str) and "[" in name:
        from veles_tpu.ops import templates
        v = templates.materialize(op, name)
    return v


def get(op: str, name: str) -> Variant:
    v = _lookup(op, name)
    if v is None:
        raise KeyError(
            f"unknown variant {name!r} for op {op!r} "
            f"(registered: {sorted(_spec(op).variants)})")
    return v


def has(op: str, name: Any) -> bool:
    return op in _OPS and _lookup(op, name) is not None


def select(op: str, name: str) -> None:
    """Pin op's lowering globally (validates both names)."""
    get(op, name)
    with _lock:
        _selection[op] = name


def selected(op: str) -> Optional[str]:
    return _selection.get(op)


def effective(op: str) -> str:
    """The variant name resolve() would use absent per-unit overrides."""
    return _selection.get(op, _spec(op).default)


def clear_selection(op: Optional[str] = None) -> None:
    with _lock:
        if op is None:
            _selection.clear()
        else:
            _selection.pop(op, None)


def selection_table(include_defaults: bool = False) -> Dict[str, str]:
    """{op: variant-name} snapshot — what a record should report. With
    `include_defaults`, ops without an explicit selection report their
    default, so the table always names every tunable op."""
    if not include_defaults:
        return dict(_selection)
    return {op: effective(op) for op in _OPS}


def pallas_ok() -> bool:
    """Can a Pallas kernel run here? True on a TPU backend
    (`pallas_kernels.available`, THE platform question: a backend that
    fails to initialise raises there, never "no"), or anywhere while
    interpret mode is asked for (`pallas_interpret()`)."""
    from veles_tpu.ops import pallas_kernels as pk
    return pk._interpret() or pk.available()


def kernels_ok(unit: Any = None) -> bool:
    """Whether what `unit` traces NOW may be a Pallas kernel: the unit
    allows it (`allow_pallas`, the fused step's word: cleared under GSPMD
    auto-partitioning, which cannot partition a pallas_call) and the
    platform runs it (`pallas_ok`). Every kernel of the package asks
    this, through `resolve` or by itself, and then its own view of the
    shape (`pallas_kernels.*_view`) where it is called: the XLA form
    otherwise."""
    return bool(getattr(unit, "allow_pallas", True)) and pallas_ok()


@contextlib.contextmanager
def pallas_interpret():
    """Resolve AND run Pallas kernels in interpret mode: the CPU
    autotune / tier-1-test path. It sets the one switch there is,
    `pallas_kernels._FORCE_INTERPRET`."""
    from veles_tpu.ops import pallas_kernels as pk
    prev, pk._FORCE_INTERPRET = pk._FORCE_INTERPRET, True
    try:
        yield
    finally:
        pk._FORCE_INTERPRET = prev


def resolve(op: str, unit: Any = None) -> Variant:
    """The variant a unit must trace NOW. Precedence:
    1. the unit's explicit per-instance `variant_override` (constructor
       knobs like MaxPooling(lowering=...));
    2. the global selection (autotuner cache / tools);
    3. the op's registered default.
    A Pallas variant is swapped for the op's non-pallas fallback where
    `kernels_ok(unit)` says no, logged once at WARNING when the variant
    was explicitly selected: the unit's `allow_pallas` is cleared
    (FusedTrainStep under GSPMD auto-partitioning), or the initialised
    backend is not a TPU and interpret mode was not asked for. On a TPU
    the selected variant is what traces: a kernel the compiler refuses is
    the compiler's error, never a quiet fallback.
    """
    spec = _spec(op)
    name = getattr(unit, "variant_override", None) if unit is not None \
        else None
    if name is None:
        name = _selection.get(op, spec.default)
    v = get(op, name)
    if not v.pallas or kernels_ok(unit):
        return v
    reason = ("the unit cleared allow_pallas (GSPMD auto-partitioning)"
              if not getattr(unit, "allow_pallas", True) else
              "the backend is not a TPU and interpret mode was not "
              "requested")
    if name != spec.default and (op, name, reason) not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add((op, name, reason))
        import logging
        logging.getLogger("veles.variants").warning(
            "%s: selected pallas variant %r not traced — %s; tracing "
            "fallback %r", op, name, reason, spec.fallback)
    return get(op, spec.fallback)


#: (op, variant, reason) fallbacks already warned about (log once)
_FALLBACK_WARNED: set = set()


# ===========================================================================
# Registered ops. apply() bodies lazy-import jax-bearing modules so this
# module stays importable from jax-free processes (resilience supervisor).
# ===========================================================================

# -- LRN forward+backward (one op: fwd and bwd ride one custom_vjp) ---------
#    apply(x, *, k, alpha, beta, n) -> y; differentiable.

def _lrn_banded(x, *, k, alpha, beta, n):
    from veles_tpu.ops import xla as ox
    return ox.lrn_forward(x, k, alpha, beta, n)


def _lrn_pallas(x, *, k, alpha, beta, n):
    from veles_tpu.ops import pallas_kernels as pk
    return pk.lrn_pallas(x, k, alpha, beta, n)


register_op(
    "lrn", default="pallas_one_pass", fallback="banded_matmul",
    doc="AlexNet across-channel LRN, forward + custom-VJP backward "
        "(a third of the AlexNet step with its pooling; off a TPU and "
        "under GSPMD the default resolves to banded_matmul)")
register(Variant("lrn", "banded_matmul", _lrn_banded,
                 doc="XLA banded-matmul window sum; bwd recomputes s/d"))
register(Variant("lrn", "pallas_one_pass", _lrn_pallas, pallas=True,
                 doc="one streaming Pallas pass each way in the layout the "
                     "convs emit (batch or channels in lanes by the shape; "
                     "any other shape traces banded_matmul)"))


# -- hyper-connection around a sub-layer (ISSUE 34) -------------------------
#    apply(p, x, f, n, *, iters, eps, clamp, norm_eps) -> (streams, f's
#    extra); differentiable. x is (T, n*C), `f` maps the read (T, C) to
#    ((T, C), anything). No autotuner times this op: the platform
#    (`resolve`) and the shape (`pallas_kernels.hc_view`) decide, in
#    `znicz/lm.py::BlockSpec.lowerings`.

def _hc_xla(p, x, f, n, **kw):
    from veles_tpu.ops import lm
    return lm.hyper_connection(lm.hc_pre_xla, lm.hc_post_xla, p, x, f, n,
                               **kw)


def _hc_pallas(p, x, f, n, **kw):
    from veles_tpu.ops import lm
    return lm.hyper_connection(lm.hc_pre_pallas, lm.hc_post_pallas, p, x,
                               f, n, **kw)


register_op(
    "hc", default="pallas_one_pass", fallback="xla",
    doc="one Sinkhorn-mixed hyper-connection around a sub-layer "
        "(ops/lm.py): the maps, the read and the write over the streams "
        "(T, n*C), memory-bound (28 % of the xing4 step as XLA traced it; "
        "off a TPU and under GSPMD the default resolves to xla)")
register(Variant("hc", "xla", _hc_xla,
                 doc="hc_maps / hc_read / hc_write as jax.numpy under "
                     "autodiff"))
register(Variant("hc", "pallas_one_pass", _hc_pallas, pallas=True,
                 doc="two custom_vjp functions over four kernels tiled "
                     "over tokens, x read once a side and direction, each "
                     "kernel jitted once for all sites (128 | C and a "
                     "token tile | T; a block traces xla for any other "
                     "shape)"))


# -- attention over the keys an indexer selects (ISSUE 35) -------------------
#    apply(p, h, **kw) -> (y, {index_loss, pairs_selected, selected});
#    differentiable. h is (N, S, C). No autotuner times this op: the
#    platform (`resolve`) and the shape (`pallas_kernels.dsa_view`) decide,
#    in `znicz/lm.py::BlockSpec.lowerings`.

def _dsa_xla(p, h, **kw):
    from veles_tpu.ops import attention as oa
    return oa.indexed_attention(p, h, lowering="xla", **kw)


def _dsa_pallas(p, h, **kw):
    from veles_tpu.ops import attention as oa
    from veles_tpu.ops import pallas_kernels as pk
    return oa.indexed_attention(p, h, lowering="pallas_flash",
                                interpret=pk._interpret(), **kw)


register_op(
    "dsa", default="pallas_flash", fallback="xla",
    doc="grouped-query attention over the keys a learned indexer selects "
        "(ops/attention.py): index scores, threshold search, attention "
        "over the selection, index loss (off a TPU and under GSPMD the "
        "default resolves to xla)")
register(Variant("dsa", "xla", _dsa_xla,
                 doc="every causal pair of a block of queries scored and "
                     "masked, bands of keys, one lax.map a band; the "
                     "scores pass through HBM"))
register(Variant("dsa", "pallas_flash", _dsa_pallas, pallas=True,
                 doc="the main attention as four flash kernels over the "
                     "int8 selection, each jitted once for all sites; "
                     "indexer, search and index loss stay XLA (128 | S and "
                     "128 | head size; a block traces xla for any other "
                     "shape)"))


# -- max pooling (fused-step lowering; the knob is the BACKWARD shape) ------
#    apply(x, ksize, stride, use_abs) -> y; differentiable.

def _maxpool_reduce_window(x, ksize, stride, use_abs):
    from veles_tpu.ops import xla as ox
    if use_abs:
        # the custom-comparator reduce_window has no reverse-mode rule;
        # the patches/argmax formulation differentiates (gather vjp)
        return ox.maxpool_forward_with_idx(x, ksize, stride,
                                           use_abs=True)[0]
    return ox.maxpool_forward(x, ksize, stride, False)


def _maxpool_slices(x, ksize, stride, use_abs):
    from veles_tpu.ops import xla as ox
    return ox.maxpool_forward_slices(x, ksize, stride, use_abs)


register_op(
    "maxpool", default="reduce_window",
    doc="max/maxabs pooling in the fused step; the variants differ in "
        "what the BACKWARD lowers to")
register(Variant("maxpool", "reduce_window", _maxpool_reduce_window,
                 doc="lax.reduce_window; backward = select_and_scatter"))
register(Variant("maxpool", "slices", _maxpool_slices,
                 doc="max-fold over ky*kx shifted strided slices; "
                     "backward = selects + zero-pads (fusion-friendly)"))


# -- lrn_maxpool: the searched (lrn, maxpool) CROSS-OP fusion ---------------
#    apply(x, *, k, alpha, beta, n, ksize, stride) -> pooled output;
#    differentiable. A PURE fusion op (ISSUE 13): "composed" is the
#    incumbent (identical math to the two units tracing separately);
#    the generated ``fused[rt=..,io=..,fuse=..]`` points come from
#    ops.templates, every one gated on the COMPOSED ops.reference
#    golden. When a fused winner is selected, FusedTrainStep lets the
#    normalization unit claim its pooling successor's work (the pooling
#    unit becomes a pass-through for that trace) — see
#    parallel/fused.py fusion_pairs().

def _lrn_maxpool_composed(x, *, k, alpha, beta, n, ksize, stride):
    from veles_tpu.ops import xla as ox
    y = ox.lrn_forward(x, k, alpha, beta, n)
    return ox.maxpool_forward(y, tuple(ksize), tuple(stride), False)


register_op(
    "lrn_maxpool", default="composed", fallback="composed",
    doc="searched cross-op fusion of an adjacent (lrn, maxpool) unit "
        "pair: both ops stream the same activation rows, so the fused "
        "Pallas point does LRN then pooling in ONE VMEM pass "
        "(ops/templates.py)")
register(Variant("lrn_maxpool", "composed", _lrn_maxpool_composed,
                 doc="the unfused incumbent: member lowerings traced "
                     "separately (XLA LRN + reduce_window pooling)"))


# -- conv stem: strided thin-channel entry conv -----------------------------
#    apply(x, w, b, stride, padding, activation) -> y; differentiable.
#    Units with s2d="auto" consult resolve("conv_stem") for the decision;
#    explicit s2d="on"/"off" stays a per-layer override.

def _conv_direct(x, w, b, stride, padding, activation):
    from veles_tpu.ops import xla as ox
    return ox.conv2d_forward(x, w, b, stride, padding, activation,
                             s2d=False)


def _conv_s2d(x, w, b, stride, padding, activation):
    from veles_tpu.ops import xla as ox
    return ox.conv2d_forward(x, w, b, stride, padding, activation,
                             s2d=True)


register_op(
    "conv_stem", default="s2d",
    doc="strided thin-channel (cin<8) entry conv: direct vs the exact "
        "space-to-depth rewrite (r4 on-chip winner, 8656 -> 9377)")
register(Variant("conv_stem", "direct", _conv_direct,
                 doc="plain lax.conv_general_dilated"))
register(Variant("conv_stem", "s2d", _conv_s2d,
                 doc="space-to-depth repack: stride-1 conv on full MXU "
                     "tiles, numerics identical"))


# -- gradient reduce-scatter (the ZeRO update's collective leg) -------------
#    apply(flat_partial, axis_name, resid=None) -> this shard's summed
#    slice; STATEFUL (error-feedback) variants return (slice, new_resid).
#    `flat_partial` is one param leaf's per-shard partial gradient,
#    flattened and zero-padded to a multiple of the axis size
#    (parallel.mesh.zero_flatten); the variant reduce-scatters it over
#    the named data axis so each shard receives only the 1/N slice of
#    the SUMMED gradient it owns under the update-sharding plan
#    (arxiv 2004.13336). Cross-host that exchange rides DCN, where bytes
#    — not FLOPs — bound scaling efficiency, so the family trades
#    gradient bits for wire bytes (EQuARX, arxiv 2506.17615):
#
#    - f32 / bf16: psum_scatter in the wire dtype (exact / bytes ÷2);
#    - int8_block: per-block absmax-scaled int8 codes, the f32 scales
#      riding the SAME all-to-all exchange, dequantize-accumulate in
#      f32 (bytes ÷~4 at blk=256);
#    - int8_ef:   int8_block + error feedback — the quantization
#      residual is carried per shard in the ZeRO flat-vector state (the
#      step's "ef" slot) and added back before the next quantization,
#      so the compression error telescopes instead of accumulating;
#    - hier2:     two-level decomposition over the (hosts x local)
#      factorization of the data axis: ICI-local reduce-scatter in the
#      gradient dtype, then the DCN exchange moves only the 1/n_local
#      slices (DCN bytes ÷n_local) — the CPU 8-device mesh tests it as
#      (hosts=2, local=4) via VELES_GRAD_REDUCE_LOCAL;
#    - the searched family `wire[dt=..,blk=..,ef=..,hier=..]`
#      (ops.templates) composes all four axes; every point is built by
#      the ONE `grad_reduce_apply` below and equivalence-gated against
#      the ops.reference quantization goldens before the budgeted
#      search may time it.
#
#    All collective calls live in THIS module by the velint
#    stray-collective contract. The byte model (`grad_reduce_bytes`)
#    feeds veles_collective_bytes_total; docs/SCALING.md states the
#    per-variant math and the trained-loss tolerances.

GRAD_REDUCE_LOCAL_ENV = "VELES_GRAD_REDUCE_LOCAL"

#: canonical configs of the named (hand-registered) family members —
#: shared by registration, `grad_reduce_config` and the byte model
_GR_NAMED: Dict[str, Dict[str, Any]] = {
    "f32": {"dt": "f32", "blk": 0, "ef": 0, "hier": 0},
    "bf16": {"dt": "bf16", "blk": 0, "ef": 0, "hier": 0},
    "int8_block": {"dt": "int8", "blk": 256, "ef": 0, "hier": 0},
    "int8_ef": {"dt": "int8", "blk": 256, "ef": 1, "hier": 0},
    "hier2": {"dt": "f32", "blk": 0, "ef": 0, "hier": 1},
}


def grad_reduce_local_request(n_shards: int) -> int:
    """The UNCLAMPED ICI-group-size request for the hierarchical
    variants: env VELES_GRAD_REDUCE_LOCAL (explicit geometry — CPU
    tests, odd topologies) or this process's local device count. The
    jaxpr auditor checks an explicit request divides the data axis;
    `grad_reduce_geometry` below clamps a non-dividing request to the
    LARGEST DIVISOR it does not exceed — the traced op then runs that
    different (but always-valid) decomposition, never a crash."""
    import os
    raw = os.environ.get(GRAD_REDUCE_LOCAL_ENV)
    if raw:
        try:
            return int(raw)
        except ValueError:
            return 0
    try:
        import jax
        return jax.local_device_count()
    except Exception:  # noqa: BLE001 — no backend: treat as single-host
        return n_shards


def grad_reduce_geometry(n_shards: int) -> tuple:
    """(n_hosts, n_local): the two-level factorization of the data axis
    the hierarchical variants decompose over. n_local is the request
    clamped to the largest divisor of n_shards it does not exceed, so
    the groups always tile the axis; (1, n) or (n, 1) geometries make
    the hierarchy degenerate and `grad_reduce_apply` falls back to the
    flat exchange."""
    loc = grad_reduce_local_request(n_shards)
    loc = max(1, min(int(loc), n_shards))
    while n_shards % loc:
        loc -= 1
    return n_shards // loc, loc


def grad_reduce_config(name: Any) -> Optional[Dict[str, Any]]:
    """Canonical EFFECTIVE config {dt, blk, ef, hier} for any
    grad_reduce variant name — named incumbents or template-generated
    ``wire[...]`` points; None for foreign names. Error feedback is an
    int8-only mechanism: ef (and blk) canonicalize to 0 for float wire
    dtypes, so two names that trace the same program report the same
    config (bytes, state slots and bench aliasing all read this)."""
    cfg = _GR_NAMED.get(name)
    if cfg is not None:
        cfg = dict(cfg)
    elif isinstance(name, str) and "[" in name:
        from veles_tpu.ops import templates
        for t in templates.templates_for("grad_reduce"):
            parsed = t.parse(name)
            if parsed is not None:
                cfg = dict(parsed)
                break
    if cfg is None:
        return None
    if cfg.get("dt") != "int8":
        cfg["ef"] = 0
        cfg["blk"] = 0
    return cfg


def grad_reduce_resid_len(name: str, padded: int,
                          n_shards: int) -> Optional[int]:
    """Per-shard error-feedback residual length for one (padded,) flat
    leaf under the named variant — None for stateless variants. The
    flat int8+EF exchange quantizes the whole per-shard partial
    (residual = padded elements); the hierarchical one applies EF to
    the DCN leg only, AFTER the ICI reduce-scatter, so its residual is
    the 1/n_local slice. One rule shared by the traced op, the step's
    state allocation and the checkpoint geometry — they can never
    disagree."""
    cfg = grad_reduce_config(name)
    if not cfg or not cfg["ef"]:
        return None
    if cfg["hier"]:
        h, loc = grad_reduce_geometry(n_shards)
        if h > 1 and loc > 1:
            return padded // loc
    return padded


def grad_reduce_bytes(name: str, n_elems: int,
                      n_shards: int) -> Dict[str, Any]:
    """Modeled per-device egress bytes per step of the grad_reduce
    exchange (plus the param all-gather leg for context), split by link
    leg under the (hosts x local) geometry. The model counts gradient
    payload a device must move to peers: off-host destinations are DCN,
    on-host are ICI; int8 wire adds the per-block f32 scale overhead
    (4/blk bytes per element). This is the producer behind
    veles_collective_bytes_total (docs/SCALING.md states the math) —
    modeled from the collective's algorithm and the plan sizes, since
    XLA exposes no per-collective wire counters."""
    cfg = grad_reduce_config(name) or dict(_GR_NAMED["f32"])
    h, loc = grad_reduce_geometry(n_shards)
    item = {"f32": 4.0, "bf16": 2.0, "int8": 1.0}[cfg["dt"]]
    if cfg["dt"] == "int8" and cfg["blk"]:
        item += 4.0 / cfg["blk"]      # the scales ride the same exchange
    n = n_shards
    if cfg["hier"] and h > 1 and loc > 1:
        # phase 1 (ICI): reduce-scatter within the local group, in the
        # gradient dtype; phase 2 (DCN): only the 1/local slices cross
        ici = n_elems * (loc - 1) / loc * 4.0
        dcn = (n_elems / loc) * (h - 1) / h * item
    else:
        dcn = n_elems * (n - loc) / n * item
        ici = n_elems * (loc - 1) / n * item
    return {"dcn_bytes": int(dcn), "ici_bytes": int(ici),
            "allgather_dcn_bytes": int(n_elems / n * (n - loc) * 4.0),
            "allgather_ici_bytes": int(n_elems / n * (loc - 1) * 4.0),
            "geometry": {"hosts": h, "local": loc},
            "config": cfg}


def q8_encode(x2, blk: int):
    """jax twin of ops.reference.quantize_blockwise over the last axis
    of a 2-D (rows, cols) array, zero-padding cols up to a block
    multiple. Returns (codes int8 (rows, colsp), scales f32
    (rows, colsp//blk)). BITWISE-identical to the numpy golden — the
    grad_reduce equivalence contract asserts it."""
    import jax.numpy as jnp
    rows, cols = x2.shape
    pad = (-cols) % blk
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad)))
    xb = x2.reshape(rows, -1, blk)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(absmax > 0, absmax / jnp.float32(127.0),
                      jnp.float32(1.0))
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q.reshape(rows, -1), scale


def q8_decode(q, scale, blk: int):
    """jax twin of ops.reference.dequantize_blockwise (2-D rows form)."""
    import jax.numpy as jnp
    rows = q.shape[0]
    xb = q.reshape(rows, -1, blk).astype(jnp.float32)
    return (xb * scale[..., None]).reshape(rows, -1)


def _q8_exchange(x, axis_name, blk, resid, groups, local, want_resid):
    """Blockwise-int8 exchange-and-accumulate: quantize each destination
    row (per-block absmax scales), all_to_all the codes AND the scales
    in one pattern (the scale exchange rides the same scatter),
    dequantize and accumulate in f32. `x` is (rows, local) with row j
    bound for exchange-group member j; returns (my summed (local,)
    slice, new residual (rows*local,) or None)."""
    import jax.numpy as jnp  # noqa: F401 — q8 helpers carry the math
    from jax import lax
    if resid is not None:
        x = x + resid.reshape(x.shape)
    q, s = q8_encode(x, blk)
    new_resid = None
    if want_resid:
        new_resid = (x - q8_decode(q, s, blk)[:, :local]).reshape(-1)
    kw = {"axis_index_groups": groups} if groups is not None else {}
    q_r = lax.all_to_all(q, axis_name, 0, 0, tiled=True, **kw)
    s_r = lax.all_to_all(s, axis_name, 0, 0, tiled=True, **kw)
    out = q8_decode(q_r, s_r, blk)[:, :local].sum(axis=0)
    return out, new_resid


def grad_reduce_apply(cfg: Dict[str, Any]) -> Callable[..., Any]:
    """Build the canonical grad_reduce apply for one config point — the
    ONE implementation behind every named incumbent and every generated
    ``wire[...]`` candidate. Stateful (EF) applies ALWAYS return
    (slice, new_resid); resid=None means a zero residual. The closure
    carries its canonical config as ``apply.gr_config`` so the
    equivalence contract can pick per-dtype tolerances without a second
    naming scheme."""
    dt = cfg["dt"]
    blk = int(cfg.get("blk") or 256)
    ef = bool(cfg.get("ef")) and dt == "int8"
    hier = bool(cfg.get("hier"))

    def apply(flat, axis_name, resid=None):
        import jax.numpy as jnp
        from jax import lax

        n = lax.axis_size(axis_name)
        h, loc = grad_reduce_geometry(n)
        two_level = hier and h > 1 and loc > 1
        local = flat.shape[0] // n
        new_resid = None
        if two_level:
            lgroups = [[hh * loc + ll for ll in range(loc)]
                       for hh in range(h)]
            cgroups = [[hh * loc + ll for hh in range(h)]
                       for ll in range(loc)]
            # phase 1 (ICI): reduce-scatter within each host's local
            # group, in the gradient dtype — the row order below lands
            # device (host h, local l) exactly the final slices device
            # index h*loc+l owns, matching the flat scatter's layout
            x = flat.astype(jnp.float32).reshape(h, loc, local) \
                .transpose(1, 0, 2)
            x = lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                 axis_index_groups=lgroups, tiled=True)
            x = x.reshape(h, local)   # per-host partials of my slices
            if dt == "int8":
                out, new_resid = _q8_exchange(
                    x, axis_name, blk, resid if ef else None, cgroups,
                    local, ef)
            else:
                w = x.astype(jnp.bfloat16) if dt == "bf16" else x
                out = lax.psum_scatter(
                    w, axis_name, scatter_dimension=0,
                    axis_index_groups=cgroups, tiled=True
                ).reshape(-1).astype(jnp.float32)
        elif dt == "int8":
            x = flat.astype(jnp.float32).reshape(n, local)
            out, new_resid = _q8_exchange(
                x, axis_name, blk, resid if ef else None, None, local,
                ef)
        elif dt == "bf16":
            out = lax.psum_scatter(
                flat.astype(jnp.bfloat16), axis_name,
                scatter_dimension=0, tiled=True).astype(jnp.float32)
        else:
            out = lax.psum_scatter(flat, axis_name,
                                   scatter_dimension=0, tiled=True)
        out = out.astype(flat.dtype)
        return (out, new_resid) if ef else out

    apply.gr_config = {"dt": dt, "blk": blk if dt == "int8" else 0,
                       "ef": int(ef), "hier": int(hier)}
    return apply


register_op(
    "grad_reduce", default="f32",
    doc="ZeRO weight-update reduce-scatter of per-shard partial "
        "gradients over the data axis (cross-host this is DCN-bound: "
        "the compressed/hierarchical variants trade gradient bits and "
        "exchange topology for DCN wire bytes — EQuARX, arxiv "
        "2506.17615)")
register(Variant("grad_reduce", "f32",
                 grad_reduce_apply(_GR_NAMED["f32"]),
                 doc="exact: psum_scatter in the gradient dtype"))
register(Variant("grad_reduce", "bf16",
                 grad_reduce_apply(_GR_NAMED["bf16"]),
                 doc="wire dtype bf16 (bytes ÷2), accumulate + store "
                     "back in the gradient dtype; equivalence contract "
                     "at the trained-loss tolerance stated in "
                     "docs/SCALING.md"))
register(Variant("grad_reduce", "int8_block",
                 grad_reduce_apply(_GR_NAMED["int8_block"]),
                 doc="EQuARX-style blockwise-scaled int8 exchange "
                     "(blk=256): codes + per-block f32 scales ride one "
                     "all_to_all, dequantize-accumulate in f32 — wire "
                     "bytes ~0.26x the f32 scatter"))
register(Variant("grad_reduce", "int8_ef",
                 grad_reduce_apply(_GR_NAMED["int8_ef"]), stateful=True,
                 doc="int8_block + error feedback: the quantization "
                     "residual carries in the ZeRO flat-vector state "
                     "(the step's 'ef' slot) and is added back before "
                     "the next quantization, telescoping the "
                     "compression error"))
register(Variant("grad_reduce", "hier2",
                 grad_reduce_apply(_GR_NAMED["hier2"]),
                 doc="two-level (hosts x local) decomposition: "
                     "ICI-local reduce-scatter, then the DCN exchange "
                     "moves only the 1/n_local slices (DCN bytes "
                     "÷n_local); exact f32 math, trajectory-equal to "
                     "the flat scatter at rtol 1e-5"))


# -- blocked flash attention (intra-chip tile loop) -------------------------
#    apply(q, k, v, scale=None, causal=False) -> (B, S, H, Dv);
#    differentiable (the pallas variants are custom-VJP kernel pairs, in
#    the operands' dtype, values of a width of their own; `scope` names
#    the scope their backward stands under).
#    MultiHeadAttention consults resolve("flash_attn") on its local path
#    when the flash gate says long-S beats the einsum; generated
#    candidates over blk_q x blk_k x kv_order come from ops.templates.
#    Latent and gated attention (`znicz/lm.py::BlockSpec.lowerings`) run
#    their core through the resolved kernels where
#    `pallas_kernels.flash_view` admits the shape, and their own blocked
#    XLA form otherwise.

def _flash_xla_mha(q, k, v, scale=None, causal=False):
    from veles_tpu.ops import attention as oa
    return oa.mha_forward(q, k, v, scale=scale, causal=causal)


def _flash_pallas(q, k, v, scale=None, causal=False, scope=None):
    from veles_tpu.ops import pallas_kernels as pk
    return pk.flash_attention_pallas(q, k, v, scale=scale, causal=causal,
                                     scope=scope)


register_op(
    "flash_attn", default="pallas", fallback="xla_mha",
    doc="intra-chip blocked attention for long-S local heads (2.3x the "
        "XLA einsum at S=16384 on v5e); the generated candidates search "
        "blk_q/blk_k/KV-stream order")
register(Variant("flash_attn", "xla_mha", _flash_xla_mha,
                 doc="the einsum golden model (ops.attention.mha_forward"
                     "); right for short S — O(S^2) score matrix"))
register(Variant("flash_attn", "pallas", _flash_pallas, pallas=True,
                 doc="hand-written incumbent: blk 512/1024, forward KV "
                     "order (= templates seed); each of its three "
                     "kernels jitted once for all sites"))


# -- fused SGD weight update (the step's optimizer leg) ---------------------
#    apply(params, grads, vel, cfg, lr_scale=1.0, mults=None) ->
#    (new_params, new_vel), one LAYER pytree at a time (the fused step
#    resolves this per layer in _apply_update; ZeRO keeps its own
#    slice-wise path). Generated pallas candidates block the flattened
#    (rows, 128) update grid by rows (ops.templates).

def _sgd_xla_tree(params, grads, vel, cfg, lr_scale=1.0, mults=None):
    from veles_tpu.ops import optim
    return optim.sgd_update(params, grads, vel, cfg, lr_scale=lr_scale,
                            mults=mults)


register_op(
    "sgd_update", default="xla_tree", fallback="xla_tree",
    doc="fused SGD+momentum+weight-decay update; XLA fuses the tree "
        "rule into the backward, the pallas candidates trade that for "
        "one explicit VMEM pass over 3 buffers with searched row "
        "blocking")
register(Variant("sgd_update", "xla_tree", _sgd_xla_tree,
                 doc="per-leaf jnp rule (ops.optim.sgd_update); fuses "
                     "into the compiled step"))


# -- quantized serving forward (ISSUE 15) -----------------------------------
#    apply(prepared, x, forward, shapes=None) -> f32 output.
#    `forward` is the caller's dense forward ((params, x) -> out — the
#    serving tier passes FusedTrainStep._forward's local trace);
#    `prepared` is the param pytree AFTER this variant's host-side wire
#    transform (`serve_prepare_params`), `shapes` the matching pytree of
#    original leaf shapes (static — needed to undo the int8 padding).
#    The EQuARX-era registry discipline (arxiv 2506.17615) applied to
#    serving: a low-byte serving path is only ever a ledger-gated CONFIG
#    POINT behind the ONE `serve_forward_apply` builder — never a fork
#    of the forward. Equivalence contract: templates._serve_contract
#    runs every variant against ops.reference.serve_forward_mlp with the
#    reference quantizers supplying the golden weight transform
#    (ints BITWISE, forward within per-wire tolerance); the serving tier
#    additionally refuses to SERVE a non-f32 variant without a passing
#    ledger record AND probes it against the f32 forward of the REAL
#    model at startup (veles_tpu/serving.py).
#
#    - f32:  identity wire — the reference point;
#    - bf16: params stored and computed in bfloat16 (model bytes /2),
#      activations cast at entry, output restored to f32;
#    - int8: weight-only — >=2-D float leaves with a full block of
#      columns stored as per-block absmax int8 codes + f32 scales
#      (ops.reference.serve_quantize_weight; model bytes ~/4),
#      dequantized to f32 in-trace so XLA fuses the dequant into the
#      matmul's weight read; 1-D leaves (biases) and sub-block-width
#      leaves stay f32 (negligible bytes / the pad would inflate them
#      — see _serve_quantizable).

_SERVE_NAMED: Dict[str, Dict[str, Any]] = {
    "f32": {"wire": "f32", "blk": 0},
    "bf16": {"wire": "bf16", "blk": 0},
    "int8": {"wire": "int8", "blk": 64},
}


def serve_forward_config(name: Any) -> Optional[Dict[str, Any]]:
    """Canonical config {wire, blk} for a serve_forward variant name
    (None for foreign names)."""
    cfg = _SERVE_NAMED.get(name)
    return dict(cfg) if cfg is not None else None


def _serve_quantizable(a, blk: int) -> bool:
    """int8-wire eligibility: >=2-D float leaves whose last axis holds
    at least one full block — a narrower leaf would zero-PAD up to the
    block and come out LARGER on the wire than its f32 form (measured:
    a (10, 16) weight ballooned 640 B of codes from 640 B of f32).
    Ineligible leaves stay f32; on real layer widths (>= blk) the wire
    is ~bytes/4."""
    import numpy as np
    arr = np.asarray(a)
    return (arr.ndim >= 2 and arr.shape[-1] >= blk
            and np.issubdtype(arr.dtype, np.floating))


def serve_prepare_params(name: str, params):
    """HOST-side wire transform of a (tuple-of-dicts) f32 param pytree
    into `name`'s serving format. Returns (prepared, shapes): int8
    leaves become {"q": codes, "s": scales} dicts built by the
    ops.reference quantizer (the codes ARE the golden — one
    quantization rule for collectives and serving), bf16 leaves are
    cast, f32 passes through; `shapes` records each original leaf shape
    (static metadata the traced dequantize needs to undo padding)."""
    import numpy as np
    cfg = _SERVE_NAMED[name]
    prepared, shapes = [], []
    for layer in params:
        pl: Dict[str, Any] = {}
        sl: Dict[str, tuple] = {}
        for k, a in layer.items():
            arr = np.asarray(a)
            sl[k] = tuple(int(s) for s in arr.shape)
            if cfg["wire"] == "int8" \
                    and _serve_quantizable(arr, cfg["blk"]):
                from veles_tpu.ops import reference
                q, s = reference.serve_quantize_weight(
                    arr.astype(np.float32), cfg["blk"])
                pl[k] = {"q": q, "s": s}
            elif cfg["wire"] == "bf16" \
                    and np.issubdtype(arr.dtype, np.floating):
                import ml_dtypes
                pl[k] = arr.astype(ml_dtypes.bfloat16)
            else:
                pl[k] = arr
        prepared.append(pl)
        shapes.append(sl)
    return tuple(prepared), tuple(shapes)


def serve_param_bytes(prepared) -> int:
    """Wire bytes of a prepared param pytree — the measured form of the
    quantized-serving memory claim (model_info/bench surface it next to
    the f32 model bytes)."""
    import jax
    import numpy as np
    return sum(int(np.asarray(leaf).nbytes)
               for leaf in jax.tree_util.tree_leaves(prepared))


def _serve_restore(cfg, prepared, shapes):
    """Traced inverse of serve_prepare_params: prepared tree -> the
    param tree the dense forward consumes (f32 for int8 wire — the
    dequantize fuses into the weight read; bf16 stays bf16 so the
    forward computes in the wire dtype)."""
    import jax.numpy as jnp
    out = []
    for li, layer in enumerate(prepared):
        d = {}
        for k, v in layer.items():
            if isinstance(v, dict) and "q" in v:
                shp = tuple(shapes[li][k])
                deq = q8_decode(v["q"], v["s"], cfg["blk"])
                d[k] = deq[:, :shp[-1]].reshape(shp)
            else:
                d[k] = v
        out.append(d)
    return tuple(out)


def serve_forward_apply(cfg: Dict[str, Any]) -> Callable[..., Any]:
    """Build the canonical serve_forward apply for one config point —
    the ONE implementation behind every named wire variant. The closure
    carries ``apply.sv_config`` so the equivalence contract can derive
    the matching reference transform without a second naming scheme."""
    cfg = dict(cfg)

    def apply(prepared, x, forward, shapes=None):
        import jax.numpy as jnp
        params = _serve_restore(cfg, prepared, shapes)
        if cfg["wire"] == "bf16":
            x = x.astype(jnp.bfloat16)
        out = forward(params, x)
        return out.astype(jnp.float32)

    apply.sv_config = cfg
    return apply


register_op(
    "serve_forward", default="f32", fallback="f32",
    doc="the serving tier's wire format for model params: f32 "
        "reference, bf16 (bytes /2) and weight-only blockwise int8 "
        "(bytes ~/4) — every low-byte point ledger-gated against the "
        "f32 forward before it may serve (ISSUE 15; the EQuARX "
        "registry discipline, arxiv 2506.17615)")
register(Variant("serve_forward", "f32",
                 serve_forward_apply(_SERVE_NAMED["f32"]),
                 doc="identity wire: the trained f32 params as-is"))
register(Variant("serve_forward", "bf16",
                 serve_forward_apply(_SERVE_NAMED["bf16"]),
                 doc="params stored + computed in bfloat16 (model "
                     "bytes /2), output restored to f32"))
register(Variant("serve_forward", "int8",
                 serve_forward_apply(_SERVE_NAMED["int8"]),
                 doc="weight-only per-block absmax int8 (blk=64, model "
                     "bytes ~/4): codes quantized by the ops.reference "
                     "golden on the host, dequantized in-trace so XLA "
                     "fuses the dequant into the weight read"))


# -- dropout mask RNG -------------------------------------------------------
#    apply(key, shape, drop_prob, dtype) -> pre-scaled mask (0 or 1/keep).
#    Streams differ between impls (counter-based either way); equivalence
#    is structural/statistical, like the reference's xorshift-vs-numpy
#    split. "auto" (default) keeps the device-dependent legacy behavior:
#    hardware RBG on accelerators, threefry on CPU (impl-stable goldens).

def _dropout_auto(key, shape, drop_prob, dtype):
    from veles_tpu.ops import xla as ox
    return ox.make_dropout_mask(key, shape, drop_prob, dtype, impl="auto")


def _dropout_threefry(key, shape, drop_prob, dtype):
    from veles_tpu.ops import xla as ox
    return ox.make_dropout_mask(key, shape, drop_prob, dtype,
                                impl="threefry")


def _dropout_rbg(key, shape, drop_prob, dtype):
    from veles_tpu.ops import xla as ox
    return ox.make_dropout_mask(key, shape, drop_prob, dtype, impl="rbg")


register_op(
    "dropout", default="auto",
    doc="dropout mask bit source (~7% of the AlexNet step under "
        "threefry on v5e; RBG measured 4x less wall-clock per mask)")
register(Variant("dropout", "auto", _dropout_auto, tunable=False,
                 doc="backend-dependent default: rbg on accelerators, "
                     "threefry on CPU"))
register(Variant("dropout", "threefry", _dropout_threefry,
                 doc="jax.random counter-based threefry"))
register(Variant("dropout", "rbg", _dropout_rbg,
                 doc="hardware rng_bit_generator (XLA RBG)"))
