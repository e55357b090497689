"""Search-generated kernel candidates: parameterized Pallas templates.

The PR-2 registry (ops/variants.py) made lowering choice systematic, but
its candidate set was closed — a handful of hand-written lowerings per
op, so the autotuner could never find a point the hand-written set
doesn't contain. Following "Agentic Operator Generation for ML ASICs"
(arxiv 2512.10977, PAPERS.md), this module makes the set GENERATED:

- a `KernelTemplate` names an op's tuning axes (a typed config space —
  the frozen constants of ops/pallas_kernels.py turned parameters:
  flash-attention blk_q/blk_k/KV-stream order, fused-SGD row blocking,
  the fused LRN+maxpool sample tile) and builds a concrete candidate
  callable from any point in the space;
- every generated point registers through `ops.variants` under a
  parseable name (``base[axis=value,...]``), so resolve()/select()/
  selection_table() treat it exactly like a hand-written variant, and a
  persisted winner re-materializes in a fresh process from its name
  alone (`materialize`, hooked into `variants.get`);
- the EQUIVALENCE LEDGER is the structural correctness gate: a
  candidate is timeable ONLY after `check_equivalence` records a pass
  against the op's `ops.reference` contract (fwd + bwd, Pallas via
  interpret mode on CPU). The budgeted search (ops/autotune.py) refuses
  to time an ungated candidate — correctness is structural, not
  hoped-for.

No jax at module scope: variants.py (jax-free by design, the resilience
supervisor imports it) calls into `materialize` from `get()`; all
jax-bearing work lives inside template builders, contracts and benches.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from veles_tpu.ops import variants

__all__ = [
    "Axis", "KernelTemplate", "register_template", "templates_for",
    "template_ops", "materialize", "space_signature",
    "check_equivalence", "equivalence_record", "passed", "clear_ledger",
    "ledger_table", "bench_candidate", "UngatedCandidateError",
    "fusion_members", "fusion_config", "fusion_point",
]


class UngatedCandidateError(RuntimeError):
    """Raised when something tries to time a candidate that has no
    passing equivalence record — the structural gate the search rides."""


@dataclass(frozen=True)
class Axis:
    """One typed tuning axis: a name and its finite choice set."""

    name: str
    choices: Tuple[Any, ...]
    doc: str = ""

    def __post_init__(self):
        if not self.choices:
            raise ValueError(f"axis {self.name!r} has no choices")


@dataclass
class KernelTemplate:
    """A parameterized kernel: op + axes + a builder that turns one
    config point into the op's canonical `apply` callable.

    `seed` is the coordinate-descent start point — the hand-written
    incumbent's settings expressed as a config, so the search begins
    where four rounds of manual tuning ended."""

    op: str
    base: str                       # variant-name prefix, e.g. "pallas"
    axes: Tuple[Axis, ...]
    build: Callable[[Dict[str, Any]], Callable[..., Any]]
    seed: Dict[str, Any]
    pallas: bool = True
    doc: str = ""
    #: optional config -> hashable key of the kernel the MICROBENCH
    #: would actually execute (kernels that clamp their parameters to
    #: the input shape — flash fit() — make distinct configs alias at
    #: the bench shapes; the search skips aliases so the budget times
    #: distinct kernels and a cached winner names an executed config)
    bench_key: Optional[Callable[[Dict[str, Any]], Any]] = None
    #: optional config -> bool: does this point carry per-shard state
    #: through the caller (grad_reduce error feedback)? Materialized
    #: variants get Variant.stateful from it so the fused step can size
    #: its state slot from the NAME alone.
    stateful: Optional[Callable[[Dict[str, Any]], bool]] = None
    #: name of the axis that decides whether a point FUSES a neighbor's
    #: work ("fuse"/"epi"/"drop"); a point is a FUSED point when that
    #: axis's value is not in _FUSE_OFF. None = the template has no
    #: fusion structure (a pure tuning-constant space).
    fuse_axis: Optional[str] = None
    #: the member registry ops a pure-fusion op's candidates compose
    #: (lrn_maxpool -> ("lrn", "maxpool")); the budgeted search charges
    #: a fused candidate against the COMBINED profile share of these.
    #: Empty for templates whose op is itself a unit op (conv_stem,
    #: flash_attn — their fuse axis rides the op's own share).
    fuses: Tuple[str, ...] = ()
    #: declarative VMEM model (ISSUE 14, analysis/resources.py):
    #: (config, shapes, dtype) -> resident bytes of the point's Pallas
    #: blocks (double-buffered in/out block bytes + scratch, derived
    #: from the kernel's BlockSpecs in ops/pallas_kernels.py; worst
    #: direction wins). `shapes` is an op-specific dim dict — missing
    #: keys fall back to the rule's canonical bench shapes, the very
    #: kernel the microbench would run. None = no static footprint
    #: (non-Pallas ops): unknown is never pruned.
    vmem_footprint: Optional[
        Callable[[Dict[str, Any], Dict[str, Any], Any], int]] = None

    def __post_init__(self):
        self.seed = self.validate(self.seed)

    # -- config handling ------------------------------------------------------

    def axis(self, name: str) -> Axis:
        for a in self.axes:
            if a.name == name:
                return a
        raise KeyError(f"template {self.op}/{self.base}: no axis {name!r}")

    def validate(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """Canonicalize a config: every axis present, every value in its
        choice set, declaration order."""
        out = {}
        for a in self.axes:
            if a.name not in config:
                raise KeyError(f"template {self.op}/{self.base}: config "
                               f"missing axis {a.name!r}")
            v = config[a.name]
            if v not in a.choices:
                raise ValueError(
                    f"template {self.op}/{self.base}: {a.name}={v!r} not "
                    f"in {a.choices}")
            out[a.name] = v
        extra = set(config) - set(out)
        if extra:
            raise KeyError(f"template {self.op}/{self.base}: unknown "
                           f"axes {sorted(extra)}")
        return out

    @property
    def size(self) -> int:
        n = 1
        for a in self.axes:
            n *= len(a.choices)
        return n

    def configs(self) -> List[Dict[str, Any]]:
        """The full cross product, declaration-ordered."""
        points: List[Dict[str, Any]] = [{}]
        for a in self.axes:
            points = [{**p, a.name: c} for p in points for c in a.choices]
        return points

    # -- naming (the cache/registry identity of a generated point) -----------

    def name(self, config: Dict[str, Any]) -> str:
        cfg = self.validate(config)
        inner = ",".join(f"{k}={cfg[k]}" for k in cfg)
        return f"{self.base}[{inner}]"

    _NAME_RE = re.compile(r"^(?P<base>[A-Za-z0-9_]+)\[(?P<cfg>[^\]]*)\]$")

    def parse(self, name: str) -> Optional[Dict[str, Any]]:
        """Config encoded in a generated-variant name; None when the
        name doesn't belong to this template (wrong base, unknown axis,
        out-of-space value — a stale cache must degrade, not crash)."""
        m = self._NAME_RE.match(name)
        if m is None or m.group("base") != self.base:
            return None
        cfg: Dict[str, Any] = {}
        for part in filter(None, m.group("cfg").split(",")):
            if "=" not in part:
                return None
            k, _, raw = part.partition("=")
            try:
                ax = self.axis(k)
            except KeyError:
                return None
            # decode by the axis's own value type (int axes vs str axes)
            val: Any = raw
            if raw.lstrip("-").isdigit():
                val = int(raw)
            if val not in ax.choices:
                return None
            cfg[k] = val
        try:
            return self.validate(cfg)
        except (KeyError, ValueError):
            return None


_TEMPLATES: Dict[str, List[KernelTemplate]] = {}


def register_template(t: KernelTemplate) -> KernelTemplate:
    _TEMPLATES.setdefault(t.op, []).append(t)
    return t


def templates_for(op: str) -> List[KernelTemplate]:
    return list(_TEMPLATES.get(op, ()))


def template_ops() -> List[str]:
    return sorted(_TEMPLATES)


def materialize(op: str, name: str) -> Optional["variants.Variant"]:
    """Register-on-demand: turn a generated-variant NAME back into a
    live registry entry (the path a persisted cache winner takes in a
    fresh process — `variants.get` falls through to here on a miss).
    None when no template of `op` owns the name."""
    for t in templates_for(op):
        cfg = t.parse(name)
        if cfg is None:
            continue
        v = variants.Variant(
            op=op, name=t.name(cfg), apply=t.build(cfg),
            pallas=t.pallas, generated=True,
            stateful=bool(t.stateful(cfg)) if t.stateful else False,
            doc=f"generated from template {t.base} at {cfg}")
        return variants.register(v)
    return None


# -- cross-op fusion structure (ISSUE 13) -----------------------------------
#: fuse-axis values that mean "do NOT fuse" — the composed point
_FUSE_OFF = (0, "none", "off", None)


def fusion_members(op: str) -> Tuple[str, ...]:
    """The member registry ops whose work a pure-fusion op's candidates
    claim (() for ordinary ops) — the search's combined-share charging
    and tools/layer_profile.py's split both read this."""
    out: List[str] = []
    for t in templates_for(op):
        for m in t.fuses:
            if m not in out:
                out.append(m)
    return tuple(out)


def fusion_config(op: str, name: Any) -> Optional[Dict[str, Any]]:
    """Parsed config of `name` IF it is a FUSED point of one of op's
    templates (its fuse axis is on); None for composed/foreign names —
    the one rule FusedTrainStep, variant_table and the jaxpr auditor
    share to decide whether a selection actually claims a neighbor."""
    for t in templates_for(op):
        if t.fuse_axis is None:
            continue
        cfg = t.parse(name) if isinstance(name, str) else None
        if cfg is not None and cfg.get(t.fuse_axis) not in _FUSE_OFF:
            return cfg
    return None


def fusion_point(op: str, unit: Any = None):
    """The variant `op` resolves to right now IF that resolution is a
    FUSED point (pallas gating included — under GSPMD or a pallas-less
    backend resolve() falls back to the composed incumbent and this
    returns None). The trace-time gate behind the pass-through-unit
    rule."""
    v = variants.resolve(op, unit=unit)
    return v if fusion_config(op, v.name) is not None else None


def space_signature(op: str) -> List[Dict[str, Any]]:
    """Cache-key payload for a template-searched op: the config space
    itself (a changed axis/choice set must invalidate old decisions the
    same way a changed layer shape does for workflow ops)."""
    return [{
        "template": t.base,
        "axes": {a.name: list(a.choices) for a in t.axes},
        "seed": dict(t.seed),
    } for t in templates_for(op)]


# ===========================================================================
# Equivalence ledger — the structural gate between generation and timing
# ===========================================================================

#: op -> contract callable(apply) -> detail dict; RAISES on mismatch.
#: Contracts compare against ops.reference (numpy goldens) forward AND
#: backward on small canonical shapes; Pallas candidates run in
#: interpret mode on CPU automatically (pallas_kernels._interpret()).
CONTRACTS: Dict[str, Callable[[Callable], Dict[str, Any]]] = {}

#: (op, variant-name) -> {"status": "pass"|"fail", ...}
_LEDGER: Dict[Tuple[str, str], Dict[str, Any]] = {}


def check_equivalence(op: str, name: str,
                      force: bool = False) -> Dict[str, Any]:
    """Run op's ops.reference contract on the named candidate and record
    the outcome. Idempotent per (op, name) unless `force`."""
    rec = _LEDGER.get((op, name))
    if rec is not None and not force:
        return rec
    contract = CONTRACTS.get(op)
    if contract is None:
        rec = {"status": "fail",
               "error": f"op {op!r} has no equivalence contract"}
    else:
        try:
            v = variants.get(op, name)
            rec = {"status": "pass", **(contract(v.apply) or {})}
        except Exception as e:  # noqa: BLE001 — a failing candidate is
            # DATA (the search skips it), never a search abort
            rec = {"status": "fail", "error": f"{e!s:.300}"}
    _LEDGER[(op, name)] = rec
    return rec


def equivalence_record(op: str, name: str) -> Optional[Dict[str, Any]]:
    rec = _LEDGER.get((op, name))
    return dict(rec) if rec else None


def passed(op: str, name: str) -> bool:
    rec = _LEDGER.get((op, name))
    return bool(rec) and rec.get("status") == "pass"


def clear_ledger() -> None:
    _LEDGER.clear()


def ledger_table() -> Dict[str, str]:
    return {f"{op}/{name}": rec.get("status", "?")
            for (op, name), rec in _LEDGER.items()}


# ===========================================================================
# Microbenches — how a candidate is timed when the op is not reachable
# through a workflow's fused step (flash_attn / sgd_update live below
# the unit graph). An op the workflow's fused step reaches times IN-GRAPH
# via the PR-2 protocol instead; see ops.autotune.
# ===========================================================================

BENCHES: Dict[str, Callable[[Callable, int], float]] = {}


def bench_candidate(op: str, apply: Callable, repeats: int = 2) -> float:
    """Seconds per fwd(+bwd where differentiable) call of `apply` on the
    op's canonical bench shapes (tiny on CPU, real on TPU)."""
    return BENCHES[op](apply, repeats)


def _on_cpu() -> bool:
    import jax
    return jax.default_backend() == "cpu"


def _time_jitted(fn, args, repeats: int) -> float:
    import time

    import jax
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))       # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ===========================================================================
# VMEM footprint rules (ISSUE 14): the declarative cost model behind the
# search's static pruning (analysis/resources.py owns the budget table
# and verdicts). Each rule mirrors its kernel's BlockSpecs in
# ops/pallas_kernels.py: Pallas pipelines grid steps with DOUBLE-
# BUFFERED in/out blocks, so resident bytes = 2 x (in-block + out-block
# bytes) + scratch. In-kernel temporaries beyond the declared refs are
# a documented under-count (docs/ANALYSIS.md blind spots).
# ===========================================================================


def _dtype_width(dtype) -> int:
    """Byte width of a compute-dtype spec ('bfloat16', np dtype, None =
    f32) without requiring numpy to know the name."""
    if dtype is None:
        return 4
    s = str(dtype)
    return {"bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
            "float64": 8, "f64": 8}.get(s, 4)


# ===========================================================================
# Registered templates: the tuning axes of ops/pallas_kernels.py
# ===========================================================================

# -- flash_attn: block shapes + KV streaming order --------------------------

def _flash_build(cfg):
    def apply(q, k, v, scale=None, causal=False, drop_mask=None,
              scope=None):
        from veles_tpu.ops import pallas_kernels as pk
        return pk.flash_attention_pallas(
            q, k, v, scale=scale, causal=causal, blk_q=cfg["blk_q"],
            blk_k=cfg["blk_k"], kv_order=cfg["kv_order"],
            drop_mask=drop_mask if cfg["drop"] else None, scope=scope)
    #: the contract/bench read the fuse axis off the closure so a fused
    #: point is exercised (and timed) WITH its mask leg
    apply.fusion_drop = cfg["drop"]
    return apply


def _flash_contract(apply):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veles_tpu.ops import attention as oa
    from veles_tpu.ops import reference as ref
    rs = np.random.RandomState(7)
    b, s, h, d = 1, 256, 2, 8
    q, k, v = (rs.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    w = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    for causal in (False, True):
        got = np.asarray(apply(q, k, v, causal=causal))
        np.testing.assert_allclose(
            got, ref.mha_forward(q, k, v, causal=causal),
            rtol=2e-4, atol=2e-5)
        # backward vs jax.vjp of the einsum golden (reference.mha_forward
        # is numpy; oa.mha_forward is its pinned jax twin)
        gf = jax.grad(lambda *a: jnp.sum(apply(*a, causal=causal) * w),
                      argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(
            lambda *a: jnp.sum(oa.mha_forward(*a, causal=causal) * w),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=name)
    checked = ("flash fwd vs ops.reference.mha_forward + bwd vs "
               "einsum vjp, causal and not")
    if getattr(apply, "fusion_drop", 0):
        # FUSED point: the in-kernel dropout epilogue vs the COMPOSED
        # golden (attn_dropout_forward = mha_forward ⊙ mask; bwd vs the
        # einsum-then-dropout_backward composition through jax.grad)
        mask = (ref.make_dropout_mask(np.random.RandomState(17),
                                      (b, s, h, d), 0.4)
                .astype(np.float32))
        mj = jnp.asarray(mask)
        got = np.asarray(apply(q, k, v, causal=True, drop_mask=mask))
        np.testing.assert_allclose(
            got, ref.attn_dropout_forward(q, k, v, mask, causal=True),
            rtol=2e-4, atol=2e-5)
        gf = jax.grad(
            lambda *a: jnp.sum(apply(*a, causal=True, drop_mask=mj) * w),
            argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(
            lambda *a: jnp.sum(
                oa.mha_forward(*a, causal=True) * mj * w),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"drop {name}")
        checked += " + dropout epilogue vs composed attn_dropout golden"
    return {"checked": checked}


def _flash_bench_shape():
    # CPU: S must span the blk choices or every config clamps to the
    # same kernel (see _flash_bench_key); 1 head + d=4 keeps the
    # interpret-mode grid walk affordable
    return (1, 512, 1, 4) if _on_cpu() else (1, 8192, 8, 64)


def _flash_bench_key(cfg):
    """The (blk_q, blk_k, kv_order, drop) the kernel ACTUALLY runs at
    the bench shapes — flash_attention_pallas shrinks requested blocks
    to divisors of S (flash_fit_block), so e.g. blk_k=1024 at S=512 IS
    blk_k=512."""
    from veles_tpu.ops.pallas_kernels import flash_fit_block
    s = _flash_bench_shape()[1]
    return (flash_fit_block(s, cfg["blk_q"]),
            flash_fit_block(s, cfg["blk_k"]), cfg["kv_order"],
            cfg["drop"])


def _flash_vmem(cfg, shapes, dtype):
    """Worst of the three flash grids (fwd / dQ / dK-dV), each with its
    declared blocks double-buffered plus its scratch — all f32 inside
    the kernels. Blocks are clamped to divisors of S exactly like the
    traced kernel (flash_fit_block), so the pruned geometry IS the one
    that would compile."""
    from veles_tpu.ops.pallas_kernels import flash_fit_block
    _, s0, _, d0 = _flash_bench_shape()
    s = int(shapes.get("s") or s0)
    d = int(shapes.get("d") or d0)
    bq = flash_fit_block(s, cfg["blk_q"])
    bk = flash_fit_block(s, cfg["blk_k"])
    f32 = 4

    def col(rows):          # one (rows, d) block
        return rows * d * f32

    def vec(rows):          # one (rows, 1) block
        return rows * f32

    # fwd: q + k + v [+ mask] in, out + lse out; scratch m/l/acc
    fwd = 2 * (col(bq) + 2 * col(bk)
               + (col(bq) if cfg.get("drop") else 0)
               + col(bq) + vec(bq)) + 2 * vec(bq) + col(bq)
    # dQ: q/do + k/v + lse/di in, dq out; scratch dq accumulator
    dq = 2 * (2 * col(bq) + 2 * col(bk) + 2 * vec(bq)
              + col(bq)) + col(bq)
    # dK/dV (transposed grid): q/do + k/v + lse/di in, dk + dv out;
    # scratch dk/dv accumulators
    dkv = 2 * (2 * col(bq) + 2 * col(bk) + 2 * vec(bq)
               + 2 * col(bk)) + 2 * col(bk)
    return max(fwd, dq, dkv)


def _flash_bench(apply, repeats):
    import jax
    import jax.numpy as jnp
    b, s, h, d = _flash_bench_shape()
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    kw = {}
    if getattr(apply, "fusion_drop", 0):
        # a FUSED point is timed with its mask leg — that is the kernel
        # a winning selection would actually trace
        kw["drop_mask"] = (
            (jax.random.uniform(jax.random.PRNGKey(6),
                                (b, s, h, d)) < 0.5)
            .astype(jnp.float32) * 2.0)

    def fwd_bwd(q, k, v):
        y, vjp = jax.vjp(lambda *a: apply(*a, causal=True, **kw),
                         q, k, v)
        return y, vjp(y)

    return _time_jitted(fwd_bwd, (q, k, v), repeats)


register_template(KernelTemplate(
    op="flash_attn", base="pallas",
    axes=(Axis("blk_q", (128, 256, 512), doc="query rows per tile"),
          Axis("blk_k", (128, 256, 512, 1024), doc="KV rows per tile"),
          Axis("kv_order", ("fwd", "rev"),
               doc="forward-pass KV tile visit order (online softmax is "
                   "order-invariant; probes prefetch locality)"),
          Axis("drop", (0, 1),
               doc="FUSE axis: apply a pre-scaled dropout mask inside "
                   "the kernel's output-block write (drops the composed "
                   "path's extra HBM round trip over the attention "
                   "output); gated by the composed attn_dropout "
                   "golden")),
    build=_flash_build,
    seed={"blk_q": 512, "blk_k": 1024, "kv_order": "fwd", "drop": 0},
    bench_key=_flash_bench_key, fuse_axis="drop",
    vmem_footprint=_flash_vmem,
    doc="blocked flash attention over blk_q x blk_k x streaming order "
        "x dropout-epilogue fusion (hand incumbent: 512/1024/fwd, "
        "unfused, tuned v5e 2026-07-29)"))
CONTRACTS["flash_attn"] = _flash_contract
BENCHES["flash_attn"] = _flash_bench


# -- sgd_update: row blocking of the fused update ---------------------------

def _sgd_pallas_build(cfg):
    rt = cfg["rt"]

    def apply(params, grads, vel, sgd_cfg, lr_scale=1.0, mults=None):
        import jax

        from veles_tpu.ops import optim
        from veles_tpu.ops import pallas_kernels as pk
        if getattr(sgd_cfg, "l1_decay", 0.0):
            # the fused kernel has no L1 term — exact math wins over
            # the lowering, fall back to the tree update
            return optim.sgd_update(params, grads, vel, sgd_cfg,
                                    lr_scale=lr_scale, mults=mults)

        def upd(path, p, g, v):
            key = path[0].key if path and hasattr(path[0], "key") \
                else None
            lr = optim.sgd_leaf_lr(sgd_cfg, p.ndim, lr_scale=lr_scale,
                                   key=key, mults=mults)
            return pk.sgd_update_pallas(p, g, v, lr, sgd_cfg.momentum,
                                        sgd_cfg.weight_decay,
                                        row_tile=rt)

        flat = jax.tree_util.tree_map_with_path(upd, params, grads, vel)
        is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
        new_p = jax.tree_util.tree_map(lambda t: t[0], flat,
                                       is_leaf=is_pair)
        new_v = jax.tree_util.tree_map(lambda t: t[1], flat,
                                       is_leaf=is_pair)
        return new_p, new_v
    return apply


def _sgd_contract(apply):
    import numpy as np

    from veles_tpu.ops import optim
    from veles_tpu.ops import reference as ref
    rs = np.random.RandomState(11)
    cfg = optim.SGDConfig(lr=0.05, momentum=0.9, weight_decay=1e-3,
                          lr_bias_mult=2.0)
    params = {"weights": rs.randn(33, 17).astype(np.float32),
              "bias": rs.randn(5).astype(np.float32)}
    grads = {k: rs.randn(*v.shape).astype(np.float32)
             for k, v in params.items()}
    vel = {k: rs.randn(*v.shape).astype(np.float32)
           for k, v in params.items()}
    new_p, new_v = apply(params, grads, vel, cfg, lr_scale=0.5)
    for k in params:
        # the bias-lr convention rides ndim, exactly like the tree path
        lr = cfg.lr * 0.5 * (cfg.lr_bias_mult if params[k].ndim == 1
                             else 1.0)
        pg, vg = ref.sgd_momentum_update(
            params[k], grads[k], vel[k], lr, cfg.momentum,
            cfg.weight_decay)
        np.testing.assert_allclose(np.asarray(new_p[k]), pg, rtol=1e-5,
                                   atol=1e-6, err_msg=k)
        np.testing.assert_allclose(np.asarray(new_v[k]), vg, rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    return {"checked": "sgd+momentum+wd vs ops.reference, incl. the "
                       "1-D bias lr multiplier, rtol 1e-5"}


def _sgd_bench(apply, repeats):
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import optim
    shape = (256, 65) if _on_cpu() else (4096, 4097)
    cfg = optim.SGDConfig(lr=0.01, momentum=0.9, weight_decay=1e-4)
    key = jax.random.PRNGKey(2)
    p, g, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(key, 3))
    tree = {"weights": p, "bias": p[0]}

    def step(params):
        return apply(params, {"weights": g, "bias": g[0]},
                     {"weights": v, "bias": v[0]}, cfg)

    return _time_jitted(step, (tree,), repeats)


def _sgd_vmem(cfg, shapes, dtype):
    """One (rt, 128) f32 block per buffer: 3 inputs (p, g, v) + 2
    outputs, double-buffered (the SMEM scalar vector is negligible)."""
    from veles_tpu.ops import pallas_kernels as pk
    rt = max(pk._MIN_ROW_TILE, cfg["rt"])
    return 2 * 5 * rt * pk._LANE * 4


register_template(KernelTemplate(
    op="sgd_update", base="pallas_rows",
    axes=(Axis("rt", (8, 16, 32, 64, 128, 256, 512, 1024),
               doc="rows per program of the flattened (rows, 128) "
                   "update grid"),),
    build=_sgd_pallas_build, seed={"rt": 8}, vmem_footprint=_sgd_vmem,
    doc="fused SGD+momentum+weight-decay update (one VMEM pass over 3 "
        "buffers) over its row blocking; the hand-written kernel froze "
        "rt=8"))
CONTRACTS["sgd_update"] = _sgd_contract
BENCHES["sgd_update"] = _sgd_bench


# -- grad_reduce: wire dtype x scale block x error feedback x hierarchy -----
#    (the EQuARX family, arxiv 2506.17615 — ISSUE 12). All points build
#    through variants.grad_reduce_apply, the ONE collective
#    implementation; the contract gates each point on the BITWISE
#    quantize/dequantize roundtrip vs ops.reference plus the shard_map
#    exchange vs the psum golden at the wire dtype's tolerance.

def _gr_build(cfg):
    return variants.grad_reduce_apply(dict(cfg))


def _gr_mesh():
    import jax

    from veles_tpu.parallel.mesh import make_mesh
    devs = jax.devices()[:8]
    return make_mesh(devs), len(devs)


def _gr_contract(apply):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from veles_tpu.ops import reference as ref
    from veles_tpu.parallel.mesh import DATA_AXIS
    cfg = getattr(apply, "gr_config", None) or variants.grad_reduce_config(
        "f32")
    blk = int(cfg.get("blk") or 256)
    # 1. BITWISE quantize/dequantize roundtrip vs the numpy goldens —
    # codes, scales and dequantized values must match exactly (the
    # "bitwise roundtrip" half of the equivalence ledger)
    rs = np.random.RandomState(5)
    xq = rs.randn(3, 2 * blk).astype(np.float32)
    qj, sj = variants.q8_encode(jnp.asarray(xq), blk)
    qg, sg = ref.quantize_blockwise(xq, blk)
    np.testing.assert_array_equal(np.asarray(qj), qg)
    np.testing.assert_array_equal(np.asarray(sj), sg)
    np.testing.assert_array_equal(
        np.asarray(variants.q8_decode(qj, sj, blk)),
        ref.dequantize_blockwise(qg, sg, blk))
    # 2. the exchange itself under shard_map vs the psum-then-slice
    # golden (the registry's admission bar for collectives)
    mesh, n = _gr_mesh()
    local = 48
    flat = rs.randn(n, n * local).astype(np.float32)
    stateful = bool(cfg.get("ef"))

    def body(g):
        r = apply(g.reshape(-1), DATA_AXIS)
        return r[0] if stateful else r

    got = np.asarray(jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(DATA_AXIS),
        out_specs=P(DATA_AXIS)))(flat))
    want = flat.reshape(n, n, local).sum(axis=0).reshape(-1)
    if cfg["dt"] == "f32":
        tol = dict(rtol=1e-5, atol=1e-5)
    elif cfg["dt"] == "bf16":
        tol = dict(rtol=0.05, atol=0.05)
    else:
        # int8 absolute error is bounded by n_shards x scale/2 with
        # scale = block-absmax/127 (~0.03 for unit-normal grads)
        tol = dict(rtol=0.1, atol=0.03 * n)
    np.testing.assert_allclose(got, want, **tol)
    if cfg["dt"] == "int8" and not cfg["hier"]:
        # flat int8 is EXACTLY the reference-quantized exchange: the sum
        # of per-shard dequantized partials, to f32 summation rounding
        deq = np.zeros_like(flat)
        pad = (-local) % blk
        for i in range(n):
            x2 = np.pad(flat[i].reshape(n, local), ((0, 0), (0, pad)))
            q, s = ref.quantize_blockwise(x2, blk)
            deq[i] = ref.dequantize_blockwise(q, s, blk)[:, :local] \
                .reshape(-1)
        want_q = deq.reshape(n, n, local).sum(axis=0).reshape(-1)
        np.testing.assert_allclose(got, want_q, rtol=1e-6, atol=1e-5)
    return {"checked": f"q8 roundtrip bitwise vs ops.reference + "
                       f"shard_map exchange vs psum golden on {n} "
                       f"devices ({cfg['dt']} tolerance)"}


def _gr_bench_key(cfg):
    """Configs that trace the same program at the bench geometry alias:
    blk/ef only matter for int8 wire, and hier degrades to flat when
    the geometry is single-level (grad_reduce_geometry)."""
    _, n = _gr_mesh()
    h, loc = variants.grad_reduce_geometry(n)
    int8 = cfg["dt"] == "int8"
    hier = bool(cfg["hier"]) and h > 1 and loc > 1
    return (cfg["dt"], cfg["blk"] if int8 else 0,
            cfg["ef"] if int8 else 0, int(hier))


def _gr_bench(apply, repeats):
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from veles_tpu.parallel.mesh import DATA_AXIS
    mesh, n = _gr_mesh()
    per_shard = n * (4096 if _on_cpu() else (1 << 19))
    flat = jax.random.normal(jax.random.PRNGKey(3), (n, per_shard),
                             jnp.float32)

    def body(g):
        r = apply(g.reshape(-1), DATA_AXIS)
        out = r[0] if isinstance(r, tuple) else r
        return out.reshape(1, -1)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                          out_specs=P(DATA_AXIS)))
    jax.block_until_ready(f(flat))          # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(f(flat))
        best = min(best, time.perf_counter() - t0)
    return best


register_template(KernelTemplate(
    op="grad_reduce", base="wire",
    axes=(Axis("dt", ("f32", "bf16", "int8"),
               doc="wire dtype of the DCN exchange"),
          Axis("blk", (64, 128, 256, 512),
               doc="int8 absmax-scale block (scale overhead 4/blk "
                   "bytes/elem); inert for float wire"),
          Axis("ef", (0, 1),
               doc="error feedback: carry the quantization residual in "
                   "the ZeRO state (int8 only — canonicalized off "
                   "otherwise)"),
          Axis("hier", (0, 1),
               doc="two-level (hosts x local) decomposition: ICI-local "
                   "reduce-scatter, DCN exchange of 1/n_local slices")),
    build=_gr_build, seed={"dt": "f32", "blk": 256, "ef": 0, "hier": 0},
    pallas=False, bench_key=_gr_bench_key,
    stateful=lambda cfg: cfg["dt"] == "int8" and bool(cfg["ef"]),
    doc="quantized + hierarchical ZeRO reduce-scatter family (EQuARX, "
        "arxiv 2506.17615) — the search picks the winner per link "
        "geometry, cache-keyed by (device_kind, hosts x local)"))
CONTRACTS["grad_reduce"] = _gr_contract
BENCHES["grad_reduce"] = _gr_bench


# -- maxpool: forward algorithm x backward combine-DAG shape ----------------
#    (carried ROADMAP item: the last registry ops with no template; the
#    axes reify the hand-written reduce_window/slices split and add the
#    slices fold's combine-tree shape — the backward's select-DAG depth)

def _maxpool_build(cfg):
    algo, fold = cfg["algo"], cfg["fold"]

    def apply(x, ksize, stride, use_abs):
        from veles_tpu.ops import variants as va
        from veles_tpu.ops import xla as ox
        if algo == "reduce_window":
            return va.get("maxpool", "reduce_window").apply(
                x, ksize, stride, use_abs)
        return ox.maxpool_forward_slices(x, ksize, stride, use_abs,
                                         fold=fold)
    return apply


def _maxpool_contract(apply):
    import jax
    import numpy as np

    from veles_tpu.ops import reference as ref
    rs = np.random.RandomState(9)
    x = rs.randn(2, 7, 7, 6).astype(np.float32)
    for use_abs in (False, True):
        y, vjp = jax.vjp(lambda a: apply(a, (3, 3), (2, 2), use_abs), x)
        yg, idx = ref.maxpool_forward(x, (3, 3), (2, 2), use_abs)
        np.testing.assert_allclose(np.asarray(y), yg, atol=1e-6,
                                   err_msg=f"use_abs={use_abs}")
        g = rs.randn(*yg.shape).astype(np.float32)
        (dx,) = vjp(g)
        np.testing.assert_allclose(
            np.asarray(dx), ref.maxpool_backward(g, idx, x.shape),
            atol=1e-6, err_msg=f"use_abs={use_abs} bwd")
    return {"checked": "maxpool fwd+bwd (max + maxabs) vs "
                       "ops.reference, atol 1e-6"}


def _maxpool_bench(apply, repeats):
    import jax
    import jax.numpy as jnp
    shape = (8, 13, 13, 8) if _on_cpu() else (256, 27, 27, 96)
    x = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32)

    def fwd_bwd(xx):
        y, vjp = jax.vjp(lambda a: apply(a, (3, 3), (2, 2), False), xx)
        return y, vjp(y)[0]

    return _time_jitted(fwd_bwd, (x,), repeats)


def _maxpool_bench_key(cfg):
    # fold only shapes the slices combine-DAG; reduce_window ignores it
    return (cfg["algo"],
            cfg["fold"] if cfg["algo"] == "slices" else "-")


register_template(KernelTemplate(
    op="maxpool", base="gen",
    axes=(Axis("algo", ("reduce_window", "slices"),
               doc="forward lowering (the knob is what the BACKWARD "
                   "lowers to: select_and_scatter vs selects+pads)"),
          Axis("fold", ("linear", "tree"),
               doc="slices combine-DAG: left fold (deep select chain) "
                   "vs pairwise tree (log depth); inert for "
                   "reduce_window")),
    build=_maxpool_build,
    seed={"algo": "reduce_window", "fold": "linear"},
    pallas=False, bench_key=_maxpool_bench_key,
    doc="max/maxabs pooling over algorithm x backward combine shape"))
CONTRACTS["maxpool"] = _maxpool_contract
BENCHES["maxpool"] = _maxpool_bench


# -- conv_stem: input packing x accumulator dtype ---------------------------

def _conv_stem_build(cfg):
    pack, acc, epi = cfg["pack"], cfg["acc"], cfg["epi"]

    def apply(x, w, b, stride, padding, activation, epilogue=None):
        from veles_tpu.ops import xla as ox
        y = ox.conv2d_forward(x, w, b, stride, padding, activation,
                              s2d=(pack == "s2d"), acc=acc)
        if epi == "lrn" and epilogue is not None:
            # the claimed successor's LRN folded into the epilogue: the
            # step passes the NORM unit's hyperparameters when a fused
            # winner claims an adjacent (conv_stem, lrn) pair
            y = ox.lrn_forward(y, epilogue["k"], epilogue["alpha"],
                               epilogue["beta"], epilogue["n"])
        return y
    apply.fusion_epi = epi
    return apply


def _conv_stem_contract(apply):
    import jax
    import numpy as np

    from veles_tpu.ops import reference as ref
    rs = np.random.RandomState(13)
    x = rs.randn(2, 19, 19, 3).astype(np.float32)
    w = (rs.randn(5, 5, 3, 8) * 0.1).astype(np.float32)
    b = rs.randn(8).astype(np.float32)
    stride, padding, act = (4, 4), (0, 0), "strictrelu"
    y, vjp = jax.vjp(
        lambda xx, ww, bb: apply(xx, ww, bb, stride, padding, act),
        x, w, b)
    yg = ref.conv2d_forward(x, w, b, stride, padding, act)
    np.testing.assert_allclose(np.asarray(y), yg, rtol=1e-4, atol=1e-4)
    g = rs.randn(*yg.shape).astype(np.float32)
    dx, dw, db = vjp(g)
    gx, gw, gb = ref.conv2d_backward(x, w, yg, g, stride, padding, act)
    np.testing.assert_allclose(np.asarray(dx), gx, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), gw, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(db), gb, rtol=1e-4, atol=1e-4)
    checked = ("stem conv fwd+bwd (stride-4 thin-channel) vs "
               "ops.reference, rtol 1e-4")
    if getattr(apply, "fusion_epi", "none") == "lrn":
        # FUSED point: bias+act+LRN epilogue vs the COMPOSED golden
        epi = {"k": 2.0, "alpha": 1e-3, "beta": 0.75, "n": 5}
        y2, vjp2 = jax.vjp(
            lambda xx, ww, bb: apply(xx, ww, bb, stride, padding, act,
                                     epilogue=epi), x, w, b)
        y2g = ref.conv_lrn_forward(x, w, b, stride, padding, act, **epi)
        np.testing.assert_allclose(np.asarray(y2), y2g, rtol=1e-4,
                                   atol=1e-4)
        g2 = rs.randn(*y2g.shape).astype(np.float32)
        dx2, dw2, db2 = vjp2(g2)
        gx2, gw2, gb2 = ref.conv_lrn_backward(
            x, w, b, g2, stride, padding, act, **epi)
        np.testing.assert_allclose(np.asarray(dx2), gx2, rtol=1e-4,
                                   atol=1e-4, err_msg="epi dx")
        np.testing.assert_allclose(np.asarray(dw2), gw2, rtol=1e-4,
                                   atol=1e-3, err_msg="epi dw")
        np.testing.assert_allclose(np.asarray(db2), gb2, rtol=1e-4,
                                   atol=1e-4, err_msg="epi db")
        checked += " + LRN epilogue vs composed conv_lrn golden"
    return {"checked": checked}


def _conv_stem_bench(apply, repeats):
    import jax
    import jax.numpy as jnp
    n, hw, co = (4, 35, 16) if _on_cpu() else (256, 227, 96)
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (n, hw, hw, 3), jnp.float32)
    w = jax.random.normal(k2, (11, 11, 3, co), jnp.float32) * 0.05
    b = jax.random.normal(k3, (co,), jnp.float32)
    kw = {}
    if getattr(apply, "fusion_epi", "none") == "lrn":
        # a FUSED point is timed with its folded epilogue — that is the
        # program a winning selection would actually trace
        kw["epilogue"] = {"k": 2.0, "alpha": 1e-4, "beta": 0.75, "n": 5}

    def fwd_bwd(xx, ww, bb):
        y, vjp = jax.vjp(
            lambda a, c, d: apply(a, c, d, (4, 4), (0, 0),
                                  "strictrelu", **kw), xx, ww, bb)
        return y, vjp(y)

    return _time_jitted(fwd_bwd, (x, w, b), repeats)


def _conv_stem_bench_key(cfg):
    # the microbench runs f32 inputs, where the accumulator axis traces
    # the same program — packing and the epilogue fusion distinguish
    # kernels there (epi=lrn points are timed WITH the folded LRN)
    return (cfg["pack"], cfg["epi"])


register_template(KernelTemplate(
    op="conv_stem", base="gen",
    axes=(Axis("pack", ("direct", "s2d"),
               doc="input packing: plain strided conv vs the exact "
                   "space-to-depth rewrite (full MXU tiles)"),
          Axis("acc", ("native", "f32"),
               doc="conv accumulator dtype under sub-f32 compute: "
                   "XLA's dtype-following default vs pinned f32 "
                   "(preferred_element_type)"),
          Axis("epi", ("none", "lrn"),
               doc="FUSE axis: fold the successor LRN unit into the "
                   "bias+activation epilogue (the normalization unit's "
                   "work claimed at the matmul boundary); gated by the "
                   "composed conv_lrn golden")),
    build=_conv_stem_build,
    seed={"pack": "s2d", "acc": "native", "epi": "none"},
    pallas=False, bench_key=_conv_stem_bench_key, fuse_axis="epi",
    doc="strided thin-channel entry conv over packing x accumulator x "
        "LRN-epilogue fusion"))
CONTRACTS["conv_stem"] = _conv_stem_contract
BENCHES["conv_stem"] = _conv_stem_bench


# -- lrn_maxpool: the searched CROSS-OP fusion (ISSUE 13) -------------------
#    LRN and the pooling behind it both stream the same activation; the
#    fused point does both in one VMEM pass (ops/pallas_kernels.py
#    lrn_maxpool_pallas). The op is a PURE fusion op: its candidates
#    compose the (lrn, maxpool) member ops, the search charges a fused
#    candidate against their COMBINED profile share, and FusedTrainStep
#    lets the normalization unit claim its pooling successor's work
#    when a fused winner is selected (the pooling unit passes through
#    for that trace). Every point — composed or fused — is gated by the
#    COMPOSED ops.reference golden (lrn_maxpool_forward/backward).

def _lrn_pool_build(cfg):
    if not cfg["fuse"]:
        # the composed point: exactly the two member lowerings the
        # UNFUSED step would trace (XLA LRN + reduce_window pooling) —
        # the incumbent the fused candidates must beat
        def apply(x, *, k, alpha, beta, n, ksize, stride):
            from veles_tpu.ops import xla as ox
            y = ox.lrn_forward(x, k, alpha, beta, n)
            return ox.maxpool_forward(y, tuple(ksize), tuple(stride),
                                      False)
        apply.fused = False
        return apply

    def apply(x, *, k, alpha, beta, n, ksize, stride):
        from veles_tpu.ops import pallas_kernels as pk
        return pk.lrn_maxpool_pallas(x, k, alpha, beta, n,
                                     tuple(ksize), tuple(stride),
                                     row_tile=cfg["rt"],
                                     io_dtype=cfg["io"])
    apply.fused = True
    return apply


def _lrn_pool_contract(apply):
    import jax
    import numpy as np

    from veles_tpu.ops import reference as ref
    rs = np.random.RandomState(21)
    k, alpha, beta, n = 2.0, 1e-4, 0.75, 5
    ksize, stride = (3, 3), (2, 2)
    # 8x8 exercises the ceil-mode edge window (Hp=9 > 8); 9x9 is exact
    for hw in (8, 9):
        x = rs.randn(2, hw, hw, 16).astype(np.float32)
        y, vjp = jax.vjp(
            lambda xx: apply(xx, k=k, alpha=alpha, beta=beta, n=n,
                             ksize=ksize, stride=stride), x)
        yg = ref.lrn_maxpool_forward(x, k, alpha, beta, n, ksize,
                                     stride)
        np.testing.assert_allclose(np.asarray(y), yg, atol=2e-5,
                                   err_msg=f"hw={hw}")
        g = rs.randn(*yg.shape).astype(np.float32)
        (dx,) = vjp(g)
        np.testing.assert_allclose(
            np.asarray(dx),
            ref.lrn_maxpool_backward(x, g, k, alpha, beta, n, ksize,
                                     stride),
            atol=2e-5, err_msg=f"hw={hw} bwd")
    return {"checked": "fused LRN+maxpool fwd+bwd vs the COMPOSED "
                       "ops.reference golden (ceil-mode edge windows "
                       "included), atol 2e-5"}


def _lrn_pool_bench(apply, repeats):
    import jax
    import jax.numpy as jnp
    shape = (8, 13, 13, 16) if _on_cpu() else (256, 55, 55, 96)
    x = jax.random.normal(jax.random.PRNGKey(8), shape, jnp.float32)

    def fwd_bwd(xx):
        y, vjp = jax.vjp(
            lambda a: apply(a, k=2.0, alpha=1e-4, beta=0.75, n=5,
                            ksize=(3, 3), stride=(2, 2)), xx)
        return y, vjp(y)[0]

    return _time_jitted(fwd_bwd, (x,), repeats)


def _lrn_pool_bench_key(cfg):
    # every fuse=0 point IS the composed incumbent (rt/io are fused-
    # kernel axes): one timing covers them all
    return ("composed",) if not cfg["fuse"] else (cfg["rt"], cfg["io"])


def _lrn_pool_vmem(cfg, shapes, dtype):
    """Fused points block whole (rt, H, W, C) sample bands; the
    backward is the worst direction (x + g in, dx out, double-buffered)
    and the kernel additionally holds two f32 scratch canvases (the
    padded recomputed LRN output, the routed error) plus its live f32
    temporaries — six canvas-sized tiles in all, fitted to what the v5e
    compiler reports (27.76M at rt=2, 55x55x96 bf16). Every tile is
    counted lane-padded (C to 128) and sublane-padded (W to 8 rows of
    f32, 16 of bf16), as Mosaic lays it out. Composed points trace XLA:
    zero Pallas footprint."""
    if not cfg["fuse"]:
        return 0
    h, w, c = shapes.get("h"), shapes.get("w"), shapes.get("c")
    if h is None or w is None or c is None:
        # canonical bench-shape fallback needs the backend; callers
        # passing full shapes (the planner's static gate) must not
        # initialize one
        _, h0, w0, c0 = ((8, 13, 13, 16) if _on_cpu()
                         else (256, 55, 55, 96))
        h, w, c = h or h0, w or w0, c or c0
    h, w, c = int(h), int(w), int(c)
    ky, kx = shapes.get("ksize") or (3, 3)
    sy, sx = shapes.get("stride") or (2, 2)
    from veles_tpu.ops import pallas_kernels as pk
    oh, ow = pk._pool_out_hw(h, w, ky, kx, sy, sx)
    hp, wp = pk._pool_canvas_hw(h, w, ky, kx, sy, sx)
    wd = 4 if cfg["io"] == "f32" else _dtype_width(dtype)
    rt = cfg["rt"]
    c_pad = -(-c // pk._LANE) * pk._LANE

    def tile(hh, ww, width):
        sub = 32 // width               # rows per sublane tile
        return rt * hh * (-(-ww // sub) * sub) * c_pad * width

    return 2 * (2 * tile(h, w, wd) + tile(oh, ow, wd)) \
        + 6 * tile(hp, wp, 4)


register_template(KernelTemplate(
    op="lrn_maxpool", base="fused",
    axes=(Axis("rt", (1, 2, 4, 8),
               doc="SAMPLES per VMEM block (each holds a whole "
                   "(H, W, C) band, so channel and pooling windows "
                   "never cross blocks)"),
          Axis("io", ("native", "f32"),
               doc="HBM staging dtype: caller's dtype (bf16 under the "
                   "fused step) vs f32 blocks"),
          Axis("fuse", (0, 1),
               doc="FUSE axis: 0 = the composed member lowerings (the "
                   "incumbent), 1 = one row-streaming Pallas pass "
                   "doing LRN then maxpool over the same tile")),
    build=_lrn_pool_build,
    seed={"rt": 2, "io": "native", "fuse": 0},
    bench_key=_lrn_pool_bench_key, fuse_axis="fuse",
    fuses=("lrn", "maxpool"), vmem_footprint=_lrn_pool_vmem,
    doc="searched cross-op fusion of the (lrn, maxpool) unit pair — "
        "sample tile x staging dtype x fuse on/off, every point gated "
        "on the composed golden"))
CONTRACTS["lrn_maxpool"] = _lrn_pool_contract
BENCHES["lrn_maxpool"] = _lrn_pool_bench


# -- serve_forward: quantized serving wire (ISSUE 15) -----------------------
#    No template (the wire formats are a closed named family, not a
#    searched space) — but the variants ride the SAME equivalence ledger
#    as every generated kernel: the serving tier refuses to serve a
#    non-f32 wire without a passing record here (veles_tpu/serving.py),
#    exactly as the search refuses to time an ungated candidate.

def _serve_contract(apply):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veles_tpu.ops import reference as ref
    from veles_tpu.ops import variants as va
    cfg = apply.sv_config
    rs = np.random.RandomState(7)
    # hidden width >= the int8 block (64) so the quantized wire's
    # eligibility rule actually quantizes w1 (w2's 4 columns stay f32
    # by the same rule — both branches exercised)
    w1 = (rs.randn(24, 96) * 0.2).astype(np.float32)
    b1 = (rs.randn(96) * 0.1).astype(np.float32)
    w2 = (rs.randn(96, 4) * 0.2).astype(np.float32)
    b2 = (rs.randn(4) * 0.1).astype(np.float32)
    params = ({"weights": w1, "bias": b1}, {"weights": w2, "bias": b2})
    x = rs.randn(8, 24).astype(np.float32)

    def forward(p, xb):
        h = jnp.tanh(xb @ p[0]["weights"] + p[0]["bias"])
        return h @ p[1]["weights"] + p[1]["bias"]

    name = {v["wire"]: k for k, v in va._SERVE_NAMED.items()}[
        cfg["wire"]]
    prepared, shapes = va.serve_prepare_params(name, params)
    if cfg["wire"] == "int8":
        # the host transform must BE the reference quantizer, bitwise —
        # one quantization rule for collectives and serving; a leaf
        # below the block width must pass through UNtouched
        for w, layer in ((w1, prepared[0]), (w2, prepared[1])):
            if w.shape[-1] >= cfg["blk"]:
                qg, sg = ref.serve_quantize_weight(w, cfg["blk"])
                np.testing.assert_array_equal(layer["weights"]["q"], qg)
                np.testing.assert_array_equal(layer["weights"]["s"], sg)
            else:
                np.testing.assert_array_equal(layer["weights"], w)
    out = np.asarray(jax.jit(
        lambda pr, xb: apply(pr, xb, forward, shapes))(prepared, x))
    # golden 1: the SAME wire transform applied through the reference
    # quantizers, forward in numpy — isolates the traced dequant+matmul
    if cfg["wire"] == "int8":
        deq = []
        for (w, b) in ((w1, b1), (w2, b2)):
            if w.shape[-1] >= cfg["blk"]:
                q, s = ref.serve_quantize_weight(w, cfg["blk"])
                w = ref.dequantize_blockwise(q, s, cfg["blk"])[
                    :, :w.shape[-1]].reshape(w.shape)
            deq.append((w, b))
        golden = ref.serve_forward_mlp(x, deq)
        np.testing.assert_allclose(out, golden, rtol=2e-5, atol=2e-5)
    elif cfg["wire"] == "f32":
        golden = ref.serve_forward_mlp(x, ((w1, b1), (w2, b2)))
        np.testing.assert_allclose(out, golden, rtol=2e-5, atol=2e-5)
    # golden 2 (every wire): stay within the serving tolerance of the
    # UNQUANTIZED f32 forward — the bound the serving tier re-probes on
    # the real model before a low-byte variant may serve
    f32 = ref.serve_forward_mlp(x, ((w1, b1), (w2, b2)))
    tol = {"f32": 1e-5, "bf16": 5e-2, "int8": 5e-2}[cfg["wire"]]
    err = float(np.max(np.abs(out - f32)))
    if err > tol:
        raise AssertionError(
            f"serve_forward/{name}: max |out - f32| = {err:.2e} "
            f"exceeds the {tol} serving tolerance")
    return {"checked": f"wire transform bitwise vs ops.reference + "
                       f"forward vs serve_forward_mlp golden; "
                       f"|out - f32| max {err:.2e} <= {tol}"}


CONTRACTS["serve_forward"] = _serve_contract
