"""Jaxpr auditor (analysis pass 2 of 3): audit the fused train step by
ABSTRACT tracing — `jax.make_jaxpr` over the unjitted step callable — so
every property checks on CPU in CI with no compile and no devices.

Rules (docs/ANALYSIS.md):

- `f64-promotion` (error): an op in the traced step produces float64 —
  a weak-type leak above the configured compute dtype that doubles HBM
  traffic and silently de-optimizes the whole chain;
- `precision-above-compute` (warn): matmul/conv ops run in float32 while
  the step is configured for a sub-f32 compute dtype (bf16/f16) — the
  MXU-feeding flops are not actually in the cheap dtype;
- `host-sync` (error): a callback/infeed/outfeed primitive inside the
  hot step (jax.debug.print, pure_callback, ...) forces a host
  round-trip per dispatch;
- `donation-dropped` (error): the step donates its input state, but a
  buffer shaped like a donated state leaf is ALSO captured as a trace
  constant (e.g. a unit reading `self.weights` instead of the `params`
  argument) — XLA keeps the constant copy alive and the donation is
  silently worthless;
- `large-trace-constant` (warn): a large array rides the jaxpr as a
  closure constant — it is re-hashed on every trace and duplicated in
  every executable;
- `retrace-hazard` (warn): the carried state contains Python scalars —
  each step's new value becomes a fresh trace constant, recompiling the
  step every call;
- `sharding-mismatch` (error): a param PartitionSpec names a mesh axis
  that does not exist or shards a dimension the axis size does not
  divide — the exact drift class the PR-2 `out_shardings` pin fixed.
  Covers OPTIMIZER-STATE specs too: a ZeRO-sharded step's velocity/
  moment plan (parallel.mesh.zero_plan) is checked leaf-by-leaf — the
  flat (padded,) vector must be divisible by the data axis, split into
  equal local slices, and must not drop elements of the leaf it encodes.
  Since ISSUE 13 it also covers the FUSED PAIR's traced step: a
  selected cross-op fusion winner (lrn_maxpool) claims an adjacent unit
  pair, and the fused kernel's geometry must equal what the claimed
  pass-through unit declared at initialize time (`_fusion_findings`);
- `nonfinite-guard-off` (warn): the run is configured without the
  non-finite loss guard, so the supervisor's snapshot rollback
  (exit 81) can never trigger on divergence.

Entry points: `audit_fused_step(step, x, y)` for a built
FusedTrainStep / PipelineTrainStep, `audit_workflow(workflow)` to derive
shapes from the workflow's loader, `environment_findings(...)` for the
import-cheap checks the supervisor embeds in its exit report.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np

from veles_tpu.analysis.findings import SEV_ERROR, SEV_WARN, Finding

#: substrings of primitive names that force a host round-trip per step
_HOST_SYNC_MARKERS = ("callback", "debug_print", "infeed", "outfeed")

#: primitives whose flops dominate — the ones `precision-above-compute`
#: watches when a sub-f32 compute dtype is configured
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")

#: consts at least this many elements trigger `large-trace-constant`
LARGE_CONST_ELEMS = 1 << 18

#: consts smaller than this are ignored by the donation check (iota
#: tables, one-hot templates — too small to matter, too common to flag)
_DONATION_MIN_ELEMS = 32


# -- jaxpr walking ------------------------------------------------------------

def _sub_jaxprs(params):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """All equations of `jaxpr` including nested sub-jaxprs (scan/cond/
    pjit bodies), each visited once."""
    stack, seen = [jaxpr], set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            stack.extend(_sub_jaxprs(eqn.params))


# -- individual checks --------------------------------------------------------

def _dtype_findings(closed, compute_dtype) -> List[Finding]:
    out: List[Finding] = []
    f64_prims: dict = {}
    f32_matmuls = 0
    cd = np.dtype(compute_dtype) if compute_dtype is not None \
        else np.dtype(np.float32)
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        for var in eqn.outvars:
            dt = getattr(var.aval, "dtype", None)
            if dt is None:
                continue
            if dt == np.float64:
                f64_prims[name] = f64_prims.get(name, 0) + 1
            elif (cd.itemsize < 4 and dt == np.float32
                    and name in _MATMUL_PRIMS):
                f32_matmuls += 1
    for name, count in sorted(f64_prims.items()):
        out.append(Finding(
            "f64-promotion", SEV_ERROR, name,
            f"{count} op(s) produce float64 above the configured "
            f"compute dtype {cd.name}: a weak-type promotion leak "
            "(2x HBM traffic, no MXU path)"))
    if f32_matmuls:
        out.append(Finding(
            "precision-above-compute", SEV_WARN, "dot/conv",
            f"{f32_matmuls} matmul/conv op(s) run in float32 while the "
            f"step is configured for {cd.name}: the dominant flops are "
            "not in the cheap dtype"))
    return out


def _host_sync_findings(closed) -> List[Finding]:
    hits: dict = {}
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if any(m in name for m in _HOST_SYNC_MARKERS):
            hits[name] = hits.get(name, 0) + 1
    return [Finding(
        "host-sync", SEV_ERROR, name,
        f"{count} {name} op(s) in the hot step force a host round-trip "
        "per dispatch (debug_print/pure_callback do not belong in the "
        "train step)") for name, count in sorted(hits.items())]


def _const_findings(closed, state, donate: bool) -> List[Finding]:
    out: List[Finding] = []
    leaves = []
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(state)
    except Exception:   # noqa: BLE001
        pass
    leaf_sigs = {(np.shape(a), np.dtype(getattr(a, "dtype", "f4")).name)
                 for a in leaves if np.ndim(a) >= 1}
    for c in closed.consts:
        shape = np.shape(c)
        if len(shape) < 1 or int(np.prod(shape)) < _DONATION_MIN_ELEMS:
            continue
        dt = np.dtype(getattr(c, "dtype", np.asarray(c).dtype)).name
        site = f"const {dt}{list(shape)}"
        identical = any(c is a for a in leaves)
        if donate and (identical or (shape, dt) in leaf_sigs):
            out.append(Finding(
                "donation-dropped", SEV_ERROR, site,
                "a buffer shaped like a donated state leaf is captured "
                "as a trace constant (a unit reading its own Array "
                "instead of the params argument?): XLA keeps the "
                "constant copy alive and the donation is silently "
                "dropped"))
        elif int(np.prod(shape)) >= LARGE_CONST_ELEMS:
            out.append(Finding(
                "large-trace-constant", SEV_WARN, site,
                "a large array rides the jaxpr as a closure constant: "
                "duplicated per executable and re-hashed per trace — "
                "pass it as an argument instead"))
    return out


def _state_findings(state) -> List[Finding]:
    out: List[Finding] = []
    try:
        import jax
        from jax.tree_util import keystr, tree_flatten_with_path
        pairs = [(keystr(kp), v)
                 for kp, v in tree_flatten_with_path(state)[0]]
    except Exception:   # noqa: BLE001
        import jax
        pairs = [("", v) for v in jax.tree_util.tree_leaves(state)]
    for name, v in pairs:
        if isinstance(v, (bool, int, float)):
            out.append(Finding(
                "retrace-hazard", SEV_WARN, f"state{name}",
                f"carried state leaf is a Python {type(v).__name__}: "
                "every new value becomes a fresh trace constant and "
                "recompiles the step (wrap it in jnp.asarray)"))
    return out


def _spec_axes(part) -> Sequence[str]:
    if part is None:
        return ()
    return (part,) if isinstance(part, str) else tuple(part)


def _sharding_findings(step) -> List[Finding]:
    """Check the step's param PartitionSpecs against its mesh — the
    static form of the PR-2 sharding-drift bug class."""
    mesh = getattr(step, "mesh", None)
    mode = getattr(step, "mode", None)
    if mesh is None or mode not in ("gspmd", "dp", "seq"):
        return []
    if mode == "gspmd":
        specs, _ = step._tp_plan()
    elif mode == "dp":
        specs = step._smap_param_specs()
    else:
        specs = step._seq_param_specs()
    out: List[Finding] = []
    for u, spec_d in zip(step.forwards, specs):
        arrs = u.param_arrays()
        for k, spec in spec_d.items():
            shape = tuple(getattr(arrs.get(k), "shape", None) or ())
            site = f"{getattr(u, 'name', u)}.{k} {tuple(spec)!r}"
            for i, part in enumerate(tuple(spec)):
                axes = _spec_axes(part)
                if not axes:
                    continue
                if i >= len(shape):
                    out.append(Finding(
                        "sharding-mismatch", SEV_ERROR, repr(u),
                        f"PartitionSpec for param {k!r} shards dim {i} "
                        f"but the array has rank {len(shape)}", site))
                    continue
                for ax in axes:
                    if ax not in mesh.shape:
                        out.append(Finding(
                            "sharding-mismatch", SEV_ERROR, repr(u),
                            f"PartitionSpec for param {k!r} names mesh "
                            f"axis {ax!r}, which the mesh "
                            f"{dict(mesh.shape)} does not have", site))
                    elif shape[i] % mesh.shape[ax]:
                        out.append(Finding(
                            "sharding-mismatch", SEV_ERROR, repr(u),
                            f"param {k!r} dim {i} ({shape[i]}) is not "
                            f"divisible by mesh axis {ax!r} "
                            f"({mesh.shape[ax]} shards): XLA would "
                            "pad-shard or reject it", site))
    out += _optstate_findings(step, mesh)
    out += _collective_findings(step, mesh)
    return out


def _collective_findings(step, mesh) -> List[Finding]:
    """Link-geometry half of the sharding audit (ISSUE 12): the
    hierarchical grad_reduce variants decompose the data axis into a
    (hosts x local) 2-level factorization. An EXPLICIT local-group
    request (env VELES_GRAD_REDUCE_LOCAL) that does not divide the
    data axis is a config bug — the traced op degrades safely to the
    flat exchange, but the user asked for a two-level decomposition
    that cannot tile, so this pass fails loud pre-flight; a merely
    degenerate geometry (single host) gets a warning, not an error."""
    if not getattr(step, "zero_active", False):
        return []
    import os

    from veles_tpu.ops import variants as va
    from veles_tpu.parallel.mesh import DATA_AXIS
    name = step._grad_reduce_variant().name
    cfg = va.grad_reduce_config(name) or {}
    if not cfg.get("hier"):
        return []
    n = mesh.shape.get(DATA_AXIS, 1)
    out: List[Finding] = []
    raw = os.environ.get(va.GRAD_REDUCE_LOCAL_ENV)
    site = f"grad_reduce/{name} over {DATA_AXIS!r} ({n} shards)"
    if raw is not None:
        try:
            req = int(raw)
        except ValueError:
            req = 0
        if req < 1 or n % req:
            h, loc = va.grad_reduce_geometry(n)
            out.append(Finding(
                "sharding-mismatch", SEV_ERROR, "grad_reduce",
                f"hierarchical grad_reduce local-group request "
                f"{raw!r} ({va.GRAD_REDUCE_LOCAL_ENV}) does not divide "
                f"the data axis ({n} shards): the requested "
                f"(hosts x local) decomposition cannot tile it, so the "
                f"traced op silently clamps to the largest divisor and "
                f"runs ({h} x {loc}) instead — a DIFFERENT "
                f"decomposition than asked for; fix the override or "
                f"the mesh", site))
            return out
    h, loc = va.grad_reduce_geometry(n)
    if h <= 1 or loc <= 1:
        out.append(Finding(
            "sharding-mismatch", SEV_WARN, "grad_reduce",
            f"hierarchical grad_reduce variant selected but the link "
            f"geometry is single-level (hosts={h}, local={loc}): the "
            f"traced op degrades to the flat exchange here — expected "
            f"on a single host; set {va.GRAD_REDUCE_LOCAL_ENV} to test "
            f"the two-level path on a CPU mesh", site))
    return out


def audit_serving(server) -> List[Finding]:
    """Sharded-serve audit (ISSUE 15): the ring server's forward must
    trace under the TRAINER'S NamedSharding plan — run the
    sharding-mismatch pass over the serving step's param specs/mesh,
    and check the serve plan's ring input spec equals the step's
    data-axis put spec (the same spec DeviceFeed puts training batches
    to) and that the frozen ring shape divides the data axis. Empty
    list = clean; merge-mode servers (the unsharded pre-ring baseline)
    have nothing to audit."""
    from veles_tpu.parallel.mesh import DATA_AXIS
    out: List[Finding] = []
    step = getattr(server, "_step", None)
    plan = getattr(server, "_plan", None)
    if step is None or plan is None:
        return out
    out += _sharding_findings(step)
    mesh = plan["mesh"]
    if mesh is None:
        return out
    want = step.input_put_specs()[0]
    site = f"serve_plan x_spec {tuple(plan['x_spec'])!r}"
    if tuple(plan["x_spec"]) != tuple(want):
        out.append(Finding(
            "sharding-mismatch", SEV_ERROR, "serving",
            f"ring input spec {tuple(plan['x_spec'])} diverges from "
            f"the trainer's data-axis put spec {tuple(want)} "
            f"(input_put_specs — the DeviceFeed rule)", site))
    n = mesh.shape.get(DATA_AXIS, 1)
    slots = server.ring_slots or 0
    if n > 1 and slots % n:
        out.append(Finding(
            "sharding-mismatch", SEV_ERROR, "serving",
            f"ring_slots ({slots}) not divisible by the mesh data axis "
            f"({n} shards): the fixed ring batch cannot lay out under "
            f"the plan", site))
    return out


def _fusion_findings(step) -> List[Finding]:
    """Fused-pair half of the sharding-mismatch audit (ISSUE 13): when a
    selected fusion winner claims an adjacent unit pair, the trailing
    unit becomes a pass-through — so the fused kernel must reproduce
    EXACTLY the geometry that unit declared at initialize time (its
    output Array shape, which every downstream layer sized its params
    against). A post-init reconfiguration (ksize/stride edited on the
    live unit) silently drifts the two apart: the fused trace would feed
    downstream layers a differently-shaped tensor than the one their
    weights were built for. Runs mesh or no mesh — the fusion claim is
    mode-gated inside fusion_pairs() itself."""
    pairs_fn = getattr(step, "fusion_pairs", None)
    if pairs_fn is None:
        return []
    out: List[Finding] = []
    for i, j, v in pairs_fn():
        a, b = step.forwards[i], step.forwards[j]
        if getattr(a, "variant_op", None) != "lrn":
            # conv epilogue: elementwise fold, geometry untouched —
            # the claimed LRN unit's output shape equals its input's
            continue
        in_shape = tuple(getattr(getattr(a, "input", None), "shape",
                                 ()) or ())
        decl = tuple(getattr(getattr(b, "output", None), "shape",
                             ()) or ())
        if len(in_shape) != 4 or len(decl) != 4:
            continue
        from veles_tpu.ops.pallas_kernels import _pool_out_hw
        ky, kx = b.ksize
        sy, sx = b.stride
        oh, ow = _pool_out_hw(in_shape[1], in_shape[2], ky, kx, sy, sx)
        traced = (in_shape[0], oh, ow, in_shape[3])
        site = (f"{getattr(a, 'name', a)}+{getattr(b, 'name', b)} "
                f"-> {v.name}")
        if traced != decl:
            out.append(Finding(
                "sharding-mismatch", SEV_ERROR, repr(b),
                f"fused pair {v.name!r} would trace a "
                f"{traced} output where the claimed pass-through "
                f"pooling unit declared {decl}: the pair's geometry "
                "drifted after initialize (ksize/stride edited on the "
                "live unit?) — downstream layers would consume a "
                "silently different tensor", site))
    return out


def _optstate_findings(step, mesh) -> List[Finding]:
    """Optimizer-state half of the sharding audit: a ZeRO-sharded step
    carries its velocities/Adam moments as flat vectors split over the
    data axis per the update-sharding plan. These checks guard the
    PLAN CACHE (step._zero_plan_cache) — the mutable handoff every
    consumer (specs, init, the traced update, checkpoint geometry)
    reads — against a corrupted/stale entry; a freshly computed plan
    satisfies them by construction, so the independent ledger is the
    LIVE state cross-check in `_optstate_state_findings` (what a
    restore or caller actually handed the step)."""
    if not getattr(step, "zero_active", False):
        return []
    from veles_tpu.parallel.mesh import DATA_AXIS
    n = mesh.shape.get(DATA_AXIS, 1)
    out: List[Finding] = []
    for u, plan in zip(step.forwards, step.zero_plans()):
        for k, lp in plan.items():
            site = (f"{getattr(u, 'name', u)}.vel[{k}] "
                    f"({lp.padded},) over {DATA_AXIS!r}")
            if lp.padded % n:
                out.append(Finding(
                    "sharding-mismatch", SEV_ERROR, repr(u),
                    f"optimizer-state leaf {k!r} plans {lp.padded} "
                    f"elements, not divisible by the data axis "
                    f"({n} shards): the reduce-scatter/all-gather pair "
                    "cannot tile it", site))
            elif lp.local * n != lp.padded:
                out.append(Finding(
                    "sharding-mismatch", SEV_ERROR, repr(u),
                    f"optimizer-state leaf {k!r} plans local slices of "
                    f"{lp.local} x {n} shards != {lp.padded} padded "
                    "elements: shards would overlap or leave gaps",
                    site))
            if lp.padded < lp.size:
                out.append(Finding(
                    "sharding-mismatch", SEV_ERROR, repr(u),
                    f"optimizer-state leaf {k!r} plans only {lp.padded} "
                    f"elements for a {lp.size}-element leaf: the "
                    "update would silently drop the tail", site))
    return out


def _optstate_state_findings(step, state) -> List[Finding]:
    """Cross-check the LIVE optimizer state against the update-sharding
    plan — the independent ledger for the plan checks above: the plan
    is what the step will trace, the state is what `init_state()`, a
    checkpoint restore, or the caller actually handed it. A velocity /
    moment leaf whose stored geometry disagrees with the plan (wrong
    flat length) would dynamic-slice out of bounds or drop tail
    elements at update time."""
    if not getattr(step, "zero_active", False):
        return []
    vel = state.get("vel") if isinstance(state, dict) else None
    if vel is None:
        return []
    from veles_tpu.ops import optim
    out: List[Finding] = []
    cfgs = getattr(step, "cfgs", None) or [None] * len(step.forwards)
    for u, plan, v, cfg in zip(step.forwards, step.zero_plans(), vel,
                               cfgs):
        if isinstance(cfg, optim.AdamConfig):
            groups = (("m", v.get("m", {})), ("v", v.get("v", {})))
        else:
            groups = (("", v),)
        for gname, leaves in groups:
            if not isinstance(leaves, dict):
                continue
            for k, lp in plan.items():
                leaf = leaves.get(k)
                if leaf is None:
                    continue
                shape = tuple(np.shape(leaf))
                label = f"{gname}.{k}" if gname else k
                if shape != (lp.padded,):
                    out.append(Finding(
                        "sharding-mismatch", SEV_ERROR, repr(u),
                        f"optimizer-state leaf {label!r} carries shape "
                        f"{shape}, but the update-sharding plan slices "
                        f"a ({lp.padded},) flat vector (leaf "
                        f"{lp.shape}, {lp.size} elements): the state "
                        "does not match the plan it will be updated "
                        "under",
                        f"{getattr(u, 'name', u)}.vel[{label}]"))
    out += _ef_state_findings(step, state)
    return out


def _ef_state_findings(step, state) -> List[Finding]:
    """Error-feedback-slot half of the live-state cross-check (ISSUE
    12): a stateful (int8+EF) grad_reduce variant carries one flat
    residual vector per param leaf, sized by the variant's rule
    (ops.variants.grad_reduce_resid_len x data-axis shards). A residual
    whose stored length disagrees — e.g. a checkpoint hand-carried
    across a (hosts x local) geometry change — would be reshaped onto
    the WRONG elements and compensate them forever: mis-sharded, the
    exact failure the reshard path's drop rule exists to prevent."""
    if not getattr(step, "ef_active", lambda: False)():
        return []
    from veles_tpu.parallel.mesh import DATA_AXIS
    n = step.mesh.shape.get(DATA_AXIS, 1)
    ef = state.get("ef") if isinstance(state, dict) else None
    out: List[Finding] = []
    if ef is None:
        out.append(Finding(
            "sharding-mismatch", SEV_ERROR, "grad_reduce",
            "the selected grad_reduce variant is stateful (error "
            "feedback) but the state carries no 'ef' slot: the traced "
            "update would have no residual to thread (rebuild the "
            "state via init_state()/restore_state())", "state[ef]"))
        return out
    for u, lens, layer in zip(step.forwards, step.ef_lens(), ef):
        if not isinstance(layer, dict):
            continue
        for k, rl in lens.items():
            leaf = layer.get(k)
            if leaf is None:
                continue
            shape = tuple(np.shape(leaf))
            if shape != (n * rl,):
                out.append(Finding(
                    "sharding-mismatch", SEV_ERROR, repr(u),
                    f"error-feedback residual {k!r} carries shape "
                    f"{shape}, but the selected grad_reduce variant "
                    f"slices ({n * rl},) ({n} shards x {rl} per-shard "
                    f"elements): a mis-sized residual would compensate "
                    f"the wrong gradient elements",
                    f"{getattr(u, 'name', u)}.ef[{k}]"))
    return out


# -- entry points -------------------------------------------------------------

def audit_fused_step(step, x, y, w=None, state=None,
                     nonfinite_guard: Optional[bool] = None
                     ) -> List[Finding]:
    """Audit a built FusedTrainStep (any mode) or PipelineTrainStep by
    tracing its unjitted train callable over the given minibatch. `x`/`y`
    are host arrays with the real shapes (values are irrelevant); `state`
    defaults to `step.init_state()`. No compile happens — `make_jaxpr`
    only traces."""
    import jax

    findings: List[Finding] = []
    sharding = _sharding_findings(step)
    sharding += _fusion_findings(step)   # fused-pair geometry (any mode)
    findings += sharding
    if any(f.severity == SEV_ERROR for f in sharding):
        # a broken partition plan (or a drifted fused-pair geometry):
        # building state / tracing would crash on the very defect just
        # reported — stop at the static verdict
        return findings
    mesh = getattr(step, "mesh", None)
    is_pipeline = hasattr(step, "_microbatch")
    if nonfinite_guard is not None and not nonfinite_guard:
        findings.append(_guard_off_finding())

    if state is None:
        state = step.init_state()
    findings += _state_findings(state)
    optstate = _optstate_state_findings(step, state)
    findings += optstate
    if any(f.severity == SEV_ERROR for f in optstate):
        # state geometry disagrees with the plan the trace would slice
        # under — tracing would crash on (or worse, silently mask) the
        # defect just reported
        return findings

    x = np.asarray(x)
    y = np.asarray(y)
    if w is None:
        w = np.ones(np.shape(x)[0], np.float32)
    if is_pipeline:
        xs, yb, wb = step._microbatch(x, y, w)
        args = (state, step._gid, xs, yb, wb)
    else:
        xb, yb = step._seq_xy(x, y)
        args = (state, xb, yb,
                step._weights_or_ones(np.asarray(w, np.float32),
                                      np.shape(x)[0]))
    closed = jax.make_jaxpr(step.train_callable())(*args)
    findings += _dtype_findings(closed, getattr(step, "compute_dtype",
                                                None))
    findings += _host_sync_findings(closed)
    findings += _const_findings(closed, state,
                                bool(getattr(step, "donate", False)))
    return findings


def audit_workflow(workflow, step=None,
                   nonfinite_guard: Optional[bool] = None,
                   **step_kwargs) -> List[Finding]:
    """Build (or take) a fused step for `workflow` and audit it with the
    loader's real minibatch shapes. Initializes the workflow on the
    default backend when needed (host-side allocation only)."""
    if not workflow.is_initialized:
        workflow.initialize(device=None, verify="off")
    if step is None:
        step = workflow.build_fused_step(**step_kwargs)
    loader = workflow.loader
    x = np.asarray(loader.minibatch_data.mem)
    y = np.asarray(loader.minibatch_labels.mem)
    w = loader.minibatch_valid.mem
    w = (np.asarray(w, np.float32) if w is not None
         else np.ones(x.shape[0], np.float32))
    return audit_fused_step(step, x, y, w=w,
                            nonfinite_guard=nonfinite_guard)


# -- environment findings (supervisor exit report, --verify-workflow) ---------

def _guard_off_finding() -> Finding:
    return Finding(
        "nonfinite-guard-off", SEV_WARN, "training loop",
        "running without --nonfinite-guard: a NaN/inf loss trains on "
        "and the supervisor's snapshot rollback (exit 81) never "
        "triggers")


def _flag_value(argv: Sequence[str], flag: str) -> Optional[str]:
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def environment_findings(argv: Optional[Sequence[str]] = None,
                         pp: Optional[int] = None,
                         tp: Optional[int] = None,
                         sp: Optional[int] = None,
                         nonfinite_guard: Optional[bool] = None
                         ) -> List[Finding]:
    """Config-level findings derivable WITHOUT building a step: the
    disabled non-finite guard. Accepts either explicit flag values or a
    child argv to parse them from (the supervisor passes its child
    command line)."""
    argv = list(argv or ())

    def parsed(flag: str) -> Optional[int]:
        raw = _flag_value(argv, flag)
        if raw is None:
            return None
        try:
            return int(raw)
        except ValueError:
            return 1    # present but unparsable: treat as enabled

    if argv:
        if pp is None:
            pp = parsed("--pp")
        if tp is None:
            tp = parsed("--tp")
        if sp is None:
            sp = parsed("--sp")
        if nonfinite_guard is None:
            nonfinite_guard = ("--nonfinite-guard" in argv
                               or "--debug-nans" in argv)
    out: List[Finding] = []
    if nonfinite_guard is not None and not nonfinite_guard:
        out.append(_guard_off_finding())
    return out
