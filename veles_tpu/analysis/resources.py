"""Static resource analyzer (analysis pass 6): memory, the resource
that actually bounds a TPU-native VELES.

Two ledgers over the shared `Finding` stream — the first analysis pass
whose findings feed the PERF machinery (the kernel search, the launcher,
serving capacity), not just CI:

1. **Kernel VMEM model.** Every generated Pallas point (ops/templates.py)
   carries a declarative `vmem_footprint(config, shapes, dtype)` rule —
   double-buffered in/out block bytes plus scratch, derived from the
   kernel's BlockSpecs in ops/pallas_kernels.py. Against the per-
   `device_kind` VMEM budget table below, an over-budget point is
   statically INFEASIBLE: the budgeted search (`ops.autotune.search_op`)
   skips it without timing it or burning budget (trial outcome
   ``pruned``), `_timed_trial` structurally refuses to time one
   (`InfeasibleCandidateError` — the `UngatedCandidateError` twin), and
   `apply_cached` refuses a cached winner whose footprint no longer fits
   the current device_kind. A candidate that would only fail minutes
   into an on-chip compile is rejected before a single trial
   (arxiv 2512.10977's "reject infeasible candidates before evaluation";
   arxiv 2203.04015's static pre-compile resource fitting).

2. **Workflow HBM model.** Params + the transient full-size gradient +
   the ZeRO-planned optimizer flat vectors (incl. the optional `ef`
   residual slot, 1/N per `mesh.zero_plan`) + an activation high-water
   estimate from a liveness walk over the UNJITTED `train_callable()`
   jaxpr + the DeviceFeed double-buffer batch bytes — resolved per
   device under the mesh plan and compared against the memstats device
   limit. Surfaced via ``--verify-workflow=resources``, the Launcher
   pre-flight in `_run_with_step` (warn at >80% of the limit, error
   above it with a per-component byte breakdown), bench records
   (``"memory"``), the supervisor exit report (predicted-vs-measured
   delta) and the serving ``/healthz`` capacity hint.

Two predicted numbers per device, because two different measurements
exist: ``resident`` (params + optimizer state + ef + feed batches — what
`jax.live_arrays()` sees between steps) and ``highwater`` (resident +
the traced step's liveness peak — what the allocator's
`peak_bytes_in_use` OOMs on). CPU meshes measure the first, TPUs the
second; predicted-vs-measured comparisons pair them accordingly.

Known blind spots (documented, not hidden): XLA fusion slack (the walk
counts jaxpr values, XLA fuses many away and materializes some
rematerializations instead), compute-dtype cast copies, gspmd TP param
sharding (params are modeled replicated), and in-kernel Pallas
temporaries beyond the declared blocks. The 25% acceptance tolerance
(tests/test_resources.py) is the empirical bound on the CPU mesh.

No jax at module scope: the budget tables and footprint parsing are
importable by jax-free consumers; every traced/measured path imports
lazily.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from veles_tpu.analysis.findings import SEV_ERROR, SEV_WARN, Finding

__all__ = [
    "SCOPED_VMEM_LIMIT", "VMEM_BUDGETS", "VMEM_BUDGET_ENV", "HBM_LIMIT_ENV",
    "InfeasibleCandidateError", "ResourcePreflightError",
    "vmem_budget", "device_limit", "kernel_footprint", "kernel_verdict",
    "shapes_from_signatures", "kernel_findings", "step_resource_report",
    "workflow_resource_findings", "preflight", "serving_capacity",
]

_log = logging.getLogger("veles.resources")

#: env override for the per-device VMEM budget (bytes) — `tools/
#: autotune.py --vmem-budget` sets it for what-if runs; tests pin it
VMEM_BUDGET_ENV = "VELES_VMEM_BUDGET"
#: env override for the per-device HBM limit (bytes) — CPU meshes have
#: no allocator limit, so tests/what-if runs pin one here
HBM_LIMIT_ENV = "VELES_HBM_LIMIT"
#: env gate: force the full (traced) pre-flight even with no known
#: device limit (the static resident model always runs)
PREFLIGHT_ENV = "VELES_RESOURCE_PREFLIGHT"

#: the limit the kernels in ops/pallas_kernels.py compile under: their
#: pallas_calls pass no `vmem_limit_bytes` (the four hyper-connection
#: kernels aside, which ask for `_HC_VMEM_LIMIT` and are no tuning axis),
#: so Mosaic holds each one
#: to its DEFAULT scoped-VMEM limit, whatever the chip's physical VMEM
#: (128 MiB on a v5e). Asked of the v5e compiler in PR 21 ("Scoped
#: allocation with size 18.83M and limit 16.00M exceeded scoped vmem
#: limit"). ONE number: the kernels' own block rule
#: (pallas_kernels.lrn_view) and the search's pruning both read it.
SCOPED_VMEM_LIMIT = 16 << 20

#: per-device_kind VMEM budget (bytes) a Pallas kernel's resident blocks
#: AND in-kernel temporaries must fit in — the compiler's scoped limit
#: above, not the physical VMEM. Confirmed against the compiler for
#: "TPU v5 lite" only; the other kinds carry the same default because
#: the kernels pass no limit of their own. Unknown kinds (CPU interpret
#: mode, GPUs) get None: no static budget, pruning inactive unless the
#: env override supplies one.
VMEM_BUDGETS: Dict[str, int] = {
    kind: SCOPED_VMEM_LIMIT
    for kind in ("TPU v2", "TPU v3", "TPU v4", "TPU v4 lite", "TPU v5",
                 "TPU v5p", "TPU v5 lite", "TPU v5e", "TPU v6 lite",
                 "TPU v6e", "TPU v7x")}

#: pre-flight warning threshold: predicted high-water above this
#: fraction of the device limit warns (above 1.0 errors)
NEAR_LIMIT_FRAC = 0.8


class InfeasibleCandidateError(RuntimeError):
    """Raised when something tries to TIME a generated candidate whose
    static VMEM footprint exceeds the device budget — the structural
    twin of templates.UngatedCandidateError: pruning is a hard gate,
    not a convention the search could drift past."""


class ResourcePreflightError(RuntimeError):
    """Predicted per-device high-water exceeds the device memory limit.
    Carries the full report so the launcher can print the per-component
    byte breakdown instead of an opaque 'would OOM'."""

    def __init__(self, message: str, report: Dict[str, Any]) -> None:
        super().__init__(message)
        self.report = report


# ===========================================================================
# Ledger 1: kernel VMEM footprints vs the device budget
# ===========================================================================


def vmem_budget(device_kind: Optional[str] = None,
                override: Optional[int] = None) -> Optional[int]:
    """The per-device VMEM budget (bytes) for `device_kind`, or None
    when no static budget exists (CPU interpret mode, unknown kinds).
    `override` (tools/autotune.py --vmem-budget) wins, then the env
    override, then the table."""
    if override is not None:
        return int(override)
    env = os.environ.get(VMEM_BUDGET_ENV)
    if env:
        try:
            return int(env)
        except ValueError:
            _log.warning("%s=%r is not an integer byte count; ignoring",
                         VMEM_BUDGET_ENV, env)
    if device_kind is None:
        return None
    return VMEM_BUDGETS.get(device_kind)


def _parse_point(op: str, name: Any):
    """(template, config) for a generated-variant NAME, or None for
    hand-written / foreign names (those carry no declarative footprint
    and are never pruned)."""
    from veles_tpu.ops import templates
    if not isinstance(name, str):
        return None
    for t in templates.templates_for(op):
        cfg = t.parse(name)
        if cfg is not None:
            return t, cfg
    return None


def kernel_footprint(op: str, name: Any,
                     shapes: Optional[Dict[str, Any]] = None,
                     dtype: Any = None) -> Optional[int]:
    """Static VMEM residency (bytes) of the named generated point at
    `shapes` (op-specific dims; missing keys fall back to the rule's
    canonical bench shapes — exactly what the microbench would run).
    None when the name is no template point or its template declares no
    footprint rule (non-Pallas ops): unknown is never pruned."""
    parsed = _parse_point(op, name)
    if parsed is None:
        return None
    t, cfg = parsed
    if t.vmem_footprint is None:
        return None
    return int(t.vmem_footprint(cfg, dict(shapes or {}), dtype))


def kernel_verdict(op: str, name: Any,
                   shapes: Optional[Dict[str, Any]] = None,
                   dtype: Any = None,
                   device_kind: Optional[str] = None,
                   budget: Optional[int] = None
                   ) -> Optional[Dict[str, Any]]:
    """None when the point fits (or nothing is known about it);
    otherwise {"footprint": bytes, "vmem_budget": bytes} — the ONE
    infeasibility rule the search's prune branch, `_timed_trial`'s hard
    gate and `apply_cached`'s refusal all share."""
    b = vmem_budget(device_kind, override=budget)
    if b is None:
        return None
    f = kernel_footprint(op, name, shapes=shapes, dtype=dtype)
    if f is None or f <= b:
        return None
    return {"footprint": f, "vmem_budget": b}


def shapes_from_signatures(op: str, sigs) -> Dict[str, Any]:
    """Footprint `shapes` for a workflow op from its autotune
    signatures (discover_tunables/discover_fusions payloads) — the
    WORST (largest) instance wins, since one registry selection covers
    every instance of the op."""
    out: Dict[str, Any] = {}
    worst_band = 0
    for sig in sigs or ():
        if not isinstance(sig, dict):
            continue
        if op == "lrn_maxpool":
            # the pair signature joins both members: the LRN side
            # carries the activation geometry, the POOLING side the
            # window/stride the fused kernel would run — worst case =
            # the largest window with the smallest stride (biggest
            # padded recompute canvas)
            pool = (sig.get("maxpool") or {}).get("params") or {}
            if pool.get("ksize"):
                ks = tuple(int(v) for v in pool["ksize"])
                prev = out.get("ksize")
                out["ksize"] = ks if prev is None else \
                    tuple(max(a, b) for a, b in zip(prev, ks))
            if pool.get("stride"):
                st = tuple(int(v) for v in pool["stride"])
                prev = out.get("stride")
                out["stride"] = st if prev is None else \
                    tuple(min(a, b) for a, b in zip(prev, st))
            sig = sig.get("lrn") or {}
        ss = sig.get("sample_shape")
        if op == "lrn_maxpool" and ss and len(ss) == 3:
            # the fused kernel blocks whole (H, W, C) bands: the worst
            # instance is the largest lane-padded band, kept together
            # (a dim-wise max would price a band no layer has)
            h, w, c = (int(v) for v in ss)
            vol = h * w * (-(-c // 128) * 128)
            if vol > worst_band:
                worst_band = vol
                out.update(h=h, w=w, c=c)
        elif op == "lrn_maxpool" and ss:
            out["c"] = max(out.get("c", 0), int(ss[-1]))
        elif op == "flash_attn" and ss:
            out["s"] = max(out.get("s", 0), int(ss[0]))
            if sig.get("head_dim"):
                out["d"] = max(out.get("d", 0), int(sig["head_dim"]))
    return out


def kernel_findings(workflow=None,
                    sigs: Optional[Dict[str, List[Dict]]] = None,
                    device_kind: Optional[str] = None,
                    budget: Optional[int] = None,
                    dtype: Any = None) -> List[Finding]:
    """`vmem-over-budget` findings for every template op whose CURRENT
    registry selection is a generated point that cannot fit the device
    budget — the pass-6 form of 'this tree would fail at compile time
    on-chip'. Clean when no budget is known (pruning inactive) or every
    selection fits."""
    from veles_tpu.ops import templates, variants
    if sigs is None and workflow is not None:
        from veles_tpu.ops.autotune import (discover_fusions,
                                            discover_tunables)
        sigs = dict(discover_tunables(workflow))
        sigs.update(discover_fusions(workflow))
    out: List[Finding] = []
    for op in templates.template_ops():
        name = variants.effective(op)
        shapes = shapes_from_signatures(op, (sigs or {}).get(op))
        ver = kernel_verdict(op, name, shapes=shapes, dtype=dtype,
                             device_kind=device_kind, budget=budget)
        if ver is None:
            continue
        out.append(Finding(
            "vmem-over-budget", SEV_ERROR, f"{op}/{name}",
            f"selected generated point needs {ver['footprint']} B of "
            f"VMEM (double-buffered blocks + scratch at "
            f"{shapes or 'bench shapes'}) but the "
            f"{device_kind or 'configured'} budget is "
            f"{ver['vmem_budget']} B: the kernel would fail at compile "
            f"time on-chip — re-run the search (it prunes this point) "
            f"or pick a smaller tile",
            f"footprint {ver['footprint']}/{ver['vmem_budget']} B"))
    return out


# ===========================================================================
# Ledger 2: workflow HBM model vs the device memory limit
# ===========================================================================


def device_limit(limit: Optional[int] = None) -> Optional[int]:
    """Per-device HBM limit in bytes: explicit arg, env override
    (VELES_HBM_LIMIT — CPU meshes report no allocator limit), else the
    smallest `bytes_limit` the backend reports (parallel.memstats).
    None when nothing is known — the comparison half of the pass then
    degrades to a pure report."""
    if limit is not None:
        return int(limit)
    env = os.environ.get(HBM_LIMIT_ENV)
    if env:
        try:
            return int(env)
        except ValueError:
            _log.warning("%s=%r is not an integer byte count; ignoring",
                         HBM_LIMIT_ENV, env)
    from veles_tpu.parallel.memstats import device_memory_limits
    limits = device_memory_limits()
    return min(limits.values()) if limits else None


def _aval_bytes(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    if shape is None or dt is None:
        return 0
    try:
        width = np.dtype(dt).itemsize
    except TypeError:
        # extended dtypes (PRNG key avals) — itemsize when they expose
        # one, else a nominal word (they are tiny either way)
        width = int(getattr(dt, "itemsize", 4) or 4)
    return int(np.prod(shape, dtype=np.int64)) * width


def _liveness_highwater(jaxpr) -> int:
    """Peak bytes of eqn-produced values simultaneously live in one
    jaxpr — a topological liveness walk (def at the producing eqn, death
    after the last consumer; jaxpr outputs live to the end). Nested
    sub-jaxprs (scan/cond/pjit/shard_map bodies) contribute their own
    peak at the owning eqn — inside a dp-mode shard_map the shapes are
    already per-shard, so the estimate lands per DEVICE. Inputs and
    consts are excluded: the caller accounts them as the resident set
    (params, batch), so the walk measures exactly the transient step
    state (activations, grads, the new state before the old one dies)."""
    from veles_tpu.analysis.trace import _sub_jaxprs
    eqns = list(jaxpr.eqns)
    n = len(eqns)
    death: Dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "aval") or type(v).__name__ == "Literal":
                continue
            death[v] = i
    for v in jaxpr.outvars:
        if type(v).__name__ != "Literal":   # a constant output: no def
            death[v] = n
    alive: Dict[Any, int] = {}
    peak = 0
    for i, eqn in enumerate(eqns):
        inner = 0
        for sub in _sub_jaxprs(eqn.params):
            inner += _liveness_highwater(sub)
        out_b = sum(_aval_bytes(v) for v in eqn.outvars
                    if type(v).__name__ != "DropVar")
        peak = max(peak, sum(alive.values()) + inner + out_b)
        for v in eqn.outvars:
            if type(v).__name__ == "DropVar":
                continue
            if death.get(v, -1) > i:
                alive[v] = _aval_bytes(v)
        for v in eqn.invars:
            if type(v).__name__ == "Literal":
                continue
            if v in alive and death.get(v) == i:
                del alive[v]
    return peak


def _static_profile(step) -> Dict[str, Any]:
    """The step's static per-device component bytes: the FusedTrainStep
    publishes its own (`resource_profile` — params/grads/opt/ef under
    the ZeRO plan); anything else (pipeline steps) degrades to a
    params-derived model."""
    prof = getattr(step, "resource_profile", None)
    if prof is not None:
        return prof()
    params = 0
    for u in getattr(step, "forwards", ()):
        for a in u.param_arrays().values():
            if a:
                arr = np.asarray(a.mem)
                params += int(arr.size) * arr.itemsize
    return {"n_data_shards": 1, "params_bytes": params,
            "grads_bytes": params, "optimizer_state_bytes": params,
            "ef_bytes": 0, "zero_active": False}


def _nbytes(a) -> int:
    """Byte size WITHOUT materializing: jax and numpy arrays both
    expose .nbytes (no transfer); anything else converts."""
    nb = getattr(a, "nbytes", None)
    if nb is not None:
        return int(nb)
    return int(np.asarray(a).nbytes)


def _batch_bytes(x, y, w=None) -> int:
    total = _nbytes(x) + _nbytes(y)
    if w is not None:
        total += _nbytes(w)
    else:
        total += int(np.shape(x)[0]) * 4      # the all-ones pad mask
    return total


def step_resource_report(step, x, y, w=None, feed_batches: int = 2,
                         trace: bool = True) -> Dict[str, Any]:
    """The per-device HBM prediction for one built step at the given
    host batch shapes. Components (bytes/device):

    - ``params``: master weights, modeled replicated over the data axis;
    - ``grads``: the transient full-size per-shard gradient (static
      fallback only — the traced walk counts the real buffers);
    - ``optimizer_state``: momentum/Adam flat vectors, 1/N under the
      ZeRO plan (pad included — the plan's own rule);
    - ``ef``: the optional error-feedback residual slot, 1/N;
    - ``feed``: `feed_batches` device-resident batches (the DeviceFeed
      double buffer: the consumed batch + the prefetched one), sharded
      over the data axis;
    - ``activations``: the liveness-walk peak over the traced unjitted
      `train_callable()` (per-shard inside dp shard_map) — present only
      with `trace=True`.

    Returns the components plus ``resident_per_device`` (what
    live-array accounting sees between steps) and
    ``highwater_per_device`` (what the allocator peak sees mid-step)."""
    prof = _static_profile(step)
    n = max(1, int(prof.get("n_data_shards", 1)))
    batch_total = _batch_bytes(x, y, w)
    per_shard = batch_total // n if batch_total % n == 0 else batch_total
    components: Dict[str, int] = {
        "params": int(prof["params_bytes"]),
        "optimizer_state": int(prof["optimizer_state_bytes"]),
        "ef": int(prof.get("ef_bytes", 0)),
        "feed": int(max(1, feed_batches)) * per_shard,
    }
    resident = sum(components.values())
    report: Dict[str, Any] = {
        "schema": "veles-resources",
        "n_data_shards": n,
        "zero_active": bool(prof.get("zero_active")),
        "batch_bytes_per_device": per_shard,
        "feed_batches": int(max(1, feed_batches)),
        "components": components,
        "resident_per_device": resident,
    }
    traced = None
    if trace:
        traced = _traced_peak(step, x, y, w)
    if traced is not None:
        components["activations"] = traced
        report["highwater_per_device"] = resident + traced
        report["static_only"] = False
    else:
        # no trace: the transient estimate degrades to grads + the new
        # params copy (the two big known buffers the walk would count)
        est = int(prof["grads_bytes"]) + int(prof["params_bytes"])
        components["grads"] = int(prof["grads_bytes"])
        report["highwater_per_device"] = resident + est
        report["static_only"] = True
    return report


def _traced_peak(step, x, y, w=None) -> Optional[int]:
    """Liveness peak over the step's traced train callable, or None when
    the step offers no unjitted callable (make_jaxpr only: no compile,
    no devices — the jaxpr-auditor contract)."""
    callable_fn = getattr(step, "train_callable", None)
    if callable_fn is None:
        return None
    import jax
    x = np.asarray(x)
    y = np.asarray(y)
    if w is None:
        w = np.ones(np.shape(x)[0], np.float32)
    state = step.init_state()
    if hasattr(step, "_microbatch"):        # pipeline step
        xs, yb, wb = step._microbatch(x, y, w)
        args = (state, step._gid, xs, yb, wb)
    else:
        xb, yb = step._seq_xy(x, y)
        args = (state, xb, yb,
                step._weights_or_ones(np.asarray(w, np.float32),
                                      np.shape(x)[0]))
    closed = jax.make_jaxpr(callable_fn())(*args)
    return _liveness_highwater(closed.jaxpr)


def hbm_findings(report: Dict[str, Any],
                 limit: Optional[int]) -> List[Finding]:
    """`hbm-over-limit` / `hbm-near-limit` from a step report and a
    per-device limit (None = nothing to compare, no findings)."""
    if not limit:
        return []
    hw = int(report.get("highwater_per_device", 0))
    comps = ", ".join(f"{k}={v}" for k, v in
                      sorted(report.get("components", {}).items()))
    site = f"{hw}/{limit} B per device"
    if hw > limit:
        return [Finding(
            "hbm-over-limit", SEV_ERROR, "fused step",
            f"predicted per-device high-water {hw} B exceeds the device "
            f"memory limit {limit} B — this (model, mesh, batch, ZeRO) "
            f"combination would OOM after minutes of compile; "
            f"breakdown: {comps}", site)]
    if hw > NEAR_LIMIT_FRAC * limit:
        return [Finding(
            "hbm-near-limit", SEV_WARN, "fused step",
            f"predicted per-device high-water {hw} B is above "
            f"{int(NEAR_LIMIT_FRAC * 100)}% of the device memory limit "
            f"{limit} B; breakdown: {comps}", site)]
    return []


def workflow_resource_findings(workflow, step=None,
                               limit: Optional[int] = None,
                               vmem_budget_override: Optional[int] = None,
                               feed_batches: int = 2
                               ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Pass-6 entry point for `--verify-workflow=resources`: build (or
    take) a fused step, run BOTH ledgers with the loader's real
    minibatch shapes, and return (findings, the per-component report).
    Initializes the workflow host-side when needed; traces, never
    compiles."""
    if not workflow.is_initialized:
        workflow.initialize(device=None, verify="off")
    if step is None:
        step = workflow.build_fused_step()
    loader = workflow.loader
    x = np.asarray(loader.minibatch_data.mem)
    y = np.asarray(loader.minibatch_labels.mem)
    wm = loader.minibatch_valid.mem
    w = (np.asarray(wm, np.float32) if wm is not None
         else np.ones(x.shape[0], np.float32))
    report = step_resource_report(step, x, y, w,
                                  feed_batches=feed_batches, trace=True)
    lim = device_limit(limit)
    report["limit_per_device"] = lim
    findings = hbm_findings(report, lim)
    import jax
    findings += kernel_findings(
        workflow, device_kind=jax.devices()[0].device_kind,
        budget=vmem_budget_override,
        dtype=getattr(step, "compute_dtype", None))
    return findings, report


def preflight(workflow, step, feed_ahead: Optional[int] = None,
              limit: Optional[int] = None) -> Dict[str, Any]:
    """Launcher pre-flight (called by `_run_with_step` before the first
    dispatch): the STATIC resident model always runs (cheap host-shape
    sums — it rides the heartbeat so the supervisor can report the
    predicted-vs-measured delta); the traced high-water walk runs only
    when a device limit is actually known (or VELES_RESOURCE_PREFLIGHT
    forces it) — there is nothing to compare against on a CPU mesh and
    the trace is not free. Warns above 80% of the limit; raises
    ResourcePreflightError (with the per-component breakdown) above
    it — failing in seconds instead of OOMing after minutes of
    compile."""
    loader = workflow.loader
    x = np.asarray(loader.minibatch_data.mem)
    y = np.asarray(loader.minibatch_labels.mem)
    feed_batches = 1 + (1 if feed_ahead is None else max(0,
                                                         int(feed_ahead)))
    lim = device_limit(limit)
    do_trace = bool(lim) or bool(os.environ.get(PREFLIGHT_ENV))
    report = step_resource_report(step, x, y, None,
                                  feed_batches=feed_batches,
                                  trace=do_trace)
    report["limit_per_device"] = lim
    if lim:
        hw = report["highwater_per_device"]
        comps = ", ".join(f"{k}={v}" for k, v in
                          sorted(report["components"].items()))
        if hw > lim:
            raise ResourcePreflightError(
                f"resource pre-flight: predicted per-device high-water "
                f"{hw} B exceeds the device memory limit {lim} B — "
                f"refusing to compile a step that would OOM; "
                f"breakdown: {comps}", report)
        if hw > NEAR_LIMIT_FRAC * lim:
            _log.warning(
                "resource pre-flight: predicted per-device high-water "
                "%d B is %.0f%% of the device limit %d B (%s)",
                hw, 100.0 * hw / lim, lim, comps)
    return report


def serving_capacity(workflow, max_batch: int) -> Dict[str, Any]:
    """The /healthz capacity hint (ROADMAP direction 2's capacity-
    planning primitive): model bytes + a per-batch forward activation
    estimate from the units' DECLARED output geometries (host shapes,
    no trace — /healthz must stay cheap), against the device limit when
    one is known. `headroom_batches` is how many max_batch forward
    rings fit in what the model leaves free — None when no limit is
    known (CPU)."""
    params = 0
    per_sample = 0
    for u in getattr(workflow, "forwards", ()):
        for a in u.param_arrays().values():
            if a:
                arr = np.asarray(a.mem)
                params += int(arr.size) * arr.itemsize
        out = getattr(u, "output", None)
        if out is not None and getattr(out, "shape", None):
            per_sample += int(np.prod(out.shape[1:],
                                      dtype=np.int64)) * 4
    loader = getattr(workflow, "loader", None)
    if loader is not None and getattr(loader, "minibatch_data", None):
        per_sample += int(np.prod(
            loader.minibatch_data.shape[1:], dtype=np.int64)) * 4
    batch_bytes = per_sample * int(max_batch)
    lim = device_limit()
    out: Dict[str, Any] = {
        "model_bytes": params,
        "batch_bytes": batch_bytes,
        "device_limit": lim,
    }
    if lim and batch_bytes:
        out["headroom_batches"] = max(0, (lim - params) // batch_bytes)
    else:
        out["headroom_batches"] = None
    return out
