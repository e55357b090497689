"""Analysis pass 7 — the whole-system performance planner.

Every earlier perf PR shipped one fragment of a step-time model:
per-op cost shares (tools/layer_profile.py -> LAYER_PROFILE.json,
PR 8), a collective byte model keyed by the searched `wire[dt,blk,ef,
hier]` family (ops/variants.grad_reduce_bytes, PR 11), ring/TP/DP
analytic cost functions (parallel/scaling_model.py, PR 12), measured
fusion gains (FUSION_AB_RECORD.json, PR 13), and static VMEM/HBM
ledgers (analysis/resources.py, PR 14). This module fuses them into
ONE analytical model of the fused train step and puts a budgeted
configuration search on top:

    predicted step time = compute roofline + exposed collective time
                          (+ exposed feed time, normally hidden)

- **compute**: `train_flops_per_sample * batch / (peak * MFU(batch))`
  where MFU(b) is a saturating curve `MFU_MAX * b / (b + B_HALF)`
  calibrated on the builders' r4 on-chip batch sweep (2026-07-30;
  see docs/PLANNER.md for the fit and its error). Fusion claims scale
  the whole-step time by the measured fused/composed ratio from
  FUSION_AB_RECORD.json when the record's device kind matches.
- **comms**: ZeRO-on steps pay the reduce-scatter + param all-gather
  legs of the PR-11 wire byte model, each leg riding its own link
  class (scaling_model.wire_collective_time_s); ZeRO-off steps pay
  the classic per-axis ring all-reduce of the full f32 gradient
  (scaling_model.allreduce_time_s), which is where the mesh SHAPE
  enters the ranking.
- **feed**: modeled hidden by default (the PR-5 device-feed overlap
  measured ~1.0); set VELES_PLAN_FEED_BW (bytes/s) to expose the
  remainder `max(0, feed_bytes/bw - (compute+comms))`.
- **memory gate**: every candidate is pre-flighted through the PR-14
  ledgers BEFORE it can be ranked or timed — an `hbm-over-limit`
  or VMEM-over-budget finding refuses the config with the ledger's
  own message (the generate-then-gate discipline: no candidate is
  timed without passing the static feasibility gate).

`plan_search()` is the PR-8 budgeted-search machinery one level up:
the hand-set defaults are the incumbent, the model-evaluation budget
is split across config axes by fixed weights through
`autotune.allocate_budget`, coordinate descent walks one axis at a
time from the incumbent, and any remaining budget is spent on a
deterministic sweep of the untried cross product. An optional `timer`
callback measures the model's top-k (incumbent always included, so
the measured winner can never lose to the defaults silently).

Import discipline: importing this module must never initialize a jax
backend — tools/plan.py proves it per run (`jax_backends=0` on the
compact line) and tests/test_planner.py pins it. Keep device/compile
work out of module scope and out of every pure-model entry point.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import asdict, dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from veles_tpu.analysis import resources
from veles_tpu.analysis.findings import SEV_ERROR, Finding
from veles_tpu.ops import autotune as _autotune
from veles_tpu.ops import variants as _variants
from veles_tpu.parallel import scaling_model

# --------------------------------------------------------------------
# device constants
# --------------------------------------------------------------------

#: dense bf16 peak FLOP/s by device kind (v5e: benchmark/peaks.json)
DEVICE_PEAK_FLOPS: Dict[str, float] = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

#: per-device HBM by kind (public specs); VELES_HBM_LIMIT overrides
DEVICE_HBM_BYTES: Dict[str, int] = {
    "TPU v5 lite": 16 << 30,
    "TPU v5e": 16 << 30,
    "TPU v4": 32 << 30,
    "TPU v6 lite": 32 << 30,
    "TPU v6e": 32 << 30,
}

#: MFU(b) = MFU_MAX * b / (b + B_HALF), exact fit through the r4
#: on-chip sweep endpoints (builders' 2026-07-30 batch sweep: 0.4745 @ 512,
#: 0.5244 @ 2048; the interior point 1024 lands within 1.7%). The fit
#: is per-device-kind in principle; only the v5e family has a
#: committed sweep, so predictions elsewhere carry calibrated=False.
MFU_MAX = 0.543448
MFU_B_HALF = 74.397

#: kinds whose MFU curve is backed by a committed measured sweep
CALIBRATED_KINDS = frozenset({"TPU v5 lite", "TPU v5e"})

#: the fused lrn+maxpool search point the planner's `fusion="fused"`
#: arm claims — the one sample tile the v5e compiler admits at both
#: AlexNet LRN sites (tests/test_chip_compile.py); its VMEM footprint is
#: the fused arm's gate input
FUSED_LRN_POOL_POINT = "fused[rt=1,io=native,fuse=1]"

#: bytes of one feed sample beyond the f32 image: int32 label + f32
#: sample weight (loader minibatch_labels + minibatch_valid)
LABEL_BYTES = 8

PLAN_SCHEMA = "veles-plan"


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


# --------------------------------------------------------------------
# model geometry: pure arithmetic over the declarative layer list
# --------------------------------------------------------------------

@dataclass
class StepGeometry:
    """Everything the model needs to know about one workflow, derived
    arithmetically from its declarative layer list — no tracing, no
    arrays, no devices."""

    n_params: int
    fwd_flops_per_sample: float
    train_flops_per_sample: float
    per_op_fwd_flops: Dict[str, float]
    #: (c, h, w) activation shapes at every LRN site — the VMEM gate
    #: input for the fused lrn+maxpool claim
    lrn_sites: List[Dict[str, int]] = field(default_factory=list)
    input_hw: int = 227
    input_channels: int = 3
    name: str = "model"

    def sample_bytes(self) -> int:
        """Host->device bytes of one feed sample (f32 image + label
        + weight)."""
        return self.input_hw * self.input_hw * self.input_channels * 4 \
            + LABEL_BYTES


def model_geometry(layers: Sequence[Dict[str, Any]], *,
                   input_hw: int = 227, input_channels: int = 3,
                   name: str = "model") -> StepGeometry:
    """Walk a Znicz declarative layer list, tracking the activation
    grid (h, w, c) and accumulating params + forward MACs per op
    class. conv/fc MACs count the MXU work (2 FLOPs each); LRN /
    pool / dropout are bandwidth-bound and carry zero MACs — their
    cost lives in the measured MFU curve, their fusion upside in the
    measured fusion gain."""
    h = w = int(input_hw)
    c = int(input_channels)
    params = 0
    macs: Dict[str, float] = {}
    lrn_sites: List[Dict[str, int]] = []
    saw_conv = False
    for layer in layers:
        kind = layer["type"]
        if kind.startswith("conv"):
            kx, ky = int(layer["kx"]), int(layer["ky"])
            sx, sy = (int(v) for v in layer.get("stride", (1, 1)))
            px, py = (int(v) for v in layer.get("padding", (0, 0)))
            nk = int(layer["n_kernels"])
            oh = (h + 2 * py - ky) // sy + 1
            ow = (w + 2 * px - kx) // sx + 1
            op = "conv_stem" if not saw_conv else "conv"
            saw_conv = True
            macs[op] = macs.get(op, 0.0) + float(oh * ow) * kx * ky * c * nk
            params += kx * ky * c * nk + nk
            h, w, c = oh, ow, nk
        elif kind == "norm":
            lrn_sites.append({"c": c, "h": h, "w": w})
            macs.setdefault("lrn", 0.0)
        elif kind == "max_pooling":
            kx, ky = (int(v) for v in layer["ksize"])
            sx, sy = (int(v) for v in layer["stride"])
            h = (h - ky) // sy + 1
            w = (w - kx) // sx + 1
            macs.setdefault("maxpool", 0.0)
        elif kind in ("all2all", "all2all_strictrelu", "all2all_tanh",
                      "softmax"):
            n_in = h * w * c if h else c
            n_out = int(layer["output_sample_shape"])
            op = "softmax" if kind == "softmax" else "matmul"
            macs[op] = macs.get(op, 0.0) + float(n_in) * n_out
            params += n_in * n_out + n_out
            h = w = 0
            c = n_out
        elif kind == "dropout":
            macs.setdefault("dropout", 0.0)
        # activation-only / unknown layers carry no params and no MACs
    fwd = 2.0 * sum(macs.values())          # MAC -> FLOP
    per_op = {op: 2.0 * m for op, m in macs.items()}
    return StepGeometry(
        n_params=params,
        fwd_flops_per_sample=fwd,
        train_flops_per_sample=3.0 * fwd,   # fwd + ~2x bwd
        per_op_fwd_flops=per_op,
        lrn_sites=lrn_sites,
        input_hw=int(input_hw),
        input_channels=int(input_channels),
        name=name,
    )


def alexnet_geometry(*, n_classes: int = 1000, width_mult: float = 1.0,
                     fc_width: int = 4096,
                     input_hw: int = 227) -> StepGeometry:
    """The flagship's geometry from its own declarative layer list —
    the single source of truth samples/alexnet.py builds units from.
    Import kept local: samples pulls the Znicz stack, which this
    module must not cost at import."""
    from veles_tpu.samples.alexnet import alexnet_layers
    layers = alexnet_layers(n_classes=n_classes, width_mult=width_mult,
                            fc_width=fc_width)
    return model_geometry(layers, input_hw=input_hw, name="alexnet")


# --------------------------------------------------------------------
# compute leg
# --------------------------------------------------------------------

def mfu_model(batch_per_chip: float, *, mfu_max: float = MFU_MAX,
              b_half: float = MFU_B_HALF) -> float:
    """Saturating MFU-vs-per-chip-batch curve (r4 sweep fit)."""
    b = float(batch_per_chip)
    return mfu_max * b / (b + b_half)


def fusion_gain(device_kind: str,
                record_path: str = "FUSION_AB_RECORD.json"
                ) -> Tuple[float, str]:
    """Whole-step fused/composed speedup claimed by an A/B record
    (`arms.composed` / `arms.fused` samples/s), applied only when the
    record was measured on the SAME device kind (a CPU-interpret record
    must not predict chip behavior). No record is committed and nothing
    in the repo writes one — none has been measured on a chip — so the
    answer today is the neutral "no record". Returns (gain,
    provenance)."""
    try:
        with open(record_path) as fh:
            rec = json.load(fh)
        if rec.get("device_kind") == device_kind:
            comp = float(rec["arms"]["composed"]["samples_per_sec"])
            fused = float(rec["arms"]["fused"]["samples_per_sec"])
            if comp > 0 and fused > 0:
                return fused / comp, record_path
    except (OSError, ValueError, KeyError, TypeError):
        pass
    return 1.0, "none (no matching measured record; neutral gain 1.0)"


# --------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------

@dataclass(frozen=True)
class PlanConfig:
    """One full system configuration — every knob that was hand-set
    before this pass existed."""

    mesh_shape: Tuple[int, ...] = (8,)
    batch_per_chip: int = 1024
    zero: str = "on"                 # ZeRO-sharded optimizer state
    wire: str = "f32"                # grad_reduce wire variant name
    fusion: str = "composed"         # "composed" | "fused"
    hosts: int = 1
    compute_dtype: str = "bfloat16"

    @property
    def n_chips(self) -> int:
        return int(math.prod(self.mesh_shape))

    def key(self) -> Tuple:
        return (tuple(self.mesh_shape), self.batch_per_chip, self.zero,
                self.wire, self.fusion, self.hosts, self.compute_dtype)


def mesh_factorizations(n: int) -> List[Tuple[int, ...]]:
    """(n,) plus every 2-axis torus factorization with a <= b —
    the shapes the zero-off ring all-reduce decomposes over."""
    out: List[Tuple[int, ...]] = [(n,)]
    for a in range(2, int(math.isqrt(n)) + 1):
        if n % a == 0:
            out.append((a, n // a))
    return out


def _wire_bytes(cfg: PlanConfig, n_params: int) -> Dict[str, Any]:
    """PR-11 byte model legs for this config's wire + geometry. The
    byte model reads host geometry from VELES_GRAD_REDUCE_LOCAL; pin
    it from the config so planning 2-host geometries needs no real
    processes, then restore."""
    n = cfg.n_chips
    local = max(1, n // max(1, cfg.hosts))
    prev = os.environ.get(_variants.GRAD_REDUCE_LOCAL_ENV)
    os.environ[_variants.GRAD_REDUCE_LOCAL_ENV] = str(local)
    try:
        return _variants.grad_reduce_bytes(cfg.wire, int(n_params), n)
    finally:
        if prev is None:
            os.environ.pop(_variants.GRAD_REDUCE_LOCAL_ENV, None)
        else:
            os.environ[_variants.GRAD_REDUCE_LOCAL_ENV] = prev


def predict_step(cfg: PlanConfig, geom: StepGeometry, *,
                 device_kind: str = "TPU v5 lite",
                 overlap: float = 0.0) -> Dict[str, Any]:
    """The model: predicted seconds for one optimizer step of `cfg`
    on `device_kind`, with every term exposed for falsification."""
    peak = _env_float("VELES_PLAN_PEAK_FLOPS", 0.0) \
        or DEVICE_PEAK_FLOPS.get(device_kind, 0.0)
    if not peak:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAK_FLOPS)}): add it to "
            "DEVICE_PEAK_FLOPS or set VELES_PLAN_PEAK_FLOPS — an unknown "
            "chip is never priced as a v5e")
    calibrated = (device_kind in CALIBRATED_KINDS
                  and "VELES_PLAN_PEAK_FLOPS" not in os.environ)
    batch = int(cfg.batch_per_chip)
    mfu = mfu_model(batch)
    t_compute = geom.train_flops_per_sample * batch / (peak * mfu)
    gain, gain_src = (fusion_gain(device_kind)
                      if cfg.fusion != "composed" else
                      (1.0, "composed baseline"))
    t_compute /= gain

    dcn_bw = _env_float("VELES_PLAN_DCN_BW", scaling_model.DCN_BW_DEFAULT)
    if cfg.zero == "on":
        legs = _wire_bytes(cfg, geom.n_params)
        dcn = legs["dcn_bytes"] + legs["allgather_dcn_bytes"]
        ici = legs["ici_bytes"] + legs["allgather_ici_bytes"]
        wire_t = scaling_model.wire_collective_time_s(
            dcn_bytes=dcn, ici_bytes=ici, dcn_bw=dcn_bw)
        t_comms = wire_t["total_s"]
        comms = {"model": "wire[dt,blk,ef,hier] reduce-scatter + "
                          "param all-gather",
                 "dcn_bytes": int(dcn), "ici_bytes": int(ici),
                 "legs": legs, "dcn_s": wire_t["dcn_s"],
                 "ici_s": wire_t["ici_s"]}
    else:
        nbytes = 4.0 * geom.n_params
        t_comms = scaling_model.allreduce_time_s(nbytes, cfg.mesh_shape)
        n = cfg.n_chips
        comms = {"model": "per-axis ring all-reduce of the full f32 "
                          "gradient",
                 "dcn_bytes": 0,
                 "ici_bytes": int(2.0 * nbytes * (n - 1) / max(1, n)),
                 "dcn_s": 0.0, "ici_s": t_comms}
    t_comms_exposed = t_comms * (1.0 - overlap)

    feed_bytes = geom.sample_bytes() * batch   # per chip per step
    feed_bw = _env_float("VELES_PLAN_FEED_BW", 0.0)
    t_feed = (max(0.0, feed_bytes / feed_bw
                  - (t_compute + t_comms_exposed))
              if feed_bw > 0 else 0.0)

    step = t_compute + t_comms_exposed + t_feed
    total_batch = batch * cfg.n_chips
    return {
        "step_time_s": step,
        "samples_per_sec": total_batch / step if step > 0 else 0.0,
        "samples_per_sec_per_chip": batch / step if step > 0 else 0.0,
        "compute_s": t_compute,
        "comms_s": t_comms_exposed,
        "feed_s": t_feed,
        "comms": comms,
        "feed_bytes_per_chip": int(feed_bytes),
        "mfu_at_batch": mfu,
        "fusion_gain": gain,
        "fusion_gain_source": gain_src,
        "peak_flops": peak,
        "overlap": float(overlap),
        "calibrated": calibrated,
    }


# --------------------------------------------------------------------
# memory gate: the PR-14 ledgers as the planner's hard constraint
# --------------------------------------------------------------------

def plan_memory_report(cfg: PlanConfig, geom: StepGeometry, *,
                       device_kind: str = "TPU v5 lite"
                       ) -> Dict[str, Any]:
    """Static per-device HBM report for `cfg`, shaped exactly like
    resources.step_resource_report's static-only path so the verdict
    comes from resources.hbm_findings — the ledger's rule, not a
    planner re-implementation. Plus the VMEM gate for fused claims
    (resources.kernel_footprint vs the device budget at every LRN
    site) and the structural refusals no ledger models."""
    n = cfg.n_chips
    params = 4 * geom.n_params
    if cfg.zero == "on":
        opt = 4 * ((geom.n_params + n - 1) // n)    # momentum, 1/N +pad
    else:
        opt = params                                # replicated momentum
    wire_cfg = _variants.grad_reduce_config(cfg.wire) or {}
    ef = 0
    if wire_cfg.get("ef"):
        resid = _variants.grad_reduce_resid_len(cfg.wire, geom.n_params, n)
        ef = 4 * int(resid or 0)
    per_shard_feed = geom.sample_bytes() * cfg.batch_per_chip
    components = {
        "params": params,
        "optimizer_state": opt,
        "ef": ef,
        "feed": 2 * per_shard_feed,      # DeviceFeed double buffer
    }
    resident = sum(components.values())
    # static-only high-water: resident + the transient full-size
    # per-shard gradient + the bwd params copy (resources.py's rule
    # when no traced activation walk is available)
    highwater = resident + 2 * params
    report: Dict[str, Any] = {
        "schema": "veles-resources",
        "static_only": True,
        "n_data_shards": n,
        "zero_active": cfg.zero == "on",
        "batch_bytes_per_device": per_shard_feed,
        "components": components,
        "resident_per_device": resident,
        "highwater_per_device": highwater,
    }

    limit = int(_env_float("VELES_HBM_LIMIT", 0.0)) \
        or DEVICE_HBM_BYTES.get(device_kind, 0)
    findings: List[Finding] = list(resources.hbm_findings(report, limit))

    if cfg.fusion != "composed":
        for site in geom.lrn_sites:
            verdict = resources.kernel_verdict(
                "lrn_maxpool", FUSED_LRN_POOL_POINT, shapes=site,
                device_kind=device_kind)
            if verdict is not None:
                findings.append(Finding(
                    "vmem-over-budget", SEV_ERROR, "lrn_maxpool",
                    f"fused point {FUSED_LRN_POOL_POINT} needs "
                    f"{verdict.get('footprint')} B VMEM at LRN site "
                    f"{site}, budget {verdict.get('vmem_budget')} B "
                    f"on {device_kind}", "plan"))
                break
    if wire_cfg.get("ef") and cfg.zero != "on":
        findings.append(Finding(
            "wire-ef-needs-zero", SEV_ERROR, "grad_reduce",
            f"wire {cfg.wire} carries error feedback in the ZeRO "
            "optimizer slice; it cannot run with zero=off", "plan"))
    if wire_cfg.get("hier") and cfg.hosts <= 1:
        findings.append(Finding(
            "wire-hier-degenerate", "warn", "grad_reduce",
            f"hierarchical wire {cfg.wire} on a single host is "
            "byte-identical to the flat leg (no DCN tier)", "plan"))

    errors = [f for f in findings if f.severity == SEV_ERROR]
    return {
        "verdict": "refused" if errors else "feasible",
        "reasons": [f.format() for f in errors],
        "warnings": [f.format() for f in findings
                     if f.severity != SEV_ERROR],
        "hbm_limit": limit,
        "report": report,
    }


# --------------------------------------------------------------------
# pod-efficiency bridge (docs/SCALING.md recipe through the planner)
# --------------------------------------------------------------------

def pod_efficiency(geom: StepGeometry, *, batch_per_chip: int,
                   mesh_shape: Sequence[int] = (8, 8),
                   device_kind: str = "TPU v5 lite",
                   step_time_s: Optional[float] = None,
                   target: float = 0.90) -> Dict[str, Any]:
    """The docs/SCALING.md pod prediction with the planner supplying
    its inputs: grad bytes from the geometry, step time from the
    model unless a measured one is given."""
    if step_time_s is None:
        cfg = PlanConfig(mesh_shape=(1,), batch_per_chip=batch_per_chip)
        step_time_s = predict_step(cfg, geom,
                                   device_kind=device_kind)["compute_s"]
    return scaling_model.predict_dp_scaling(
        grad_bytes=4.0 * geom.n_params, step_time_s=step_time_s,
        batch_per_chip=batch_per_chip, mesh_shape=mesh_shape,
        target=target)


# --------------------------------------------------------------------
# serve proposal (the serving-tier knobs, same gate)
# --------------------------------------------------------------------

SERVE_RING_CHOICES = (512, 256, 128, 64)


def propose_serve(cfg: PlanConfig, geom: StepGeometry, *,
                  device_kind: str = "TPU v5 lite") -> Dict[str, Any]:
    """Serving-tier knobs for a train config, under the same HBM
    ledger: weight wire int8 when bf16 weights alone would pass 25%
    of the device, the largest ring that divides the data axis and
    keeps serve residency under half the device."""
    limit = int(_env_float("VELES_HBM_LIMIT", 0.0)) \
        or DEVICE_HBM_BYTES.get(device_kind, 16 << 30)
    quant = "int8" if 2 * geom.n_params > 0.25 * limit else "bf16"
    wbytes = geom.n_params * (1 if quant == "int8" else 2)
    sample = geom.sample_bytes()
    ring = 0
    for slots in SERVE_RING_CHOICES:
        if slots % cfg.n_chips:
            continue
        if wbytes + slots * sample <= 0.5 * limit:
            ring = slots
            break
    return {"serve_quantize": quant, "ring_slots": ring or
            min(SERVE_RING_CHOICES),
            "weights_bytes": int(wbytes), "hbm_limit": limit}


# --------------------------------------------------------------------
# budgeted configuration search (the PR-8 machinery one level up)
# --------------------------------------------------------------------

#: axis exploration weights for allocate_budget — batch dominates the
#: measured step time (the r4 sweep moved it 10.5%/octave), the wire
#: dominates multi-host comms, mesh/zero reshape the collective, the
#: fusion claim is binary
AXIS_WEIGHTS: List[Tuple[str, float]] = [
    ("batch_per_chip", 0.35),
    ("wire", 0.25),
    ("mesh_shape", 0.15),
    ("zero", 0.15),
    ("fusion", 0.10),
]

BATCH_CHOICES = (128, 256, 512, 1024, 2048)


def default_space(n_chips: int, hosts: int = 1) -> Dict[str, List[Any]]:
    wires = ["f32", "bf16", "int8_block", "int8_ef"]
    if hosts > 1:
        wires.append("hier2")       # degenerate (= f32) on one host
    return {
        "batch_per_chip": list(BATCH_CHOICES),
        "wire": wires,
        "mesh_shape": mesh_factorizations(n_chips),
        "zero": ["on", "off"],
        "fusion": ["composed", "fused"],
    }


def _plan_counter():
    """veles_plan_configs_total{outcome} on the PR-7 registry; lazily
    bound like autotune's trials counter (planning is not a hot
    path)."""
    from veles_tpu.telemetry import metrics as tm
    return tm.default_registry().counter(
        "veles_plan_configs_total",
        "planner candidate configurations by gate outcome "
        "(feasible / refused / timed)",
        labelnames=("outcome",))


def plan_search(geom: Optional[StepGeometry] = None, *,
                device_kind: str = "TPU v5 lite", n_chips: int = 8,
                hosts: int = 1, budget: int = 32,
                incumbent: Optional[PlanConfig] = None,
                space: Optional[Dict[str, List[Any]]] = None,
                timer: Optional[Callable[[PlanConfig], float]] = None,
                top_k: int = 3) -> Dict[str, Any]:
    """Incumbent-first coordinate descent over the config space, then
    deterministic exploration of whatever budget remains; every
    candidate is model-priced and ledger-gated, and only the model's
    top-k (plus the incumbent, always) is ever timed."""
    if geom is None:
        geom = alexnet_geometry()
    if space is None:
        space = default_space(n_chips, hosts)
    if incumbent is None:
        incumbent = PlanConfig(mesh_shape=(n_chips,), hosts=hosts)
    counter = None
    try:
        counter = _plan_counter()
    except Exception:           # telemetry must never break planning
        pass

    evaluated: Dict[Tuple, Dict[str, Any]] = {}

    def evaluate(cfg: PlanConfig) -> Dict[str, Any]:
        k = cfg.key()
        if k in evaluated:
            return evaluated[k]
        pred = predict_step(cfg, geom, device_kind=device_kind)
        mem = plan_memory_report(cfg, geom, device_kind=device_kind)
        entry = {"config": asdict(cfg), "predicted": pred,
                 "memory": {kk: mem[kk] for kk in
                            ("verdict", "reasons", "warnings",
                             "hbm_limit")},
                 "hbm_highwater_per_device":
                     mem["report"]["highwater_per_device"],
                 "_cfg": cfg}
        evaluated[k] = entry
        if counter is not None:
            counter.labels(outcome=mem["verdict"]).inc()
        return entry

    axes = [a for a, _ in AXIS_WEIGHTS if len(space.get(a, [])) > 1]
    weights = [(a, w) for a, w in AXIS_WEIGHTS if a in axes]
    alloc = (_autotune.allocate_budget(
        weights, max(0, budget - 1), floors={a: 1 for a in axes})
        if weights else {})

    # the objective is throughput: seconds per SAMPLE, not per step —
    # otherwise a tiny batch wins on raw step time while starving the
    # MXU (the r4 sweep's whole point)
    def per_sample(e: Dict[str, Any]) -> float:
        rate = e["predicted"]["samples_per_sec"]
        return 1.0 / rate if rate > 0 else float("inf")

    def better(a: Dict[str, Any], b: Optional[Dict[str, Any]]) -> bool:
        if b is None:
            return a["memory"]["verdict"] == "feasible"
        return (a["memory"]["verdict"] == "feasible"
                and per_sample(a) < per_sample(b))

    inc_entry = evaluate(incumbent)
    best_entry = inc_entry if inc_entry["memory"]["verdict"] == \
        "feasible" else None

    # coordinate descent: walk each axis from the current best point
    for axis in axes:
        base = best_entry["_cfg"] if best_entry else incumbent
        spent = 0
        for choice in space[axis]:
            if choice == getattr(base, axis):
                continue
            if spent >= alloc.get(axis, 0):
                break
            if axis == "mesh_shape":
                cand = replace(base, mesh_shape=tuple(choice))
            else:
                cand = replace(base, **{axis: choice})
            if cand.key() not in evaluated:
                spent += 1
            e = evaluate(cand)
            if better(e, best_entry):
                best_entry = e

    # deterministic exploration of the remaining budget over the
    # untried cross product, fixed axis order
    import itertools
    names = list(space.keys())
    for combo in itertools.product(*(space[a] for a in names)):
        if len(evaluated) >= budget:
            break
        kw = dict(zip(names, combo))
        if "mesh_shape" in kw:
            kw["mesh_shape"] = tuple(kw["mesh_shape"])
        cand = replace(incumbent, **kw)
        if cand.key() in evaluated:
            continue
        e = evaluate(cand)
        if better(e, best_entry):
            best_entry = e

    feasible = [e for e in evaluated.values()
                if e["memory"]["verdict"] == "feasible"]
    refused = [e for e in evaluated.values()
               if e["memory"]["verdict"] != "feasible"]
    feasible.sort(key=lambda e: (per_sample(e),
                                 e["config"]["batch_per_chip"]))
    refused.sort(key=per_sample)

    measured_top1 = None
    if timer is not None:
        to_time: List[Dict[str, Any]] = []
        if inc_entry not in to_time:
            to_time.append(inc_entry)
        for e in feasible:
            if e not in to_time:
                to_time.append(e)
            if len(to_time) >= top_k + 1:
                break
        for e in to_time:
            e["measured_step_s"] = float(timer(e["_cfg"]))
            if counter is not None:
                counter.labels(outcome="timed").inc()
        timed = [e for e in to_time if e.get("measured_step_s")]
        if timed:
            # same objective measured: seconds per sample
            measured_top1 = min(
                timed, key=lambda e: e["measured_step_s"]
                / (e["config"]["batch_per_chip"]
                   * max(1, math.prod(e["config"]["mesh_shape"]))))

    for e in feasible[: max(1, top_k)]:
        e["serve"] = propose_serve(e["_cfg"], geom,
                                   device_kind=device_kind)
    ranked = feasible + refused
    for e in ranked:
        e.pop("_cfg", None)

    plan: Dict[str, Any] = {
        "schema": PLAN_SCHEMA,
        "version": 1,
        "model": {
            "name": geom.name,
            "n_params": geom.n_params,
            "train_gflops_per_sample":
                geom.train_flops_per_sample / 1e9,
            "mfu_curve": {"mfu_max": MFU_MAX, "b_half": MFU_B_HALF,
                          "source": "r4 on-chip batch sweep "
                                    "(builder measurement 2026-07-30)"},
        },
        "device_kind": device_kind,
        "n_chips": n_chips,
        "hosts": hosts,
        "calibrated": device_kind in CALIBRATED_KINDS,
        "budget": {"total": budget, "allocation": alloc,
                   "evaluated": len(evaluated)},
        "incumbent": inc_entry,
        "ranked": ranked,
        "n_feasible": len(feasible),
        "n_refused": len(refused),
    }
    if measured_top1 is not None:
        plan["measured_top1"] = {"config": measured_top1["config"],
                                 "measured_step_s":
                                     measured_top1["measured_step_s"]}
    return plan
