"""Analysis pass 7 — the whole-system planner (ISSUE 17).

Four contracts:

1. **Calibration**: the analytical step model reproduces the
   committed measured records within the bounds stated in
   docs/PLANNER.md — the r4 on-chip batch sweep absolutely (<10%,
   actually <2%), the r3 sweep's batch-scaling SHAPE (<10%; r3
   absolute rates predate the current lowerings, which is exactly
   what the model does not predict), and the docs/SCALING.md
   pod-efficiency pins through the planner's own bridge.
2. **Byte-model cross-check**: the planner's collective legs equal
   the byte counts of the actual per-destination payload arrays for
   all four `wire[dt,blk,ef,hier]` legs, and the PR-11 quantized-DCN
   claim is a regression test, not a one-off measurement.
3. **Ledger completeness**: every registered kernel-template point
   resolves through `resources.kernel_footprint` — an unknown VMEM
   footprint must be a loud finding here, never a silently unpruned
   search point.
4. **Staticness**: `tools/plan.py` plans the flagship with ZERO jax
   backends initialized (no devices, no compiles) and every emitted
   config carries the ledger's memory verdict.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from veles_tpu.analysis import planner, resources
from veles_tpu.ops import variants

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the docs/PLANNER.md stated bounds
R4_ABS_BOUND = 0.10
R3_SHAPE_BOUND = 0.10


#: builder measurement 2026-07-30, predates PRs 1–19 (and the installed
#: jax): AlexNet training on one "TPU v5 lite", samples/s/chip. The r4
#: batch sweep the planner's MFU curve was fitted to, and the r3 sweep
#: (older lowering, same protocol) its curve SHAPE is checked against.
#: History the model was calibrated on — not a current number.
_MEASURED = {
    "batch_sweep": {
        "512": {"value": 13724.09, "mfu": 0.4745},
        "1024": {"value": 14408.59, "mfu": 0.4982},
        "2048": {"value": 15165.81, "mfu": 0.5244},
    },
    "r3_batch_sweep_same_protocol": {
        "128": 6455.91, "256": 8950.94, "512": 9619.56,
        "1024": 9907.12, "2048": 10042.77},
}


def _measured():
    return _MEASURED


# ---------------------------------------------------------------------------
# geometry: the pure-arithmetic walker vs the flagship pins
# ---------------------------------------------------------------------------

def test_alexnet_geometry_matches_flagship_pins():
    g = planner.alexnet_geometry()
    # the exact flagship param count every scaling doc/test pins
    assert g.n_params == 62378344
    # train FLOPs/sample implied by the committed r4 record
    # (mfu * peak / rate); the walker must land within 0.5%
    m = _measured()
    b = m["batch_sweep"]["512"]
    implied = b["mfu"] * 197e12 / b["value"]
    assert abs(g.train_flops_per_sample / implied - 1.0) < 0.005
    # both LRN sites present with the real activation shapes — the
    # fused-claim VMEM gate's input
    assert g.lrn_sites == [{"c": 96, "h": 55, "w": 55},
                           {"c": 256, "h": 27, "w": 27}]


# ---------------------------------------------------------------------------
# calibration vs the committed measured records
# ---------------------------------------------------------------------------

def test_r4_batch_sweep_within_stated_bound():
    """Absolute per-chip rate error < R4_ABS_BOUND on every point of
    the r4 on-chip sweep (the MFU curve's source — the fit uses the
    512/2048 endpoints, so 1024 is a genuine interior check)."""
    m = _measured()
    g = planner.alexnet_geometry()
    for batch, rec in m["batch_sweep"].items():
        cfg = planner.PlanConfig(mesh_shape=(1,),
                                 batch_per_chip=int(batch))
        pred = planner.predict_step(cfg, g, device_kind="TPU v5 lite")
        err = pred["samples_per_sec_per_chip"] / rec["value"] - 1.0
        assert abs(err) < R4_ABS_BOUND, (batch, err)
        assert pred["calibrated"]


def test_r3_batch_scaling_shape_within_stated_bound():
    """r3 absolute rates predate the current lowerings, so the model
    (which prices the CURRENT code) must not be held to them — but
    the batch-scaling SHAPE (rate ratio across the sweep) is a
    lowering-independent property of the MFU saturation the model
    claims to capture."""
    m = _measured()
    g = planner.alexnet_geometry()
    r3 = m["r3_batch_sweep_same_protocol"]

    def rate(b):
        cfg = planner.PlanConfig(mesh_shape=(1,), batch_per_chip=b)
        return planner.predict_step(cfg, g)["samples_per_sec_per_chip"]

    measured_ratio = r3["2048"] / r3["512"]
    predicted_ratio = rate(2048) / rate(512)
    assert abs(predicted_ratio / measured_ratio - 1.0) < R3_SHAPE_BOUND


def test_pod_efficiency_recipe_pinned():
    """The docs/SCALING.md headline numbers reproduced through the
    planner's bridge: 92.9% weak-scaling efficiency at batch 1024 on
    a v5e-64, 90% crossing near batch 708."""
    m = _measured()
    g = planner.alexnet_geometry()
    step = 1024 / m["batch_sweep"]["1024"]["value"]
    eff = planner.pod_efficiency(g, batch_per_chip=1024,
                                 step_time_s=step)
    assert abs(eff["predicted_efficiency"] - 0.929) < 0.003
    assert abs(eff["batch_per_chip_at_target"] - 708) < 5


def test_fusion_gain_uses_matching_record_only(tmp_path):
    # no fused-vs-composed A/B has been measured on a chip: the repo
    # commits no record (the PR-13 one was an interpret-mode CPU timing
    # of a kernel body the v5e compiler refused), so the planner's
    # answer is the neutral "no record"
    assert not os.path.exists(os.path.join(REPO, "FUSION_AB_RECORD.json"))
    gain, src = planner.fusion_gain("TPU v5 lite")
    assert gain == 1.0 and "none" in src
    # a record IS read when one exists, for its own device kind only
    path = str(tmp_path / "rec.json")
    with open(path, "w") as fh:
        json.dump({"device_kind": "TPU v5 lite", "arms": {
            "composed": {"samples_per_sec": 100.0},
            "fused": {"samples_per_sec": 110.0}}}, fh)
    gain, src = planner.fusion_gain("TPU v5 lite", path)
    assert abs(gain - 1.1) < 1e-9 and src == path
    other, osrc = planner.fusion_gain("TPU v93 hyper", path)
    assert other == 1.0 and "none" in osrc


# ---------------------------------------------------------------------------
# byte-model cross-check: model legs == counted payload bytes
# ---------------------------------------------------------------------------

N_ELEMS = 262144        # divisible by n * blk: zero padding effects


def _counted_flat_legs(n, loc, payload_bytes_per_dest):
    """Wire bytes of a flat ring exchange counted from the actual
    per-destination payload sizes: each device sends one shard-slice
    payload toward every OTHER shard; crossings split by host."""
    dcn = sum(payload_bytes_per_dest
              for d in range(n) if d // loc != 0) \
        * 1  # device 0's egress; model is per-device
    ici = sum(payload_bytes_per_dest
              for d in range(1, n) if d // loc == 0)
    return dcn, ici


@pytest.fixture
def _two_host_geometry(monkeypatch):
    monkeypatch.setenv(variants.GRAD_REDUCE_LOCAL_ENV, "4")


def test_byte_model_vs_counted_wire_all_legs(_two_host_geometry):
    n, loc, hosts = 8, 4, 2
    grad = np.arange(N_ELEMS, dtype=np.float32)
    shard = np.split(grad, n)[0]          # one destination's payload

    # f32 leg: payload per destination is the raw f32 slice
    legs = variants.grad_reduce_bytes("f32", N_ELEMS, n)
    dcn, ici = _counted_flat_legs(n, loc, shard.nbytes)
    assert legs["dcn_bytes"] == dcn
    assert legs["ici_bytes"] == ici
    # all-gather legs ride f32 regardless of wire: own slice to peers
    assert legs["allgather_dcn_bytes"] == shard.nbytes * (n - loc)
    assert legs["allgather_ici_bytes"] == shard.nbytes * (loc - 1)

    # bf16 leg: 2-byte payload (np.float16 is the byte-width twin)
    legs = variants.grad_reduce_bytes("bf16", N_ELEMS, n)
    dcn, ici = _counted_flat_legs(n, loc, shard.astype(np.float16).nbytes)
    assert legs["dcn_bytes"] == dcn
    assert legs["ici_bytes"] == ici

    # int8_block leg: the payload is the REAL q8 encoding of the
    # slice — int8 codes + the f32 block scales, counted from the
    # encoded arrays themselves
    codes, scales = variants.q8_encode(shard.reshape(1, -1), 256)
    per_dest = int(np.asarray(codes).nbytes + np.asarray(scales).nbytes)
    legs = variants.grad_reduce_bytes("int8_block", N_ELEMS, n)
    dcn, ici = _counted_flat_legs(n, loc, per_dest)
    assert legs["dcn_bytes"] == dcn
    assert legs["ici_bytes"] == ici

    # hier leg (f32, 2 hosts): phase 1 exchanges group-slices over
    # ICI inside each host, phase 2 exchanges the reduced group-slice
    # across hosts over DCN
    group_slice = np.split(grad, loc)[0]
    legs = variants.grad_reduce_bytes("hier2", N_ELEMS, n)
    assert legs["ici_bytes"] == group_slice.nbytes * (loc - 1)
    assert legs["dcn_bytes"] == group_slice.nbytes * (hosts - 1) // hosts


def test_quantized_dcn_claim_is_a_regression_test(_two_host_geometry):
    """The PR-11 claim: the quantized wire's cross-host bytes are
    ≤0.26× the full-precision flat wire's. Pinned both ways it is
    quoted: flat int8 vs flat f32 (item ratio (1+4/256)/4), and the
    shipped int8+hierarchical composite vs flat bf16."""
    n = 8
    f32 = variants.grad_reduce_bytes("f32", N_ELEMS, n)
    bf16 = variants.grad_reduce_bytes("bf16", N_ELEMS, n)
    int8 = variants.grad_reduce_bytes("int8_block", N_ELEMS, n)
    hier8 = variants.grad_reduce_bytes(
        "wire[dt=int8,blk=256,ef=0,hier=1]", N_ELEMS, n)
    assert int8["dcn_bytes"] <= 0.26 * f32["dcn_bytes"]
    assert hier8["dcn_bytes"] <= 0.26 * bf16["dcn_bytes"]
    # and the planner consumes exactly these legs
    g = planner.StepGeometry(
        n_params=N_ELEMS, fwd_flops_per_sample=1e9,
        train_flops_per_sample=3e9, per_op_fwd_flops={})
    cfg = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=128,
                             wire="int8_block", hosts=2)
    pred = planner.predict_step(cfg, g)
    assert pred["comms"]["legs"]["dcn_bytes"] == int8["dcn_bytes"]


# ---------------------------------------------------------------------------
# ledger completeness: every template point has a knowable footprint
# ---------------------------------------------------------------------------

#: templates that legitimately declare no VMEM footprint: they do not
#: lower through Pallas (XLA lowerings / collective wires). ANY new
#: template outside this list without a footprint rule is a silently
#: unprunable search space — add the rule, don't extend the list.
NON_PALLAS_TEMPLATES = {("conv_stem", "gen"), ("maxpool", "gen"),
                        ("grad_reduce", "wire")}


def test_every_template_point_resolves_a_footprint():
    from veles_tpu.ops import templates as T
    seen = 0
    for op in T.template_ops():
        for t in T.templates_for(op):
            if t.vmem_footprint is None:
                assert (t.op, t.base) in NON_PALLAS_TEMPLATES, (
                    f"template {t.op}/{t.base} lowers through Pallas "
                    f"but declares no vmem_footprint — every one of "
                    f"its {len(list(t.configs()))} search points "
                    f"would dodge the PR-14 prune AND the planner's "
                    f"memory gate")
                continue
            for cfg in t.configs():
                name = t.name(cfg)
                fp = resources.kernel_footprint(t.op, name)
                assert fp is not None and fp >= 0, (t.op, name)
                seen += 1
    assert seen >= 72       # the registry's current point count


# ---------------------------------------------------------------------------
# memory gate + search behavior
# ---------------------------------------------------------------------------

def test_memory_gate_refuses_oversized_and_structural():
    g = planner.alexnet_geometry()
    # HBM: a batch that cannot fit the v5e feed buffers
    big = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=65536)
    m = planner.plan_memory_report(big, g, device_kind="TPU v5 lite")
    assert m["verdict"] == "refused"
    assert any("hbm-over-limit" in r for r in m["reasons"])
    # structural: error feedback lives in the ZeRO slice
    ef = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=512,
                            wire="int8_ef", zero="off")
    m = planner.plan_memory_report(ef, g)
    assert m["verdict"] == "refused"
    assert any("wire-ef-needs-zero" in r for r in m["reasons"])


def test_memory_gate_vmem_refusal_for_fused_claim(monkeypatch):
    monkeypatch.setenv("VELES_VMEM_BUDGET", "4096")
    g = planner.alexnet_geometry()
    fused = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=512,
                               fusion="fused")
    m = planner.plan_memory_report(fused, g, device_kind="TPU v5 lite")
    assert m["verdict"] == "refused"
    assert any("vmem-over-budget" in r for r in m["reasons"])


def test_plan_search_incumbent_first_and_ranked():
    g = planner.alexnet_geometry()
    inc = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=1024)
    plan = planner.plan_search(g, n_chips=8, budget=20, incumbent=inc)
    assert plan["budget"]["evaluated"] <= 20
    assert plan["incumbent"]["config"]["batch_per_chip"] == 1024
    ranked = plan["ranked"]
    assert ranked and len(ranked) == plan["budget"]["evaluated"]
    for e in ranked:
        assert e["memory"]["verdict"] in ("feasible", "refused")
        assert e["predicted"]["step_time_s"] > 0
    # feasible block ranked by throughput (per-sample time)
    feas = [e for e in ranked if e["memory"]["verdict"] == "feasible"]
    rates = [e["predicted"]["samples_per_sec"] for e in feas]
    assert rates == sorted(rates, reverse=True)
    # the model must prefer a saturating batch over a starving one
    assert feas[0]["config"]["batch_per_chip"] >= 1024
    # serve proposal rides the leaders and divides the data axis
    assert feas[0]["serve"]["ring_slots"] % 8 == 0


def test_plan_search_timer_includes_incumbent():
    g = planner.alexnet_geometry()
    timed = []

    def timer(cfg):
        timed.append(cfg)
        # pretend the defaults are secretly fastest per sample
        return 0.01 if cfg.wire == "f32" else 0.02

    inc = planner.PlanConfig(mesh_shape=(8,), batch_per_chip=2048,
                             wire="f32")
    plan = planner.plan_search(g, n_chips=8, budget=10, incumbent=inc,
                               timer=timer, top_k=2)
    assert any(c.wire == "f32" and c.batch_per_chip == 2048
               for c in timed)
    assert plan["measured_top1"]["config"]["wire"] == "f32"


# ---------------------------------------------------------------------------
# the static smoke: tools/plan.py with zero backends
# ---------------------------------------------------------------------------

def test_plan_tool_is_fully_static(tmp_path):
    """tools/plan.py plans the AlexNet flagship for the 8-chip mesh
    with ZERO jax backends initialized — no devices, no compiles —
    and every emitted config carries the ledger's verdict."""
    record = tmp_path / "PLAN.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["VELES_PLAN_PATH"] = str(record)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "plan.py"),
         "--chips", "8", "--budget", "16"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("PLAN ")]
    assert lines, out.stdout
    compact = json.loads(lines[-1][5:])
    assert compact["jax_backends"] == 0
    assert compact["evaluated"] == 16
    assert compact["top1"]["verdict"] == "feasible"
    with open(record) as fh:
        plan = json.load(fh)
    assert plan["schema"] == "veles-plan"
    assert plan["jax_backends_after_planning"] == 0
    assert len(plan["ranked"]) == plan["budget"]["evaluated"]
    for e in plan["ranked"]:
        assert e["memory"]["verdict"] in ("feasible", "refused")
        if e["memory"]["verdict"] == "refused":
            assert e["memory"]["reasons"]
