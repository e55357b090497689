"""Analytic weak-scaling prediction for data-parallel training on TPU pods.

The BASELINE.json north star (>=90% weak-scaling efficiency on a v5e-64,
SURVEY.md §6) cannot be *measured* in this environment (one real chip), so
this module turns it into a falsifiable prediction instead: given the
measured single-chip step time, the model's gradient byte count, and the
public per-axis ICI bandwidth, predict the efficiency of the synchronous
data-parallel step on an (X, Y) chip mesh — and the batch-per-chip where
it crosses a target.

Model (the "How to Scale Your Model" collective-cost recipe):
- the fused train step is compute + one gradient all-reduce per step
  (parallel/fused.py emits a single fused psum over the dp axis — the
  compiled-HLO collective counts are verified device-count-independent by
  __graft_entry__.dryrun_multichip);
- a bidirectional-ring all-reduce of V bytes over a torus axis of size X
  with per-axis bidirectional ICI bandwidth W costs
      T_axis = 2 * V * (X - 1) / (X * W);
- on a 2-axis mesh the reduction decomposes per axis (reduce-scatter along
  the first axis shrinks the payload X0-fold before the second), so
      T_comm = 2*V*(X0-1)/(X0*W) + 2*(V/X0)*(X1-1)/(X1*W);
- XLA overlaps the all-reduce with the tail of the backward pass; the
  `overlap` knob discounts the exposed fraction (0 = fully exposed, the
  conservative default used for the headline prediction).

Parity: the reference had no analog — its NCCL/MPI data plane shipped full
weight payloads per slave per step (SURVEY.md §2.4); the prediction here
is for the TPU-native in-graph psum design.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

#: public v5e numbers (scaling-book / cloud docs): one-way ICI bandwidth
#: per link 4.5e10 B/s, 2 links per torus axis -> 9e10 B/s bidirectional
#: per axis; dense bf16 peak 197 TFLOP/s (benchmark/peaks.json).
V5E_ICI_BW_AXIS_BIDIR = 9.0e10


def allreduce_time_s(nbytes: float, mesh_shape: Sequence[int],
                     ici_bw_axis_bidir: float = V5E_ICI_BW_AXIS_BIDIR
                     ) -> float:
    """Bidirectional-ring all-reduce of `nbytes` over every axis of a
    torus mesh, decomposed reduce-scatter-then-continue per axis."""
    t, v = 0.0, float(nbytes)
    for x in mesh_shape:
        if x <= 1:
            continue
        t += 2.0 * v * (x - 1) / (x * ici_bw_axis_bidir)
        v /= x      # reduce-scatter along this axis shrinks the payload
    return t


def predict_dp_scaling(*, grad_bytes: float, step_time_s: float,
                       batch_per_chip: int,
                       mesh_shape: Sequence[int] = (8, 8),
                       ici_bw_axis_bidir: float = V5E_ICI_BW_AXIS_BIDIR,
                       overlap: float = 0.0,
                       target: float = 0.90) -> Dict[str, Any]:
    """Predicted weak-scaling efficiency of the synchronous dp step.

    `step_time_s` is the measured single-chip step wall time at
    `batch_per_chip`; compute time is assumed to scale linearly with the
    per-chip batch (true within the builders' 2026-07-30 512..2048 sweep).
    Returns the prediction with every input echoed so a future pod run can
    falsify it term by term.
    """
    t_comm = allreduce_time_s(grad_bytes, mesh_shape, ici_bw_axis_bidir)
    exposed = t_comm * (1.0 - overlap)
    eff = step_time_s / (step_time_s + exposed)
    # batch-per-chip where efficiency crosses `target`: compute must cover
    # target/(1-target) times the exposed comm time
    per_sample_s = step_time_s / batch_per_chip
    need_comp = exposed * target / (1.0 - target)
    batch_at_target = need_comp / per_sample_s if per_sample_s > 0 else 0.0
    return {
        "model": "2-axis ring all-reduce, exposed (overlap=%g)" % overlap,
        "inputs": {
            "grad_bytes": float(grad_bytes),
            "step_time_s": float(step_time_s),
            "batch_per_chip": int(batch_per_chip),
            "mesh_shape": list(mesh_shape),
            "ici_bw_axis_bidir_bytes_per_s": float(ici_bw_axis_bidir),
            "overlap": float(overlap),
        },
        "allreduce_time_s": t_comm,
        "exposed_comm_s": exposed,
        "predicted_efficiency": eff,
        "target_efficiency": target,
        "batch_per_chip_at_target": batch_at_target,
        "meets_target_at_measured_batch": eff >= target,
    }


#: v5e dense bf16 peak, FLOP/s (benchmark/peaks.json)
V5E_PEAK_FLOPS = 197e12


def predict_tp_layer(*, batch_tokens: int, width: int, hidden: int,
                     tp: int, dtype_bytes: int = 2,
                     ici_bw_axis_bidir: float = V5E_ICI_BW_AXIS_BIDIR,
                     peak_flops: float = V5E_PEAK_FLOPS
                     ) -> Dict[str, Any]:
    """Megatron col→row FFN pair under `tp`-way tensor parallelism: is
    the per-layer activation all-reduce smaller than the compute it
    buys? (docs/SCALING.md "TP pays activation all-reduces per layer
    pair", made numeric.)

    Per forward, the row-parallel output all-reduces `batch_tokens ×
    width` activations over the tp axis; backward mirrors it (2×/step).
    Compute per step ≈ 3 × 2·batch_tokens·width·hidden·2 (fwd + ~2×
    bwd) split tp ways."""
    act_bytes = batch_tokens * width * dtype_bytes
    t_comm = 2.0 * allreduce_time_s(act_bytes, (tp,), ici_bw_axis_bidir)
    flops = 3.0 * 2.0 * batch_tokens * width * hidden * 2.0
    t_comp = flops / tp / peak_flops
    return {
        "comm_s": t_comm,
        "comp_s": t_comp,
        "comm_over_comp": t_comm / t_comp if t_comp else float("inf"),
        "worth_it": t_comm < t_comp,
        "inputs": {"batch_tokens": batch_tokens, "width": width,
                   "hidden": hidden, "tp": tp,
                   "dtype_bytes": dtype_bytes},
    }


#: default cross-host (DCN) bandwidth, bytes/s per host: ~100 Gb/s NIC
#: (public v5e pod specs). The planner's hierarchical-collective leg
#: divides by this; override per deployment via the planner's dcn_bw
#: argument (env VELES_PLAN_DCN_BW in tools/plan.py).
DCN_BW_DEFAULT = 12.5e9


def wire_collective_time_s(*, dcn_bytes: float, ici_bytes: float,
                           ici_bw_axis_bidir: float = V5E_ICI_BW_AXIS_BIDIR,
                           dcn_bw: float = DCN_BW_DEFAULT
                           ) -> Dict[str, Any]:
    """Seconds for one collective whose PER-DEVICE egress is already
    split by link leg — the PR-11 `wire[dt,blk,ef,hier]` byte model
    (`ops.variants.grad_reduce_bytes`) extended into a time model. The
    byte model already carries the ring (x-1)/x factors and the
    quantized/hierarchical payload shrinkage, so the legs just ride
    their respective bandwidths; the slower leg does NOT hide the
    faster one (the hierarchical exchange runs ICI phase then DCN
    phase sequentially — conservative for the flat legs, exact for
    hier)."""
    t_ici = float(ici_bytes) / ici_bw_axis_bidir
    t_dcn = float(dcn_bytes) / dcn_bw
    return {"ici_s": t_ici, "dcn_s": t_dcn, "total_s": t_ici + t_dcn,
            "inputs": {"dcn_bytes": float(dcn_bytes),
                       "ici_bytes": float(ici_bytes),
                       "ici_bw_axis_bidir_bytes_per_s":
                           float(ici_bw_axis_bidir),
                       "dcn_bw_bytes_per_s": float(dcn_bw)}}


#: one direction of one v5e ICI link — the ring's K/V hop
#: (lax.ppermute i -> i+1, ops/attention.py) travels ONE way, so it
#: rides a single link, not the per-axis bidirectional aggregate the
#: all-reduce formula legitimately uses
V5E_ICI_BW_ONEWAY = 4.5e10


def ring_sp_overlap(*, batch: int, heads: int, head_dim: int,
                    seq_local: int, dtype_bytes: int = 2,
                    ici_bw_oneway: float = V5E_ICI_BW_ONEWAY,
                    peak_flops: float = V5E_PEAK_FLOPS
                    ) -> Dict[str, Any]:
    """Ring attention: each hop `lax.ppermute`s the local K,V shard one
    step around the ring while the chip computes attention of its
    queries against the PREVIOUS shard. The hop hides iff per-hop
    compute ≥ per-hop transfer (docs/SCALING.md "S_local·d ≳ hop
    bytes", made numeric — below the crossing, Ulysses' two all_to_alls
    win). Unidirectional: the hop uses ONE link's bandwidth."""
    hop_bytes = 2 * batch * heads * seq_local * head_dim * dtype_bytes
    t_hop = hop_bytes / ici_bw_oneway
    # per-hop attention compute: QK^T + PV over one (S_local x S_local)
    # block for every head
    flops = 2.0 * 2.0 * batch * heads * seq_local * seq_local * head_dim
    t_comp = flops / peak_flops
    # t_comp >= t_hop  ⇔  4·S²·d/peak >= 2·S·d·bytes/W_oneway
    #                  ⇔  S_local >= peak·bytes/(2·W_oneway)  (d,B,H cancel)
    crossing = peak_flops * dtype_bytes / (2.0 * ici_bw_oneway)
    return {
        "hop_transfer_s": t_hop,
        "hop_compute_s": t_comp,
        "hidden": t_comp >= t_hop,
        "seq_local_at_crossing": crossing,
        "inputs": {"batch": batch, "heads": heads, "head_dim": head_dim,
                   "seq_local": seq_local, "dtype_bytes": dtype_bytes},
    }
