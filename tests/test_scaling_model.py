"""Analytic scaling-efficiency model (parallel/scaling_model.py).

The BASELINE.json >=90%-on-v5e-64 target is unmeasurable on one chip;
these tests pin the *prediction machinery* instead: the ring all-reduce
cost formula, the efficiency computation, and the self-consistency of the
reported crossing batch (training at exactly `batch_per_chip_at_target`
must predict exactly `target` efficiency)."""

import pytest

from veles_tpu.parallel.scaling_model import (allreduce_time_s,
                                              predict_dp_scaling)


def test_allreduce_single_axis_formula():
    # 2*V*(X-1)/(X*W), one axis
    v, x, w = 1e9, 8, 9e10
    assert allreduce_time_s(v, (x,), w) == pytest.approx(
        2 * v * 7 / (8 * w))


def test_allreduce_two_axis_decomposition():
    # second axis operates on the reduce-scattered payload V/X0
    v, w = 1e9, 9e10
    expect = 2 * v * 7 / (8 * w) + 2 * (v / 8) * 7 / (8 * w)
    assert allreduce_time_s(v, (8, 8), w) == pytest.approx(expect)
    # size-1 axes are free
    assert allreduce_time_s(v, (8, 1), w) == pytest.approx(
        2 * v * 7 / (8 * w))
    assert allreduce_time_s(v, (1, 1), w) == 0.0


def test_prediction_self_consistency():
    p = predict_dp_scaling(grad_bytes=2.5e8, step_time_s=0.071,
                           batch_per_chip=1024, mesh_shape=(8, 8))
    assert 0.0 < p["predicted_efficiency"] < 1.0
    # re-predict at the reported crossing batch: must land on target
    scale = p["batch_per_chip_at_target"] / 1024
    p2 = predict_dp_scaling(
        grad_bytes=2.5e8, step_time_s=0.071 * scale,
        batch_per_chip=int(round(p["batch_per_chip_at_target"])),
        mesh_shape=(8, 8))
    assert p2["predicted_efficiency"] == pytest.approx(0.90, abs=1e-6)


def test_overlap_and_bigger_batch_help():
    base = predict_dp_scaling(grad_bytes=2.5e8, step_time_s=0.071,
                              batch_per_chip=1024)
    overlapped = predict_dp_scaling(grad_bytes=2.5e8, step_time_s=0.071,
                                    batch_per_chip=1024, overlap=0.5)
    bigger = predict_dp_scaling(grad_bytes=2.5e8, step_time_s=0.142,
                                batch_per_chip=2048)
    assert overlapped["predicted_efficiency"] > base["predicted_efficiency"]
    assert bigger["predicted_efficiency"] > base["predicted_efficiency"]
    # inputs echoed for falsifiability
    assert base["inputs"]["grad_bytes"] == 2.5e8


def test_flagship_prediction_meets_target():
    """The model's headline use: the round-4 chip numbers
    (62.38M-param AlexNet, 71.07 ms step @1024/chip) predict >=90%
    weak-scaling on a v5e-64 even with zero comm/compute overlap."""
    p = predict_dp_scaling(grad_bytes=62378344 * 4,
                           step_time_s=1024 / 14408.59,
                           batch_per_chip=1024, mesh_shape=(8, 8))
    assert p["meets_target_at_measured_batch"]
    assert p["batch_per_chip_at_target"] < 1024


def test_tp_layer_rule_of_thumb():
    """docs/SCALING.md's 'TP worth it when layer width x batch makes the
    all-reduce smaller than the compute it buys', numeric: the 4096-wide
    FC pair at batch >= 512 clears the bar; a tiny layer does not."""
    from veles_tpu.parallel.scaling_model import predict_tp_layer

    big = predict_tp_layer(batch_tokens=512, width=4096, hidden=4096,
                           tp=2)
    assert big["worth_it"], big
    tiny = predict_tp_layer(batch_tokens=8, width=64, hidden=64, tp=8)
    assert not tiny["worth_it"], tiny
    # comm is per-step constant in tp (ring (k-1)/k factor saturates),
    # compute shrinks with tp: the ratio must worsen as tp grows
    worse = predict_tp_layer(batch_tokens=512, width=4096, hidden=4096,
                             tp=8)
    assert worse["comm_over_comp"] > big["comm_over_comp"]


def test_ring_sp_crossing():
    """Ring hop hides under compute iff S_local exceeds the
    peak·bytes/(2·W_oneway) crossing — independent of
    heads/batch/head_dim (they cancel), ~4.4k tokens on v5e bf16 (the
    ppermute hop is UNIDIRECTIONAL: one link, not the per-axis
    aggregate)."""
    from veles_tpu.parallel.scaling_model import ring_sp_overlap

    r = ring_sp_overlap(batch=8, heads=16, head_dim=128, seq_local=8192)
    assert r["hidden"], r
    assert 3000 < r["seq_local_at_crossing"] < 6000
    small = ring_sp_overlap(batch=8, heads=16, head_dim=128,
                            seq_local=2048)
    assert not small["hidden"], small
    # the crossing is where the two times meet
    at = ring_sp_overlap(batch=2, heads=4, head_dim=64,
                         seq_local=int(r["seq_local_at_crossing"]))
    assert at["hop_compute_s"] == pytest.approx(at["hop_transfer_s"],
                                                rel=1e-3)
