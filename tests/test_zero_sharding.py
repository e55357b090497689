"""ZeRO-style weight-update sharding (arxiv 2004.13336; ISSUE 6).

The equivalence contract: on the CPU test mesh the zero-sharded dp step
(reduce-scatter grads -> shard-local 1/N update over sliced optimizer
state -> param all-gather) must follow the SAME trajectory as the
replicated update, for SGD+momentum AND Adam, for leaf sizes the data
axis divides and for ragged ones (the pad-to-divisible remainder rule),
within rtol=1e-5/atol=1e-6 — the tolerance stated in docs/SCALING.md.
The memory contract: per-replica optimizer-state bytes drop by
>= (N-1)/N. Plus: snapshot -> restore -> resume across a data-axis-size
change, the grad_reduce registry contract, clean degradation, and the
analysis rules that police the new geometry.
"""

import logging
import os

import jax
import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import XLADevice
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.parallel import make_mesh
from veles_tpu.parallel.fused import FusedTrainStep
from veles_tpu.parallel.mesh import DATA_AXIS, zero_leaf, zero_plan
from veles_tpu.znicz.standard_workflow import StandardWorkflow

RTOL, ATOL = 1e-5, 1e-6     # the stated trajectory tolerance


def build(hidden=33, n_classes=10, lr=0.1, seed=1234):
    prng.seed_all(seed)
    loader = SyntheticClassifierLoader(
        n_classes=n_classes, sample_shape=(8, 8), n_validation=96,
        n_train=480, minibatch_size=48, noise=0.6)
    return StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": hidden,
                 "weights_stddev": 0.05},
                {"type": "softmax", "output_sample_shape": n_classes,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=n_classes,
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        gd_config={"learning_rate": lr, "gradient_moment": 0.9,
                   "weights_decay": 0.0005},
        name="ZeroWF")


def first_batch(wf):
    wf.initialize(device=XLADevice())
    from veles_tpu.loader.base import TRAIN
    ld = wf.loader
    while True:
        ld.run()
        if ld.minibatch_class == TRAIN:
            return (ld.minibatch_data.mem.copy(),
                    ld.minibatch_labels.mem.copy())


def steps_pair(eight_devices, n_data=4, optimizer="sgd", hidden=33,
               compute_dtype=None):
    """(replicated step+state, zero step+state, batch) with identical
    seeds on an n_data-way dp mesh."""
    mesh = make_mesh(eight_devices[:n_data])
    out = []
    for zs in ("off", "on"):
        wf = build(hidden=hidden)
        x, y = first_batch(wf)
        for g in wf.gds:
            g.optimizer = optimizer
        step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding=zs,
                              compute_dtype=compute_dtype)
        out.append((wf, step, step.init_state()))
    (wf_a, step_a, sa), (wf_b, step_b, sb) = out
    assert not step_a.zero_active
    assert step_b.zero_active, step_b.zero_reason
    return (wf_a, step_a, sa), (wf_b, step_b, sb), (x, y)


def assert_states_match(sa, sb):
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(
                np.asarray(pa[k]), np.asarray(pb[k]),
                rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# the plan itself
# ---------------------------------------------------------------------------

def test_zero_leaf_remainder_rule():
    lp = zero_leaf((33,), 4)
    assert (lp.size, lp.padded, lp.local, lp.ndim) == (33, 36, 9, 1)
    lp = zero_leaf((64, 32), 8)
    assert (lp.size, lp.padded, lp.local) == (2048, 2048, 256)
    plan = zero_plan({"w": np.zeros((5, 3)), "b": np.zeros(7)}, 4)
    assert plan["w"].padded == 16 and plan["b"].padded == 8
    with pytest.raises(ValueError):
        zero_leaf((3,), 0)


# ---------------------------------------------------------------------------
# trajectory equivalence (the ISSUE's stated contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("hidden", [32, 33])   # divisible and ragged
def test_zero_matches_replicated_trajectory(optimizer, hidden,
                                            compute_dtype, eight_devices):
    """Both updates take the same float32 partial gradients and sum
    them in float32, whatever the forward computes in."""
    (_, step_a, sa), (_, step_b, sb), (x, y) = steps_pair(
        eight_devices, n_data=4, optimizer=optimizer, hidden=hidden,
        compute_dtype=compute_dtype)
    for _ in range(5):
        sa, (la, ea) = step_a.train(sa, x, y)
        sb, (lb, eb) = step_b.train(sb, x, y)
    assert float(la) == pytest.approx(float(lb), rel=1e-5)
    assert int(ea) == int(eb)
    assert_states_match(sa, sb)


def test_zero_matches_local_step(eight_devices):
    """The full equivalence ladder: zero-sharded dp == the single-device
    local step (not just == replicated dp)."""
    wf_l = build()
    x, y = first_batch(wf_l)
    step_l = wf_l.build_fused_step()
    sl = step_l.init_state()

    wf_z = build()
    first_batch(wf_z)
    mesh = make_mesh(eight_devices[:4])
    step_z = wf_z.build_fused_step(mesh=mesh, mode="dp",
                                   zero_sharding="on")
    sz = step_z.init_state()
    for _ in range(3):
        sl, (ll, _) = step_l.train(sl, x, y)
        sz, (lz, _) = step_z.train(sz, x, y)
    assert float(ll) == pytest.approx(float(lz), rel=1e-5)
    assert_states_match(sl, sz)


def test_zero_accum_matches_plain(eight_devices):
    """Gradient accumulation under ZeRO: one reduce-scatter of the
    accumulated partials == the plain step's update."""
    (_, step_a, sa), (_, step_b, sb), (x, y) = steps_pair(
        eight_devices, n_data=4)
    w = np.ones(48, np.float32)
    w[-5:] = 0.0            # wrapped final minibatch: pad-mask rows
    sa, (la, _) = step_a.train(sa, x, y, w)
    sb, (lb, _) = step_b.train_accum(sb, x, y, 4, w)
    assert float(la) == pytest.approx(float(lb), rel=1e-5)
    assert_states_match(sa, sb)


def test_zero_train_repeat_and_many(eight_devices):
    """The scanned hot loops carry the sharded optimizer state through
    lax.scan: K repeat steps == K sequential train() calls."""
    (_, step_a, sa), (_, step_b, sb), (x, y) = steps_pair(
        eight_devices, n_data=4)
    for _ in range(3):
        sa, _ = step_a.train(sa, x, y)
    sb, (losses, _) = step_b.train_repeat(sb, x, y, 3)
    assert losses.shape == (3,)
    assert_states_match(sa, sb)


def test_zero_pad_region_stays_zero(eight_devices):
    """The remainder rule is numerically invisible: the padded tail of
    every flat optimizer-state vector stays exactly zero over steps."""
    (_, _, _), (_, step_b, sb), (x, y) = steps_pair(
        eight_devices, n_data=4, hidden=33)
    for _ in range(3):
        sb, _ = step_b.train(sb, x, y)
    for layer_vel, plan in zip(sb["vel"], step_b.zero_plans()):
        for k, lp in plan.items():
            flat = np.asarray(layer_vel[k])
            assert flat.shape == (lp.padded,)
            np.testing.assert_array_equal(flat[lp.size:], 0.0)


def test_zero_write_back_unflattens_velocity(eight_devices):
    """write_back lands the gathered, unflattened velocities in the GD
    twins — granular resume / whole-workflow snapshots keep working."""
    (wf_a, step_a, sa), (wf_b, step_b, sb), (x, y) = steps_pair(
        eight_devices, n_data=4)
    for _ in range(2):
        sa, _ = step_a.train(sa, x, y)
        sb, _ = step_b.train(sb, x, y)
    step_a.write_back(sa)
    step_b.write_back(sb)
    for ga, gb in zip(wf_a.gds, wf_b.gds):
        np.testing.assert_allclose(ga.vel_w.mem, gb.vel_w.mem,
                                   rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(ga.vel_b.mem, gb.vel_b.mem,
                                   rtol=RTOL, atol=ATOL)
        assert gb.vel_w.mem.shape == gb.weights.mem.shape


# ---------------------------------------------------------------------------
# memory: the (N-1)/N acceptance criterion, measured
# ---------------------------------------------------------------------------

def test_optimizer_state_bytes_drop_sgd(eight_devices):
    """All-divisible leaves, N=8: per-replica optimizer-state bytes
    drop by EXACTLY (N-1)/N (>= the acceptance floor)."""
    n = 8
    mesh = make_mesh(eight_devices)
    states = {}
    for zs in ("off", "on"):
        wf = build(hidden=32, n_classes=16)
        x, y = first_batch(wf)
        step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding=zs)
        s = step.init_state()
        s, _ = step.train(s, x, y)   # replicated leaves spread mesh-wide
        states[zs] = (step, s)
    rep = max(states["off"][0].optimizer_state_bytes(
        states["off"][1]).values())
    zro = max(states["on"][0].optimizer_state_bytes(
        states["on"][1]).values())
    drop = 1.0 - zro / rep
    assert drop >= (n - 1) / n, (rep, zro, drop)
    # and the measurement equals the plan's prediction
    plans = states["on"][0].zero_plans()
    predicted = sum(lp.local for plan in plans
                    for lp in plan.values()) * 4
    assert zro == predicted


def test_optimizer_state_bytes_drop_adam_ragged(eight_devices):
    """Adam (2 moment trees + a replicated scalar t) with ragged leaves
    still lands within a whisker of the (N-1)/N floor — padding and the
    t scalar are the only slack."""
    n = 8
    mesh = make_mesh(eight_devices)
    per_dev = {}
    for zs in ("off", "on"):
        wf = build(hidden=33)
        x, y = first_batch(wf)
        for g in wf.gds:
            g.optimizer = "adam"
        step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding=zs)
        s = step.init_state()
        s, _ = step.train(s, x, y)
        per_dev[zs] = max(step.optimizer_state_bytes(s).values())
    drop = 1.0 - per_dev["on"] / per_dev["off"]
    assert drop >= (n - 1) / n * 0.99, per_dev


# ---------------------------------------------------------------------------
# checkpoint: restore across a data-axis change (and zero <-> replicated)
# ---------------------------------------------------------------------------

def test_restore_across_data_axis_change(tmp_path, eight_devices):
    """Save under N=4 zero, restore into N=2 zero: the resumed
    trajectory matches the uninterrupted N=4 one."""
    from veles_tpu.parallel.checkpoint import restore_state, save_state
    wf = build()
    x, y = first_batch(wf)
    mesh4 = make_mesh(eight_devices[:4])
    step4 = FusedTrainStep(wf, mesh=mesh4, mode="dp", zero_sharding="on")
    s = step4.init_state()
    for _ in range(2):
        s, _ = step4.train(s, x, y)
    save_state(s, str(tmp_path))
    ref = s
    for _ in range(2):
        ref, (l_ref, _) = step4.train(ref, x, y)

    wf2 = build()
    first_batch(wf2)
    step2 = FusedTrainStep(wf2, mesh=make_mesh(eight_devices[:2]),
                           mode="dp", zero_sharding="on")
    restored = restore_state(step2, str(tmp_path))
    v = restored["vel"][0]["weights"]
    assert v.ndim == 1 and DATA_AXIS in tuple(v.sharding.spec)
    for _ in range(2):
        restored, (l2, _) = step2.train(restored, x, y)
    assert float(l2) == pytest.approx(float(l_ref), rel=1e-5)
    assert_states_match(ref, restored)


def test_restore_zero_save_into_replicated_step(tmp_path, eight_devices):
    from veles_tpu.parallel.checkpoint import restore_state, save_state
    wf = build()
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step_z = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    s = step_z.init_state()
    s, _ = step_z.train(s, x, y)
    save_state(s, str(tmp_path))
    s, (l_ref, _) = step_z.train(s, x, y)

    wf2 = build()
    first_batch(wf2)
    step_r = FusedTrainStep(wf2, mesh=mesh, mode="dp",
                            zero_sharding="off")
    restored = restore_state(step_r, str(tmp_path))
    assert restored["vel"][0]["weights"].shape == (64, 33)
    restored, (l2, _) = step_r.train(restored, x, y)
    assert float(l2) == pytest.approx(float(l_ref), rel=1e-5)


def test_restore_replicated_save_into_zero_step(tmp_path, eight_devices):
    from veles_tpu.parallel.checkpoint import restore_state, save_state
    wf = build()
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step_r = FusedTrainStep(wf, mesh=mesh, mode="dp",
                            zero_sharding="off")
    s = step_r.init_state()
    s, _ = step_r.train(s, x, y)
    save_state(s, str(tmp_path))
    s, (l_ref, _) = step_r.train(s, x, y)

    wf2 = build()
    first_batch(wf2)
    step_z = FusedTrainStep(wf2, mesh=mesh, mode="dp", zero_sharding="on")
    restored = restore_state(step_z, str(tmp_path))
    v = restored["vel"][0]["weights"]
    assert v.ndim == 1 and DATA_AXIS in tuple(v.sharding.spec)
    restored, (l2, _) = step_z.train(restored, x, y)
    assert float(l2) == pytest.approx(float(l_ref), rel=1e-5)


def test_real_geometry_mismatch_still_raises(tmp_path, eight_devices):
    """The reshard fallback is surgical: a DIFFERENT-model checkpoint
    (param shapes disagree) still raises CheckpointGeometryError."""
    from veles_tpu.parallel.checkpoint import (CheckpointGeometryError,
                                               restore_state, save_state)
    wf = build(hidden=33)
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    s = step.init_state()
    s, _ = step.train(s, x, y)
    save_state(s, str(tmp_path))

    wf2 = build(hidden=17)      # narrower model
    first_batch(wf2)
    step2 = FusedTrainStep(wf2, mesh=mesh, mode="dp", zero_sharding="on")
    with pytest.raises(CheckpointGeometryError):
        restore_state(step2, str(tmp_path))


# ---------------------------------------------------------------------------
# grad_reduce registry (the EQuARX slot)
# ---------------------------------------------------------------------------

def test_grad_reduce_variants_contract(eight_devices):
    """f32 reduce-scatter == the psum-then-slice it replaces, exactly;
    bf16 within the quantization tolerance. Both run under shard_map on
    the CPU mesh — the registry's admission bar for collectives."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from veles_tpu.ops import variants
    mesh = make_mesh(eight_devices)
    n = 8
    rng = np.random.RandomState(3)
    flat = rng.randn(n, 64).astype(np.float32)   # one partial per shard

    def run(variant_name):
        v = variants.get("grad_reduce", variant_name)
        f = shard_map(lambda g: v.apply(g.reshape(-1), DATA_AXIS),
                      mesh=mesh, in_specs=P(DATA_AXIS),
                      out_specs=P(DATA_AXIS))
        return np.asarray(jax.jit(f)(flat))

    want = flat.sum(axis=0)                       # the psum's verdict
    np.testing.assert_allclose(run("f32"), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(run("bf16"), want, rtol=0.05, atol=0.05)
    assert variants.resolve("grad_reduce").name == "f32"


def test_zero_variant_table_names_grad_reduce(eight_devices):
    wf = build()
    first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    assert step.variant_table().get("grad_reduce") == "f32"
    step_off = FusedTrainStep(wf, mesh=mesh, mode="dp",
                              zero_sharding="off")
    assert "grad_reduce" not in step_off.variant_table()
    # reported == traced: the registry scatter is in the step's jaxpr
    state = step.init_state()
    x = np.asarray(wf.loader.minibatch_data.mem)
    y = np.asarray(wf.loader.minibatch_labels.mem)
    text = str(jax.make_jaxpr(step.train_callable())(
        state, x, y, np.ones(x.shape[0], np.float32)))
    assert "reduce_scatter" in text


# ---------------------------------------------------------------------------
# degradation: every uncovered geometry gets a reason, not silence
# ---------------------------------------------------------------------------

def test_zero_degrades_with_reason(eight_devices):
    # assert the logged-reason contract at the handler level: the
    # project Logger config owns propagation, so attach directly
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    log = logging.getLogger("veles.fused")
    log.addHandler(handler)
    try:
        wf = build(hidden=32, n_classes=16)
        first_batch(wf)
        mesh_tp = make_mesh(eight_devices, model=2)
        step = FusedTrainStep(wf, mesh=mesh_tp, mode="gspmd",
                              zero_sharding="on")
    finally:
        log.removeHandler(handler)
    assert not step.zero_active
    assert "mode" in step.zero_reason
    assert any("zero-sharding inactive" in m for m in records)

    step = FusedTrainStep(wf, zero_sharding="on")      # local, no mesh
    assert not step.zero_active and "mode" in step.zero_reason

    mesh1 = make_mesh(eight_devices[:1])
    step = FusedTrainStep(wf, mesh=mesh1, mode="dp", zero_sharding="on")
    assert not step.zero_active and "single shard" in step.zero_reason

    step = FusedTrainStep(wf, mesh=make_mesh(eight_devices[:4]),
                          mode="dp", zero_sharding="off")
    assert not step.zero_active and "request" in step.zero_reason

    with pytest.raises(ValueError):
        FusedTrainStep(wf, mesh=make_mesh(eight_devices[:4]),
                       mode="dp", zero_sharding="maybe")


def test_zero_degrades_for_ep(eight_devices):
    from tests.test_moe_pipeline import _build_moe_wf
    wf = _build_moe_wf()
    wf.initialize(device=None)
    mesh = make_mesh(eight_devices[:4], data=4)
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", ep=True,
                          zero_sharding="on")
    assert not step.zero_active
    assert "ep" in step.zero_reason


# ---------------------------------------------------------------------------
# "auto": memory decides (the state against the device's limit)
# ---------------------------------------------------------------------------

#: build()'s state under SGD with momentum: 2,453 parameters x 12 B
STATE_BYTES = 12 * (64 * 33 + 33 + 33 * 10 + 10)


@pytest.mark.parametrize("req,limit,optimizer,active", [
    ("auto", 16 << 30, "sgd", False),           # a v5e's limit: fits
    ("auto", 2 * STATE_BYTES, "sgd", False),    # exactly the share: fits
    ("auto", 2 * STATE_BYTES - 2, "sgd", True),     # past the share: shard
    ("auto", 2 * STATE_BYTES, "adam", True),    # 16 B a parameter: past it
    ("auto", None, "sgd", False),               # no limit known (the CPU)
    ("on", 16 << 30, "sgd", True),              # on request, whatever memory
    ("on", None, "sgd", True),
    ("off", 1, "sgd", False),
])
def test_auto_decides_from_memory(req, limit, optimizer, active,
                                  eight_devices, monkeypatch):
    from veles_tpu.analysis.resources import HBM_LIMIT_ENV
    from veles_tpu.parallel.fused import ZERO_AUTO_STATE_SHARE
    if limit is None:
        monkeypatch.delenv(HBM_LIMIT_ENV, raising=False)
    else:
        monkeypatch.setenv(HBM_LIMIT_ENV, str(limit))
    wf = build()
    first_batch(wf)
    for g in wf.gds:
        g.optimizer = optimizer
    step = FusedTrainStep(wf, mesh=make_mesh(eight_devices[:4]),
                          mode="dp", zero_sharding=req)
    assert step.zero_active is active, step.zero_reason
    assert step.resource_profile()["zero_active"] is active
    if req != "auto":
        return
    # the reason names the quantity that decided, with both numbers
    state = STATE_BYTES // 12 * (16 if optimizer == "adam" else 12)
    assert str(state) in step.zero_reason
    if limit is None:
        assert "no device memory limit" in step.zero_reason
    else:
        assert str(limit) in step.zero_reason
        assert str(int(ZERO_AUTO_STATE_SHARE * limit)) in step.zero_reason


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_default_dp_step_all_reduces_float32_leaves(compute_dtype,
                                                    eight_devices):
    """The default dp step on the CPU mesh (no limit: replicated) asks
    for one float32 all-reduce per parameter leaf, of the leaf's shape,
    under the unit's grad_exchange scope, and for no all-gather — at
    bfloat16 compute too, where autodiff's own psum would reduce the
    bfloat16 cotangent."""
    import re
    wf = build(hidden=32)
    x, y = first_batch(wf)
    step = FusedTrainStep(wf, mesh=make_mesh(eight_devices[:4]),
                          mode="dp", compute_dtype=compute_dtype)
    assert not step.zero_active
    text = jax.jit(step.train_callable()).lower(
        step.init_state(), x, y,
        np.ones(x.shape[0], np.float32)).as_text(debug_info=True)
    assert "all_gather" not in text and "reduce_scatter" not in text
    where = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    reduced = [(operand, where.get(loc, "")) for operand, loc in re.findall(
        r'"stablehlo\.all_reduce".*?\}\) : \((tensor<[^>]*>)\) -> '
        r'tensor<[^>]*> loc\((#loc\d+)\)', text, re.S)]
    exchanged = sorted(t for t, scope in reduced
                       if "/grad_exchange/" in scope)
    assert exchanged == sorted(["tensor<64x32xf32>", "tensor<32xf32>",
                                "tensor<32x10xf32>", "tensor<10xf32>"]), \
        reduced
    # and nothing else of a leaf's size is reduced (the loss's scalars)
    assert all(t in ("tensor<f32>", "tensor<i32>") for t, scope in reduced
               if "/grad_exchange/" not in scope), reduced
    assert {scope.split("/grad_exchange/")[0] for _t, scope in reduced
            if "/grad_exchange/" in scope} \
        == {"update/L00.all2all_tanh", "update/L01.softmax"}


# ---------------------------------------------------------------------------
# the production loop + CLI surface
# ---------------------------------------------------------------------------

def test_run_fused_zero_end_to_end(eight_devices):
    """run_fused drives the zero-sharded step through the real
    Loader/Decision/DeviceFeed loop; the trained weights match the
    replicated run's."""
    results = {}
    for zs in ("off", "on"):
        wf = build(lr=0.05)
        wf.run_fused(epochs=2, device=XLADevice(),
                     mesh=make_mesh(jax.devices()[:4]), mode="dp",
                     zero_sharding=zs)
        results[zs] = [np.asarray(u.weights.mem) for u in wf.forwards]
        assert wf.fused_state is not None
    for wa, wb in zip(results["off"], results["on"]):
        np.testing.assert_allclose(wa, wb, rtol=1e-4, atol=1e-5)


def test_launcher_rejects_bad_zero_flag():
    from veles_tpu.launcher import Launcher
    with pytest.raises(SystemExit):
        Launcher(zero_sharding="sideways")
    # GPipe + explicit on degrades with a warning, not an error
    lau = Launcher(pp=2, zero_sharding="on")
    assert lau.zero_sharding == "on"
    # the granular graph never consumes the knob: explicit on/off
    # without --fused/--pp/-l/-m is rejected (--feed-ahead precedent),
    # the "auto" default passes through silently
    for req in ("on", "off"):
        with pytest.raises(SystemExit):
            Launcher(zero_sharding=req)
    assert Launcher().zero_sharding == "auto"
    assert Launcher(fused=True, zero_sharding="off").zero_sharding \
        == "off"


def test_cli_parser_accepts_zero_sharding():
    from veles_tpu.__main__ import build_parser
    p = build_parser()
    args = p.parse_args(["wf.py", "--fused", "--zero-sharding", "off"])
    assert args.zero_sharding == "off"
    args = p.parse_args(["wf.py", "--fused", "--zero-sharding"])
    assert args.zero_sharding == "on"
    args = p.parse_args(["wf.py", "--fused"])
    assert args.zero_sharding == "auto"


# ---------------------------------------------------------------------------
# analysis: the auditor's optimizer-state specs + velint stray-collective
# ---------------------------------------------------------------------------

def test_auditor_clean_on_zero_step(eight_devices):
    from veles_tpu.analysis.trace import audit_fused_step
    wf = build(hidden=32, n_classes=16)
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    findings = audit_fused_step(step, x, y)
    assert not [f for f in findings if f.rule == "sharding-mismatch"], \
        [f.format() for f in findings]


def test_auditor_flags_broken_optstate_plan(eight_devices):
    """Seed a corrupted plan (padded not divisible / dropping elements):
    the auditor reports sharding-mismatch naming the optimizer state and
    stops before tracing."""
    from veles_tpu.analysis.trace import audit_fused_step
    from veles_tpu.parallel.mesh import ZeroLeaf
    wf = build(hidden=32, n_classes=16)
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    good = step.zero_plans()
    bad0 = dict(good[0])
    bad0["weights"] = ZeroLeaf(shape=(64, 32), size=2048, padded=2049,
                               local=512)
    bad0["bias"] = ZeroLeaf(shape=(32,), size=32, padded=16, local=4)
    step._zero_plan_cache = (bad0,) + tuple(good[1:])
    findings = audit_fused_step(step, x, y)
    mism = [f for f in findings if f.rule == "sharding-mismatch"]
    assert any("not divisible by the data axis" in f.message
               for f in mism)
    assert any("silently drop the tail" in f.message for f in mism)


def test_auditor_flags_state_plan_disagreement(eight_devices):
    """The live-state cross-check (the plan checks' independent
    ledger): a vel leaf whose stored flat length disagrees with the
    plan — e.g. a checkpoint restored into the wrong geometry — is a
    sharding-mismatch error, and the audit stops before tracing."""
    import jax.numpy as jnp

    from veles_tpu.analysis.trace import audit_fused_step
    wf = build(hidden=32, n_classes=16)
    x, y = first_batch(wf)
    mesh = make_mesh(eight_devices[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    state = step.init_state()
    bad_vel = list(state["vel"])
    bad0 = dict(bad_vel[0])
    k = next(iter(bad0))
    bad0[k] = jnp.zeros((int(np.shape(bad0[k])[0]) + 4,),
                        jnp.asarray(bad0[k]).dtype)
    bad_vel[0] = bad0
    state["vel"] = tuple(bad_vel)
    findings = audit_fused_step(step, x, y, state=state)
    mism = [f for f in findings if f.rule == "sharding-mismatch"]
    assert any("does not match the plan" in f.message for f in mism), \
        [f.format() for f in findings]
    # a clean state passes the same cross-check
    clean = audit_fused_step(step, x, y, state=step.init_state())
    assert not [f for f in clean if f.rule == "sharding-mismatch"], \
        [f.format() for f in clean]


def test_velint_stray_collective_rule():
    from veles_tpu.analysis.lint import lint_source
    bad = ("from jax import lax\n"
           "def step(g):\n"
           "    return lax.psum(g, 'data')\n")
    hits = lint_source(bad, "veles_tpu/znicz/unit.py")
    assert [f.rule for f in hits] == ["stray-collective"]
    # the registry and step modules legitimately place collectives
    assert lint_source(bad, "veles_tpu/parallel/fused.py") == []
    assert lint_source(bad, "veles_tpu/ops/variants.py") == []
    # suppression-with-justification works (the znicz TP psums)
    sup = ("from jax import lax\n"
           "def step(g):\n"
           "    # velint: disable=stray-collective\n"
           "    return lax.psum(g, 'data')\n")
    assert lint_source(sup, "veles_tpu/znicz/unit.py") == []
    # bare-name imports are caught too
    bare = ("from jax.lax import psum_scatter\n"
            "def step(g):\n"
            "    return psum_scatter(g, 'data')\n")
    assert [f.rule for f in
            lint_source(bare, "veles_tpu/loader/x.py")] \
        == ["stray-collective"]


# ---------------------------------------------------------------------------
# memory accounting plumbing (satellite: measured, not claimed)
# ---------------------------------------------------------------------------

def test_device_memory_stats_shape():
    from veles_tpu.parallel.memstats import device_memory_stats
    _ = jax.numpy.zeros((16, 16)) + 1       # ensure something is live
    stats = device_memory_stats()
    assert stats is not None
    assert stats["n_live_arrays"] >= 1
    assert stats["live_bytes_max"] > 0
    assert all(isinstance(v, int) for v in stats["live_bytes"].values())


def test_heartbeat_carries_mem(tmp_path):
    from veles_tpu.resilience.supervisor import (read_heartbeat,
                                                 write_heartbeat)
    hb = os.path.join(str(tmp_path), "hb.json")
    mem = {"n_live_arrays": 3, "live_bytes": {"0": 1024},
           "live_bytes_max": 1024}
    write_heartbeat(hb, 7, feed={"bytes_per_batch": 1,
                                 "epoch_log": ["dropped"]}, mem=mem)
    back = read_heartbeat(hb)
    assert back["epoch"] == 7
    assert back["mem"] == mem
    assert "epoch_log" not in back["feed"]
