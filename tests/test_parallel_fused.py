"""Tests for veles_tpu.parallel: the fused train step and its sharded
modes (SURVEY.md §4 "multi-device tests on a single host" — here an
8-device virtual CPU mesh from conftest.py).

Equivalence ladder:
  granular XLA path  ==  fused local step  ==  shard_map DP over 8 devices
                                           ==  GSPMD DP×TP over 4×2 mesh
"""

import jax
import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import XLADevice
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.parallel import make_mesh
from veles_tpu.znicz.standard_workflow import StandardWorkflow


def build(minibatch_size=48, max_epochs=2, layers=None):
    prng.seed_all(1234)
    loader = SyntheticClassifierLoader(
        n_classes=10, sample_shape=(8, 8), n_validation=96, n_train=480,
        minibatch_size=minibatch_size, noise=0.6)
    return StandardWorkflow(
        layers=layers or [
            {"type": "all2all_tanh", "output_sample_shape": 32,
             "weights_stddev": 0.05},
            {"type": "softmax", "output_sample_shape": 10,
             "weights_stddev": 0.05},
        ],
        loader=loader, loss="softmax", n_classes=10,
        decision_config={"max_epochs": max_epochs, "fail_iterations": 50},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="FusedTest")


def first_batch(wf):
    wf.initialize(device=XLADevice())
    ld = wf.loader
    # walk the schedule to the first TRAIN minibatch
    from veles_tpu.loader.base import TRAIN
    while True:
        ld.run()
        if ld.minibatch_class == TRAIN:
            return ld.minibatch_data.mem.copy(), ld.minibatch_labels.mem.copy()


def test_fused_matches_granular_one_step():
    """One fused step == one granular forward+backward+update pass on the
    same minibatch with the same initial weights."""
    wf_g = build()
    x, y = first_batch(wf_g)
    # granular: run the chain by hand on exactly this minibatch
    wf_g.loader.minibatch_data.reset(x)
    wf_g.loader.minibatch_labels.reset(y)
    for fwd in wf_g.forwards:
        fwd.run()
    wf_g.evaluator.run()
    for g in wf_g.gds:
        g.run()

    wf_f = build()
    first_batch(wf_f)  # same seeds -> same init weights & same first batch
    step = wf_f.build_fused_step()
    state = step.init_state()
    state, (loss, n_err) = step.train(state, x, y)
    step.write_back(state)

    for uf, ug in zip(wf_f.forwards, wf_g.forwards):
        np.testing.assert_allclose(uf.weights.mem, ug.weights.mem,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(uf.bias.mem, ug.bias.mem,
                                   rtol=1e-5, atol=1e-6)
    assert float(loss) == pytest.approx(float(wf_g.evaluator.loss), rel=1e-4)
    assert int(n_err) == int(wf_g.evaluator.n_err)


@pytest.mark.parametrize("mesh_kw,mode", [
    (dict(), "dp"),                 # 8-way data parallel, shard_map+pmean
    (dict(model=2), "gspmd"),       # 4×2 DP×TP via named shardings
    (dict(model=4, data=2), "gspmd"),
])
def test_sharded_matches_local(mesh_kw, mode, eight_devices):
    """The sharded step computes the SAME update as the local step: the
    all-reduce of per-shard mean grads == global mean grad."""
    wf_a = build()
    x, y = first_batch(wf_a)
    step_a = wf_a.build_fused_step()          # local single-device
    sa = step_a.init_state()
    sa, (loss_a, err_a) = step_a.train(sa, x, y)

    wf_b = build()
    first_batch(wf_b)
    mesh = make_mesh(**mesh_kw)
    step_b = wf_b.build_fused_step(mesh=mesh, mode=mode)
    sb = step_b.init_state()
    sb, (loss_b, err_b) = step_b.train(sb, x, y)

    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    assert int(err_a) == int(err_b)
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]), np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_train_accum_matches_full_batch(optimizer):
    """K-microbatch gradient accumulation == one full-batch step (same
    normalization, pad mask included), for SGD+momentum and Adam."""
    wf_a = build(minibatch_size=48)
    x, y = first_batch(wf_a)
    for gd in wf_a.gds:
        gd.optimizer = optimizer
    step_a = wf_a.build_fused_step()
    # a wrapped final microbatch: zero-weight pad rows in the mask
    w = np.ones(48, np.float32)
    w[-5:] = 0.0
    sa = step_a.init_state()
    sa, (loss_a, err_a) = step_a.train(sa, x, y, w)

    wf_b = build(minibatch_size=48)
    xb, yb = first_batch(wf_b)
    np.testing.assert_array_equal(x, xb)
    for gd in wf_b.gds:
        gd.optimizer = optimizer
    step_b = wf_b.build_fused_step()
    sb = step_b.init_state()
    sb, (loss_b, err_b) = step_b.train_accum(sb, xb, yb, 4, w)

    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    assert int(err_a) == int(err_b)
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_train_accum_dp_matches_local(eight_devices):
    """Accumulated step under shard_map DP == local accumulated step:
    the per-microbatch gradient psum composes with accumulation."""
    wf_a = build(minibatch_size=48)
    x, y = first_batch(wf_a)
    step_a = wf_a.build_fused_step()
    sa = step_a.init_state()
    sa, (loss_a, _) = step_a.train_accum(sa, x, y, 2)

    wf_b = build(minibatch_size=48)
    xb, yb = first_batch(wf_b)
    mesh = make_mesh(eight_devices[:4], data=4)
    step_b = wf_b.build_fused_step(mesh=mesh, mode="dp")
    sb = step_b.init_state()
    sb, (loss_b, _) = step_b.train_accum(sb, xb, yb, 2)

    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_run_fused_accum_steps_trains():
    """Workflow-level plumbing: run_fused(accum_steps=K) drives training
    through train_accum with the Decision bookkeeping intact."""
    wf = build(minibatch_size=48, max_epochs=3)
    wf.run_fused(accum_steps=4)
    assert wf.decision.best_validation_err < 96   # learns something
    assert wf.decision.epoch_number >= 1


def test_scaling_harness_virtual_mesh(eight_devices):
    """Smoke the scaling_efficiency harness itself on a >1-device mesh
    (round-2 verdict weak #7: the harness was only ever exercised at
    n=1 outside the dryrun path)."""
    from veles_tpu.parallel.distributed import scaling_efficiency
    wf = build(minibatch_size=32)
    wf.initialize(device=XLADevice())
    res = scaling_efficiency(wf, mesh_devices=list(eight_devices[:4]),
                             batch_per_chip=16, warmup=1, steps=3)
    assert res["chips"] == 4 and not res["trivial"]
    assert res["samples_per_sec_per_chip_1"] > 0
    assert res["scaling_efficiency"] > 0
    # the compiled 4-chip step must actually carry the gradient
    # all-reduce (r3 verdict weak #8: emit the collective counts so a
    # pod run is verifiable with zero new code)
    assert res["compiled_collectives_n_chips"]["all-reduce"] > 0


def test_workflow_stop_releases_unit_resources():
    """stop() (and an exception escaping the pump loop) must tear down
    unit-owned threads — round-2 verdict weak #6."""
    calls = []
    wf = build(max_epochs=1)
    wf.loader.stop = lambda: calls.append("loader")  # type: ignore
    wf.stop()
    assert "loader" in calls

    # exception mid-run still reaches teardown
    wf2 = build(max_epochs=1)
    wf2.initialize(device=XLADevice())
    calls2 = []
    wf2.loader.stop = lambda: calls2.append("loader")  # type: ignore

    def boom():
        raise RuntimeError("unit exploded")
    wf2.evaluator.run = boom  # type: ignore
    with pytest.raises(RuntimeError, match="unit exploded"):
        wf2.run()
    assert "loader" in calls2


def test_gspmd_tp_actually_partitions(eight_devices):
    """Round-2 verdict: numerics-only TP tests would also pass under
    silent replication. This asserts the PARTITIONING itself: after a
    gspmd step on a 2×4 (data×model) mesh, weights/velocities span the
    model axis with per-device buffers a quarter the global size, and the
    compiled module contains cross-device collectives."""
    wf = build()
    first_batch(wf)
    mesh = make_mesh(model=4, data=2)
    step = wf.build_fused_step(mesh=mesh, mode="gspmd")
    state = step.init_state()
    x = np.random.RandomState(0).randn(48, 8, 8).astype(np.float32)
    y = np.random.RandomState(0).randint(0, 10, 48)
    state, _ = step.train(state, x, y)

    from veles_tpu.parallel.mesh import MODEL_AXIS
    # layer 0: weights (64, 32), 32 % 4 == 0 -> COLUMN-parallel
    for part in ("params", "vel"):
        w = state[part][0]["weights"]
        assert tuple(w.sharding.spec) == (None, MODEL_AXIS), \
            (part, w.sharding)
        shapes = {s.data.shape for s in w.addressable_shards}
        assert shapes == {(64, 8)}, (part, shapes)  # quarter of 32/device
    assert {s.data.shape for s in
            state["params"][0]["bias"].addressable_shards} == {(8,)}
    # layer 1: input arrives feature-sharded, weights (32, 10) with
    # 32 % 4 == 0 -> ROW-parallel (the megatron pairing: one psum)
    w_last = state["params"][-1]["weights"]
    assert tuple(w_last.sharding.spec)[:1] == (MODEL_AXIS,), \
        w_last.sharding
    assert {s.data.shape for s in w_last.addressable_shards} == {(8, 10)}
    # its bias adds to the psum'd (replicated) output -> replicated
    assert {s.data.shape for s in
            state["params"][-1]["bias"].addressable_shards} == {(10,)}

    # compute is partitioned => the module must communicate: look for
    # cross-replica/partition collectives in the compiled HLO
    compiled = step._train_fn.lower(
        state, x, y, np.ones(48, np.float32)).compile()
    hlo = compiled.as_text()
    assert ("all-reduce" in hlo or "all-gather" in hlo
            or "collective-permute" in hlo or "reduce-scatter" in hlo), \
        "no collectives in compiled gspmd module — TP silently replicated?"


def test_run_fused_trains_and_decision_tracks(eight_devices):
    """run_fused drives the real Loader/Decision units: trains to low
    error on the 8-device DP mesh and leaves weights written back."""
    wf = build(max_epochs=3)
    mesh = make_mesh()
    w0 = None
    wf.initialize(device=XLADevice())
    w0 = wf.forwards[0].weights.mem.copy()
    wf.run_fused(mesh=mesh, mode="dp")
    assert wf.decision.epoch_number == 3
    assert wf.decision.best_validation_err <= 20, \
        wf.decision.best_validation_err
    assert not np.allclose(wf.forwards[0].weights.mem, w0)


def test_fused_conv_net_with_dropout_trains(eight_devices):
    """Conv+pool+LRN+dropout chain end-to-end under the fused DP step
    (dropout keys decorrelate per shard; eval minibatches skip dropout)."""
    prng.seed_all(77)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(8, 8, 1), n_validation=64, n_train=320,
        minibatch_size=32, noise=0.4)
    wf = StandardWorkflow(
        layers=[
            {"type": "conv_strictrelu", "n_kernels": 8, "kx": 3, "ky": 3,
             "weights_stddev": 0.1},
            {"type": "maxabs_pooling", "ksize": (2, 2)},
            {"type": "dropout", "dropout_ratio": 0.2},
            {"type": "softmax", "output_sample_shape": 4,
             "weights_stddev": 0.05},
        ],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 3, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="FusedConv")
    wf.run_fused(mesh=make_mesh(), mode="dp")
    assert wf.decision.best_validation_err <= 24, \
        wf.decision.best_validation_err


def test_mse_loss_fused():
    """MSE (autoencoder-style) fused path: identity target reconstruction
    error decreases."""
    prng.seed_all(5)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6, 6), n_validation=32, n_train=160,
        minibatch_size=32, noise=0.3, autoencoder=True)
    wf = StandardWorkflow(
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "weights_stddev": 0.1},
            {"type": "all2all", "output_sample_shape": (6, 6),
             "weights_stddev": 0.1},
        ],
        loader=loader, loss="mse",
        decision_config={"max_epochs": 15, "fail_iterations": 50},
        gd_config={"learning_rate": 0.02, "gradient_moment": 0.9},
        name="FusedAE")
    wf.run_fused()
    # reconstruction MSE (summed per validation pass) falls well below the
    # ~35/minibatch starting point
    assert wf.decision.best_validation_err < 5.0, wf.decision.epoch_metrics


def test_train_many_matches_sequential():
    """K scanned steps in one dispatch == K sequential train() calls."""
    import jax.numpy as jnp
    wf = build(minibatch_size=50)
    wf.initialize(device=None)
    step_a = wf.build_fused_step()
    step_b = wf.build_fused_step()
    sa = step_a.init_state()
    sb = step_b.init_state()
    rng = np.random.RandomState(0)
    K, B = 4, 50
    xs = rng.randn(K, B, 8, 8).astype(np.float32)
    ys = rng.randint(0, 10, (K, B))
    losses_seq = []
    for t in range(K):
        sa, (loss, _) = step_a.train(sa, xs[t], ys[t])
        losses_seq.append(float(loss))
    sb, (losses, n_errs) = step_b.train_many(sb, xs, ys)
    assert losses.shape == (K,)
    np.testing.assert_allclose(np.asarray(losses), losses_seq,
                               rtol=1e-5, atol=1e-6)
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_fused_velocity_roundtrip_nonbase_layers():
    """Momentum velocities for layer families whose GD twins use
    vel_<name> attributes (attention: vel_wq..., not the base vel_w/vel_b)
    survive write_back -> new fused step; a fresh step resumes with the
    exact velocity pytree instead of silently zeroing it."""
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    prng.seed_all(77)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(8, 16), n_validation=40, n_train=160,
        minibatch_size=40, noise=0.3)
    wf = StandardWorkflow(
        layers=[
            {"type": "attention", "n_heads": 2, "causal": False,
             "weights_stddev": 0.1},
            {"type": "softmax", "output_sample_shape": 4,
             "weights_stddev": 0.05},
        ],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="VelRoundTrip")
    wf.initialize(device=None)
    step = wf.build_fused_step()
    state = step.init_state()
    rng = np.random.RandomState(0)
    x = rng.randn(40, 8, 16).astype(np.float32)
    y = rng.randint(0, 4, 40)
    state, _ = step.train(state, x, y)
    state, _ = step.train(state, x, y)
    # attention velocities are non-trivial after 2 momentum steps
    att_vel = state["vel"][0]
    assert set(att_vel) == {"wq", "wk", "wv", "wo"}
    for k, v in att_vel.items():
        assert np.abs(np.asarray(v)).max() > 0, k
    step.write_back(state)
    # the GD twin now holds them under vel_wq/... and a NEW fused step
    # (fresh object, as after snapshot resume) seeds from those buffers
    step2 = wf.build_fused_step()
    s2 = step2.init_state()
    for k in att_vel:
        np.testing.assert_array_equal(np.asarray(s2["vel"][0][k]),
                                      np.asarray(att_vel[k]))


@pytest.mark.parametrize("mesh_kw,mode", [
    ({}, "dp"),
    ({"model": 2}, "gspmd"),
])
def test_train_many_sharded_matches_sequential(mesh_kw, mode,
                                               eight_devices):
    """scan-of-steps == K sequential steps on the 8-device mesh, for both
    the shard_map dp mode and the GSPMD dp x tp mode (VERDICT r1 #4: the
    dispatch-amortized hot loop must exist exactly where multi-chip DP
    pays per-step dispatch)."""
    mesh = make_mesh(eight_devices, **mesh_kw)
    wf = build(minibatch_size=48)
    wf.initialize(device=None)
    step_a = wf.build_fused_step(mesh=mesh, mode=mode)
    step_b = wf.build_fused_step(mesh=mesh, mode=mode)
    sa = step_a.init_state()
    sb = step_b.init_state()
    rng = np.random.RandomState(0)
    K, B = 3, 48
    xs = rng.randn(K, B, 8, 8).astype(np.float32)
    ys = rng.randint(0, 10, (K, B))
    losses_seq = []
    for t in range(K):
        sa, (loss, _) = step_a.train(sa, xs[t], ys[t])
        losses_seq.append(float(loss))
    sb, (losses, _) = step_b.train_many(sb, xs, ys)
    np.testing.assert_allclose(np.asarray(losses), losses_seq,
                               rtol=1e-5, atol=1e-6)
    for pa, pb in zip(sa["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_precision_type_config_sets_fused_dtype():
    """root.common.precision_type (the reference's global precision knob,
    SURVEY.md §2.2) governs the fused step's default compute dtype; an
    explicit compute_dtype argument still wins."""
    from veles_tpu.config import root
    prev = root.common.precision_type
    try:
        root.common.precision_type = "bfloat16"
        wf = build()
        wf.initialize(device=None)
        step = wf.build_fused_step()
        assert step.compute_dtype == "bfloat16"
        state = step.init_state()
        rng = np.random.RandomState(0)
        x = rng.randn(48, 8, 8).astype(np.float32)
        y = rng.randint(0, 10, 48)
        state, (loss, _) = step.train(state, x, y)
        assert np.isfinite(float(loss))
        # master weights stay f32 regardless of compute precision
        assert state["params"][0]["weights"].dtype == np.float32
        # explicit argument overrides the knob
        assert wf.build_fused_step(
            compute_dtype="float32").compute_dtype == "float32"
        root.common.precision_type = "float32"
        assert wf.build_fused_step().compute_dtype is None
    finally:
        root.common.precision_type = prev


def test_seq_mode_rejects_bad_labels(eight_devices):
    """seq mode must fail with a clear shape message when labels cannot
    be brought to per-token (N, S) form (ADVICE r2)."""
    from veles_tpu.config import root
    from veles_tpu.samples.char_transformer import create_workflow
    prng.seed_all(11)
    prev = root.char_transformer.parallel_mode
    try:
        root.char_transformer.parallel_mode = "ring"
        wf = create_workflow()
        wf.initialize(device=None)
        mesh = make_mesh(model=1, seq=4)
        step = wf.build_fused_step(mesh, mode="seq")
        state = step.init_state()
        x = wf.loader.data.mem[:8]
        bad_y = np.zeros(8, np.int64)  # classifier-shaped: not per-token
        with pytest.raises(ValueError, match="per-token"):
            step.train(state, x, bad_y)
    finally:
        root.char_transformer.parallel_mode = prev


@pytest.mark.parametrize("mesh_kw,mode", [
    (None, "local"),
    (dict(), "dp"),
    (dict(model=2), "gspmd"),
])
def test_fused_adam_trains(mesh_kw, mode, eight_devices):
    """gd_config={"optimizer": "adam"} threads through pair_gd_configs
    into the fused update: Adam state ({m, v, t}) replaces the velocity
    tree, t counts steps, sharded modes carry the Adam tree through their
    state specs, and every mode computes the SAME update as local."""
    def build_adam():
        prng.seed_all(99)
        loader = SyntheticClassifierLoader(
            n_classes=10, sample_shape=(8, 8), n_validation=48,
            n_train=240, minibatch_size=48, noise=0.6)
        return StandardWorkflow(
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 32,
                 "weights_stddev": 0.05},
                {"type": "softmax", "output_sample_shape": 10,
                 "weights_stddev": 0.05},
            ],
            loader=loader, loss="softmax", n_classes=10,
            decision_config={"max_epochs": 2, "fail_iterations": 50},
            gd_config={"learning_rate": 3e-3, "optimizer": "adam"},
            name="AdamTest")

    wf_ref = build_adam()
    x, y = first_batch(wf_ref)
    step_ref = wf_ref.build_fused_step()
    s_ref = step_ref.init_state()
    assert set(s_ref["vel"][0]) == {"m", "v", "t"}
    losses = []
    for _ in range(5):
        s_ref, (loss, _err) = step_ref.train(s_ref, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert int(s_ref["vel"][0]["t"]) == 5

    if mesh_kw is None:
        return
    wf_b = build_adam()
    first_batch(wf_b)
    mesh = make_mesh(**mesh_kw)
    step_b = wf_b.build_fused_step(mesh=mesh, mode=mode)
    sb = step_b.init_state()
    for _ in range(5):
        sb, _ = step_b.train(sb, x, y)
    for pa, pb in zip(s_ref["params"], sb["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=2e-5, atol=2e-6)


# -- a dense layer's weight gradient from gathered operands (PR 29) ----------
# On a dp mesh the replicated update sums float32 partial gradients with an
# all-reduce, except for a dense layer whose weights outweigh its batch of
# activations: there every chip all-gathers the layer's operands and forms
# the whole gradient itself (ops.xla.dense_gathered_grad), chosen per layer
# from shapes and the mesh (parallel.fused.dense_grad_form).

#: name -> (layers, sample shape, global batch, units that gather on 4 chips)
GATHER_NETS = {
    # 2 rows a chip: 64x512 and the 512x10 head both outweigh their operands
    "every_dense_gathers": (
        [{"type": "all2all_tanh", "output_sample_shape": 512,
          "weights_stddev": 0.05},
         {"type": "softmax", "output_sample_shape": 10,
          "weights_stddev": 0.05}], (8, 8), 8, {0, 1}),
    # 8 rows a chip: the head's 20 KB of gradient is cheaper all-reduced
    "one_gathers_one_sums": (
        [{"type": "all2all_tanh", "output_sample_shape": 512,
          "weights_stddev": 0.05},
         {"type": "softmax", "output_sample_shape": 10,
          "weights_stddev": 0.05}], (8, 8), 32, {0}),
    # a conv leaf never gathers; the 288x256 dense layer behind it does
    "conv_then_dense": (
        [{"type": "conv_tanh", "n_kernels": 8, "kx": 3, "ky": 3,
          "weights_stddev": 0.1},
         {"type": "all2all_tanh", "output_sample_shape": 256,
          "weights_stddev": 0.05},
         {"type": "softmax", "output_sample_shape": 10,
          "weights_stddev": 0.05}], (8, 8, 1), 32, {1}),
}


def _gather_net(name, **step_kw):
    """(workflow, step, state) of one of GATHER_NETS, same seed each call."""
    layers, shape, batch, _ = GATHER_NETS[name]
    prng.seed_all(1234)
    loader = SyntheticClassifierLoader(
        n_classes=10, sample_shape=shape, n_validation=batch,
        n_train=4 * batch, minibatch_size=batch, noise=0.6)
    wf = StandardWorkflow(
        layers=layers, loader=loader, loss="softmax", n_classes=10,
        decision_config={"max_epochs": 1, "fail_iterations": 50},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="Gather")
    wf.initialize(device=None)
    step = wf.build_fused_step(**step_kw)
    return wf, step, step.init_state()


def _gather_batch(name, seed=0):
    _, shape, batch, _ = GATHER_NETS[name]
    rng = np.random.RandomState(seed)
    return (rng.randn(batch, *shape).astype(np.float32),
            rng.randint(0, 10, batch))


def _assert_same_state(local, dp):
    """Parameters and velocity agree with the local step's, and every
    chip's copy of every dp leaf is the same to the bit."""
    for part in ("params", "vel"):
        for la, lb in zip(local[part], dp[part]):
            for k in la:
                np.testing.assert_allclose(
                    np.asarray(la[k]), np.asarray(lb[k]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{part} {k}")
                copies = [np.asarray(s.data)
                          for s in lb[k].addressable_shards]
                assert len(copies) == 4
                for c in copies[1:]:
                    np.testing.assert_array_equal(copies[0], c)


def _collectives(step, state, x, y):
    """(shapes all-reduced, shapes all-gathered) in the compiled step."""
    import re
    txt = step._train_fn.lower(
        state, x, y, np.ones(len(x), np.float32)).compile().as_text()
    found = {"all-reduce": [], "all-gather": []}
    for line in txt.splitlines():
        m = re.search(r" = (.*?) (all-reduce|all-gather)(-start)?\(", line)
        if m:
            found[m.group(2)] += re.findall(r"\w+\[[\d,]*\]", m.group(1))
    return found["all-reduce"], found["all-gather"]


@pytest.mark.parametrize("net", sorted(GATHER_NETS))
def test_dp_gathered_grad_matches_local(net, eight_devices):
    """Three dp steps over four chips == three local steps at the global
    batch, whichever way each leaf's gradient crossed the mesh; the
    compiled step all-reduces no gathered layer's [in, out] gradient and
    all-gathers its operands; variant_table() reports what was traced."""
    gathers = GATHER_NETS[net][3]
    x, y = _gather_batch(net)
    _, local, sa = _gather_net(net)
    wf, dp, sb = _gather_net(
        net, mesh=make_mesh(eight_devices[:4], data=4), mode="dp")
    assert "grad_exchange" not in local.variant_table()
    before = dp.variant_table()["grad_exchange"]
    for _ in range(3):
        sa, (loss_a, _) = local.train(sa, x, y)
        sb, (loss_b, _) = dp.train(sb, x, y)
        assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    _assert_same_state(sa, sb)

    rows = len(x) // 4
    assert dp._gathered_units(rows) == frozenset(gathers)
    reduced, gathered = _collectives(dp, sb, x, y)
    for i, u in enumerate(wf.forwards):
        w = u.weights.mem
        shape = "f32[" + ",".join(map(str, w.shape)) + "]"
        if i in gathers:
            fan_in, fan_out = w.shape
            assert shape not in reduced, (shape, reduced)
            assert f"f32[{len(x)},{fan_in}]" in gathered, gathered
            assert f"f32[{len(x)},{fan_out}]" in gathered, gathered
        else:
            assert shape in reduced, (shape, reduced)
    assert len(gathered) == 2 * len(gathers)
    after = dp.variant_table()["grad_exchange"]
    assert after == before      # the loader's batch is the batch fed
    n_units = sum(1 for u in wf.forwards if u.weights)
    assert after.startswith(
        f"{len(gathers)} of {n_units} units gather at {rows} rows x 4 chips")


@pytest.mark.parametrize("case", ["accum", "pad_mask"])
def test_dp_gathered_grad_accum_and_pad_mask(case, eight_devices):
    """The gathered gradient under gradient accumulation (two microbatches:
    each one's gathered gradient is global already, the sum is exchanged
    by nobody) and with a pad mask that zeroes the last rows of one shard
    (their dY is zero, so they drop out of the gathered product too)."""
    net = "one_gathers_one_sums"
    x, y = _gather_batch(net, seed=3)
    w = np.ones(len(x), np.float32)
    w[12:16] = 0.0              # the last rows of the second chip's shard
    _, local, sa = _gather_net(net)
    _, dp, sb = _gather_net(
        net, mesh=make_mesh(eight_devices[:4], data=4), mode="dp")
    for _ in range(3):
        if case == "accum":
            sa, (loss_a, _) = local.train_accum(sa, x, y, 2, w)
            sb, (loss_b, _) = dp.train_accum(sb, x, y, 2, w)
        else:
            sa, (loss_a, _) = local.train(sa, x, y, w)
            sb, (loss_b, _) = dp.train(sb, x, y, w)
        assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    _assert_same_state(sa, sb)
    assert dp._gathered_units(len(x) // (8 if case == "accum" else 4)) \
        == frozenset({0})


@pytest.mark.parametrize("n,rows,fan_in,fan_out,itemsize,form", [
    # VGG-16 at 64 a chip x 4, bfloat16: 411 MB against 15 MB, 67 against
    # 4.2, 16 against 2.6 (ISSUE 29)
    (4, 64, 25088, 4096, 2, "gather"),
    (4, 64, 4096, 4096, 2, "gather"),
    (4, 64, 4096, 1000, 2, "gather"),
    # AlexNet at 256 a chip x 4: 151 MB against 27 MB, 67 against 17; the
    # head's 16 MB against 10 MB of operands and 6 GFLOP is under the
    # margin
    (4, 256, 9216, 4096, 2, "gather"),
    (4, 256, 4096, 4096, 2, "gather"),
    (4, 256, 4096, 1000, 2, "psum"),
    # a small layer under a large batch: 0.3 MB against 7 MB
    (4, 1024, 784, 100, 4, "psum"),
    # one chip sends nothing either way
    (1, 64, 25088, 4096, 2, "psum"),
    (1, 2, 64, 512, 4, "psum"),
])
def test_dense_grad_form_table(n, rows, fan_in, fan_out, itemsize, form):
    from veles_tpu.parallel.fused import dense_grad_form
    assert dense_grad_form(n, rows, fan_in, fan_out, itemsize) == form


@pytest.mark.parametrize("kind", ["zero_on", "ep", "one_shard"])
def test_grad_exchange_absent_where_nothing_gathers(kind, eight_devices):
    """variant_table() names `grad_exchange` only where the replicated dp
    update traced it: not under ZeRO (the reduce-scatter stays), not
    under EP (autodiff's own psum stays); on a one-chip data axis it
    reports that nothing gathers."""
    mesh4 = make_mesh(eight_devices[:4], data=4)
    if kind == "zero_on":
        _, step, _ = _gather_net("one_gathers_one_sums", mesh=mesh4,
                                 mode="dp", zero_sharding="on")
        assert step.zero_active
    elif kind == "ep":
        prng.seed_all(1234)
        loader = SyntheticClassifierLoader(
            n_classes=4, sample_shape=(12,), n_validation=32, n_train=128,
            minibatch_size=32, noise=0.3)
        wf = StandardWorkflow(
            layers=[{"type": "moe", "n_experts": 4, "hidden": 16,
                     "capacity_factor": 4.0, "weights_stddev": 0.2},
                    {"type": "softmax", "output_sample_shape": 4,
                     "weights_stddev": 0.05}],
            loader=loader, loss="softmax", n_classes=4,
            decision_config={"max_epochs": 1, "fail_iterations": 50},
            gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
            name="GatherEP")
        wf.initialize(device=None)
        step = wf.build_fused_step(mesh=mesh4, mode="dp", ep=True)
    else:
        _, step, state = _gather_net(
            "every_dense_gathers",
            mesh=make_mesh(eight_devices[:1], data=1), mode="dp")
        x, y = _gather_batch("every_dense_gathers")
        step.train(state, x, y)
        assert step.variant_table()["grad_exchange"].startswith(
            "0 of 2 units gather at 8 rows x 1 chips")
        return
    assert step._gathered_units(8) == frozenset()
    assert "grad_exchange" not in step.variant_table()
    assert all(u.grad_gather_axis_name is None for u in step.forwards
               if hasattr(u, "grad_gather_axis_name"))


@pytest.mark.parametrize("activation", ["linear", "strictrelu"])
def test_dense_without_axis_is_plain_autodiff(activation):
    """With no axis name `all2all_forward` IS what it was: the jaxpr of
    its value and gradient is that of `act(x2 @ w + b)` written out, so
    local, gspmd, seq and the one-chip cells trace what they traced."""
    from veles_tpu.ops import xla as ox
    rng = np.random.RandomState(0)
    x = rng.randn(4, 3, 2).astype(np.float32)
    w = rng.randn(6, 5).astype(np.float32)
    b = rng.randn(5).astype(np.float32)

    def plain(x, w, b):
        return ox.act_forward(activation,
                              x.reshape(x.shape[0], -1) @ w + b).sum()

    def dense(x, w, b):
        return ox.all2all_forward(x, w, b, activation).sum()

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2))  # noqa: E731
    assert str(jax.make_jaxpr(grad(dense))(x, w, b)) \
        == str(jax.make_jaxpr(grad(plain))(x, w, b))
    assert "custom_vjp" not in str(jax.make_jaxpr(grad(dense))(x, w, b))
