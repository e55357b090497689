"""Quantized + hierarchical `grad_reduce` (ISSUE 12; EQuARX, arxiv
2506.17615).

The acceptance contracts: (1) the jax quantize/dequantize twins match
the ops.reference goldens BITWISE; (2) every family member passes the
equivalence ledger (shard_map exchange vs the psum golden, flat int8
exactly the reference-quantized exchange); (3) the hierarchical variant
is trajectory-EQUAL to the flat scatter at rtol 1e-5 on the 8-device
CPU mesh as (hosts=2, local=4); (4) the int8 variants' trained loss
stays within the stated rel 5e-2 of the f32 path (docs/SCALING.md) —
and error feedback tightens it; (5) the modeled DCN bytes of the int8
variants are <= 0.30x the f32 variant's; (6) the error-feedback slot
rides same-geometry checkpoints and is DROPPED (never mis-sharded)
across a data-axis change; (7) the auditor polices the 2-axis geometry
and the live EF state; (8) the flash_attn search winner's tiling
reaches the seq-parallel ring hop.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from veles_tpu.ops import reference as ref
from veles_tpu.ops import templates, variants
from veles_tpu.parallel import make_mesh
from veles_tpu.parallel.fused import FusedTrainStep
from veles_tpu.parallel.mesh import DATA_AXIS, zero_ef_plan, zero_plan
from tests.test_zero_sharding import build, first_batch

LOCAL_ENV = variants.GRAD_REDUCE_LOCAL_ENV


@pytest.fixture(autouse=True)
def _clean_selection():
    prev = variants.selected("grad_reduce")
    yield
    if prev is None:
        variants.clear_selection("grad_reduce")
    else:
        variants.select("grad_reduce", prev)


# ---------------------------------------------------------------------------
# 1. bitwise quantize/dequantize roundtrip vs ops.reference
# ---------------------------------------------------------------------------

def test_q8_roundtrip_bitwise():
    rs = np.random.RandomState(3)
    for rows, cols, blk in ((2, 512, 128), (5, 96, 32), (1, 64, 64)):
        x = rs.randn(rows, cols).astype(np.float32) * 3.0
        x[0, :blk] = 0.0        # an all-zero block: scale 1, codes 0
        qj, sj = variants.q8_encode(jnp.asarray(x), blk)
        qg, sg = ref.quantize_blockwise(x, blk)
        np.testing.assert_array_equal(np.asarray(qj), qg)
        np.testing.assert_array_equal(np.asarray(sj), sg)
        np.testing.assert_array_equal(
            np.asarray(variants.q8_decode(qj, sj, blk)),
            ref.dequantize_blockwise(qg, sg, blk))
    # codes saturate at +-127 and zero blocks decode to exact zeros
    assert np.abs(qg).max() <= 127
    np.testing.assert_array_equal(
        ref.dequantize_blockwise(qg, sg, blk)[0, :blk], 0.0)


# ---------------------------------------------------------------------------
# 2. equivalence ledger over the family (named + generated points)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [
    "f32", "bf16", "int8_block", "int8_ef", "hier2",
    "wire[dt=int8,blk=64,ef=1,hier=1]",
    "wire[dt=bf16,blk=128,ef=0,hier=1]",
])
def test_grad_reduce_equivalence_ledger(name, eight_devices,
                                        monkeypatch):
    monkeypatch.setenv(LOCAL_ENV, "4")
    rec = templates.check_equivalence("grad_reduce", name, force=True)
    assert rec["status"] == "pass", rec
    assert templates.passed("grad_reduce", name)


def test_search_cannot_time_ungated_candidate(tmp_path, monkeypatch):
    """The structural gate on the new family: a candidate whose
    contract LIES (claims pass without running) is caught by the
    timing path's own ledger check."""
    from veles_tpu.ops import autotune as at
    monkeypatch.setitem(templates.CONTRACTS, "grad_reduce",
                        lambda apply: (_ for _ in ()).throw(
                            AssertionError("refused")))
    templates.clear_ledger()
    try:
        rep = at.search_op(
            "grad_reduce", budget=6,
            cache=at.AutotuneCache(str(tmp_path / "c.json")))
        # every trial failed equivalence -> nothing timed, no winner
        assert rep["source"] == "error"
        assert all(t["outcome"] == "equiv_fail" for t in rep["trace"])
    finally:
        templates.clear_ledger()


# ---------------------------------------------------------------------------
# 3+4. trajectories on the (2 x 4) CPU mesh
# ---------------------------------------------------------------------------

def _traj(name, mesh, steps=4):
    variants.select("grad_reduce", name)
    wf = build()
    x, y = first_batch(wf)
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    assert step.zero_active, step.zero_reason
    s = step.init_state()
    loss = None
    for _ in range(steps):
        s, (loss, _) = step.train(s, x, y)
    return step, s, float(loss)


def test_hier_trajectory_equals_flat(eight_devices, monkeypatch):
    """Acceptance: the two-level decomposition verified on the
    8-device CPU mesh as (hosts=2, local=4), trajectory-equal to the
    flat reduce-scatter at rtol 1e-5."""
    monkeypatch.setenv(LOCAL_ENV, "4")
    mesh = make_mesh(jax.devices()[:8])
    _, sf, lf = _traj("f32", mesh)
    step_h, sh, lh = _traj("hier2", mesh)
    acct = step_h.collective_accounting()
    assert acct["geometry"] == {"hosts": 2, "local": 4}
    assert lh == pytest.approx(lf, rel=1e-5)
    for pa, pb in zip(sf["params"], sh["params"]):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]),
                                       np.asarray(pb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_int8_trained_loss_within_tolerance(eight_devices, monkeypatch):
    """Acceptance: the quantized variants' end-to-end CPU-mesh trained
    loss stays within the stated rel 5e-2 of the f32 path; error
    feedback exists, updates, and does not worsen plain int8."""
    monkeypatch.setenv(LOCAL_ENV, "2")
    mesh = make_mesh(jax.devices()[:4])
    _, _, lf = _traj("f32", mesh, steps=5)
    _, _, lq = _traj("int8_block", mesh, steps=5)
    step_e, se, le = _traj("int8_ef", mesh, steps=5)
    assert abs(lq - lf) / abs(lf) < 5e-2
    assert abs(le - lf) / abs(lf) < 5e-2
    # the EF slot exists, is sharded over the data axis, and carries a
    # non-zero residual after training
    assert "ef" in se
    leaf = se["ef"][0]["weights"]
    assert DATA_AXIS in tuple(leaf.sharding.spec)
    total = sum(float(np.abs(np.asarray(v)).sum())
                for layer in se["ef"] for v in layer.values())
    assert total > 0.0
    # scanned hot loop carries the residual through lax.scan
    wf = build()
    x, y = first_batch(wf)
    variants.select("grad_reduce", "int8_ef")
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    s = step.init_state()
    s, (losses, _) = step.train_repeat(s, x, y, 2)
    assert losses.shape == (2,) and np.isfinite(np.asarray(losses)).all()


def test_variant_table_and_cached_resolution(eight_devices):
    """variant_table names the generated winner, and the step's cached
    resolution keeps reported == traced even across a registry
    re-selection (the EF slot's geometry depends on it)."""
    gen = "wire[dt=int8,blk=128,ef=1,hier=0]"
    variants.select("grad_reduce", gen)
    wf = build()
    first_batch(wf)
    mesh = make_mesh(jax.devices()[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    assert step.variant_table()["grad_reduce"] == gen
    assert step.ef_active()
    variants.select("grad_reduce", "f32")      # mid-life re-selection
    assert step.variant_table()["grad_reduce"] == gen
    assert step.ef_active()


# ---------------------------------------------------------------------------
# 5. the byte model + the counter family (the bytes-moved claim)
# ---------------------------------------------------------------------------

def test_byte_model_ratios(monkeypatch):
    monkeypatch.setenv(LOCAL_ENV, "4")
    e, n = 100_000, 8
    f32 = variants.grad_reduce_bytes("f32", e, n)
    for name in ("int8_block", "int8_ef"):
        b = variants.grad_reduce_bytes(name, e, n)
        # acceptance: DCN-leg bytes/step <= 0.30x the f32 variant
        assert b["dcn_bytes"] / f32["dcn_bytes"] <= 0.30
    hier = variants.grad_reduce_bytes("hier2", e, n)
    # the DCN leg moves only the 1/local slices (L=4 here)
    assert hier["dcn_bytes"] == pytest.approx(f32["dcn_bytes"] / 4,
                                              rel=0.01)
    assert variants.grad_reduce_bytes("bf16", e, n)["dcn_bytes"] \
        == pytest.approx(f32["dcn_bytes"] / 2, rel=0.01)
    # degenerate single-host geometry: everything is ICI
    monkeypatch.delenv(LOCAL_ENV, raising=False)
    flat = variants.grad_reduce_bytes("f32", e, 8)
    if variants.grad_reduce_geometry(8)[0] == 1:
        assert flat["dcn_bytes"] == 0


def test_driver_feeds_collective_counters(eight_devices, monkeypatch):
    """run_fused on a zero dp mesh increments
    veles_collective_bytes_total by the step's modeled egress per
    dispatched train step — reported from the counters, as the
    acceptance criterion requires."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.telemetry import metrics as tm
    monkeypatch.setenv(LOCAL_ENV, "2")
    variants.select("grad_reduce", "int8_block")
    reg = tm.default_registry()
    fam = reg.counter("veles_collective_bytes_total",
                      labelnames=("op", "leg"))
    before = fam.labels(op="grad_reduce", leg="dcn").value
    wf = build()
    wf.run_fused(epochs=1, device=XLADevice(),
                 mesh=make_mesh(jax.devices()[:4]), mode="dp",
                 zero_sharding="on")
    after = fam.labels(op="grad_reduce", leg="dcn").value
    step = wf.build_fused_step(mesh=make_mesh(jax.devices()[:4]),
                               mode="dp", zero_sharding="on")
    acct = step.collective_accounting()
    assert acct["variant"] == "int8_block"
    moved = after - before
    assert moved > 0 and moved % acct["dcn_bytes"] == 0
    # the all-gather leg is attributed under its own op label
    assert fam.labels(op="param_allgather", leg="dcn").value > 0


# ---------------------------------------------------------------------------
# 6. checkpoint: the EF slot across geometry changes (satellite)
# ---------------------------------------------------------------------------

def test_ef_snapshot_across_data_axis_change(tmp_path, eight_devices,
                                             monkeypatch):
    """Save under N=4 int8+EF, restore into N=2: velocities reshard
    (the PR-6 path), the EF residual is DROPPED to zeros — never
    mis-sharded — and training resumes. Same-geometry restore carries
    it; a restore into a stateless-variant step drops the slot."""
    from veles_tpu.parallel.checkpoint import restore_state, save_state
    monkeypatch.setenv(LOCAL_ENV, "2")
    variants.select("grad_reduce", "int8_ef")
    wf = build()
    x, y = first_batch(wf)
    mesh4 = make_mesh(jax.devices()[:4])
    step4 = FusedTrainStep(wf, mesh=mesh4, mode="dp", zero_sharding="on")
    s = step4.init_state()
    for _ in range(2):
        s, _ = step4.train(s, x, y)
    save_state(s, str(tmp_path))

    # same geometry: the residual rides the checkpoint
    wf2 = build()
    first_batch(wf2)
    stepA = FusedTrainStep(wf2, mesh=mesh4, mode="dp",
                           zero_sharding="on")
    rA = restore_state(stepA, str(tmp_path))
    np.testing.assert_allclose(np.asarray(rA["ef"][0]["weights"]),
                               np.asarray(s["ef"][0]["weights"]))

    # N change: vel resharded, EF dropped to zeros, trains on
    wf3 = build()
    first_batch(wf3)
    step2 = FusedTrainStep(wf3, mesh=make_mesh(jax.devices()[:2]),
                           mode="dp", zero_sharding="on")
    rB = restore_state(step2, str(tmp_path))
    assert "ef" in rB
    for layer in rB["ef"]:
        for v in layer.values():
            np.testing.assert_array_equal(np.asarray(v), 0.0)
    v = rB["vel"][0]["weights"]
    assert v.ndim == 1 and DATA_AXIS in tuple(v.sharding.spec)
    rB, (loss, _) = step2.train(rB, x, y)
    assert np.isfinite(float(loss))

    # into a stateless-variant step: the slot is dropped cleanly
    variants.select("grad_reduce", "f32")
    wf4 = build()
    first_batch(wf4)
    stepC = FusedTrainStep(wf4, mesh=mesh4, mode="dp",
                           zero_sharding="on")
    rC = restore_state(stepC, str(tmp_path))
    assert "ef" not in rC
    rC, (lossC, _) = stepC.train(rC, x, y)
    assert np.isfinite(float(lossC))


# ---------------------------------------------------------------------------
# 7. the auditor: 2-axis geometry + live EF state (seeded + clean)
# ---------------------------------------------------------------------------

def test_auditor_hier_geometry(eight_devices, monkeypatch):
    from veles_tpu.analysis.trace import audit_fused_step
    variants.select("grad_reduce", "hier2")
    wf = build(hidden=32, n_classes=16)
    x, y = first_batch(wf)
    mesh = make_mesh(jax.devices()[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    # seeded: an explicit local-group override that cannot tile the
    # data axis is a sharding-mismatch ERROR (audit stops pre-trace)
    monkeypatch.setenv(LOCAL_ENV, "3")
    bad = audit_fused_step(step, x, y)
    assert any(f.rule == "sharding-mismatch"
               and "does not divide the data axis" in f.message
               for f in bad), [f.format() for f in bad]
    # clean: a dividing override passes with no sharding findings
    monkeypatch.setenv(LOCAL_ENV, "2")
    clean = audit_fused_step(step, x, y)
    assert not [f for f in clean if f.rule == "sharding-mismatch"
                and f.severity == "error"], \
        [f.format() for f in clean]
    # degenerate single-level geometry: a warning, not an error
    monkeypatch.setenv(LOCAL_ENV, "4")      # local == data axis -> h=1
    warn = audit_fused_step(step, x, y)
    hits = [f for f in warn if f.rule == "sharding-mismatch"]
    assert hits and all(f.severity == "warn" for f in hits), \
        [f.format() for f in warn]


def test_auditor_flags_missized_ef_state(eight_devices, monkeypatch):
    from veles_tpu.analysis.trace import audit_fused_step
    monkeypatch.setenv(LOCAL_ENV, "2")
    variants.select("grad_reduce", "int8_ef")
    wf = build(hidden=32, n_classes=16)
    x, y = first_batch(wf)
    mesh = make_mesh(jax.devices()[:4])
    step = FusedTrainStep(wf, mesh=mesh, mode="dp", zero_sharding="on")
    state = step.init_state()
    # clean state passes
    clean = audit_fused_step(step, x, y, state=state)
    assert not [f for f in clean if f.rule == "sharding-mismatch"], \
        [f.format() for f in clean]
    # seeded: a residual hand-carried across a geometry change
    bad_ef = list(state["ef"])
    layer0 = dict(bad_ef[0])
    k = next(iter(layer0))
    layer0[k] = jnp.zeros((int(np.shape(layer0[k])[0]) // 2,),
                          jnp.float32)
    bad_ef[0] = layer0
    state["ef"] = tuple(bad_ef)
    findings = audit_fused_step(step, x, y, state=state)
    assert any(f.rule == "sharding-mismatch"
               and "error-feedback residual" in f.message
               for f in findings), [f.format() for f in findings]


# ---------------------------------------------------------------------------
# 8. the ring hop consumes the flash_attn search winner (satellite)
# ---------------------------------------------------------------------------

def test_ring_params_from_flash_winner():
    from veles_tpu.znicz.attention import MultiHeadAttention
    u = MultiHeadAttention.__new__(MultiHeadAttention)
    u.variant_override = None
    prev = variants.selected("flash_attn")
    try:
        variants.select("flash_attn",
                        "pallas[blk_q=128,blk_k=256,kv_order=rev,drop=0]")
        assert u.ring_params() == {"kv_block": 256, "kv_order": "rev"}
        variants.select("flash_attn", "pallas")     # hand incumbent
        assert u.ring_params() == {"kv_block": 1024, "kv_order": "fwd"}
        variants.select("flash_attn", "xla_mha")    # einsum golden
        assert u.ring_params() == {}
    finally:
        if prev is None:
            variants.clear_selection("flash_attn")
        else:
            variants.select("flash_attn", prev)


def test_ring_path_traces_selected_point(eight_devices, monkeypatch):
    """A seq-mode trace of the attention unit routes the selected
    generated point's (blk_k, kv_order) into ring_attention — asserted
    on the actual traced call, and the rev order is numerically equal
    to fwd (online softmax is order-invariant)."""
    from veles_tpu.ops import attention as oa
    seen = {}
    real = oa.ring_attention

    def spy(q, k, v, axis_name, **kw):
        seen.update(kw)
        return real(q, k, v, axis_name, **kw)

    monkeypatch.setattr(oa, "ring_attention", spy)
    prev = variants.selected("flash_attn")
    try:
        variants.select("flash_attn",
                        "pallas[blk_q=128,blk_k=128,kv_order=rev,drop=0]")
        from veles_tpu.znicz.attention import MultiHeadAttention
        u = MultiHeadAttention.__new__(MultiHeadAttention)
        u.variant_override = None
        u.n_heads, u.head_dim, u.causal = 2, 4, True
        u.parallel_mode, u.residual = "ring", False
        u.use_flash = "auto"
        u.model_axis_name = None
        mesh = make_mesh(jax.devices()[:4], seq=4, data=1)
        rs = np.random.RandomState(0)
        # S=1024 over 4 seq shards -> s_local 256 > kv_block 128, so
        # the inner block scan (where kv_order matters) really runs
        x = rs.randn(1, 1024, 8).astype(np.float32)
        params = {"wq": rs.randn(8, 8).astype(np.float32),
                  "wk": rs.randn(8, 8).astype(np.float32),
                  "wv": rs.randn(8, 8).astype(np.float32),
                  "wo": rs.randn(8, 8).astype(np.float32)}

        def body(xx):
            return u._apply(params, xx, axis_name="seq")

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=P(None, "seq", None),
                              out_specs=P(None, "seq", None)))
        y_rev = np.asarray(f(x))
        assert seen.get("kv_block") == 128
        assert seen.get("kv_order") == "rev"
        variants.select("flash_attn",
                        "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=0]")
        y_fwd = np.asarray(jax.jit(shard_map(
            body, mesh=mesh, in_specs=P(None, "seq", None),
            out_specs=P(None, "seq", None)))(x))
        np.testing.assert_allclose(y_rev, y_fwd, rtol=1e-5, atol=1e-5)
    finally:
        if prev is None:
            variants.clear_selection("flash_attn")
        else:
            variants.select("flash_attn", prev)


# ---------------------------------------------------------------------------
# the whole registry is template-covered (carried ROADMAP item)
# ---------------------------------------------------------------------------

def test_templates_cover_whole_registry_but_dropout():
    """maxpool/conv_stem were the last registry ops with no generated
    axes; dropout stays resolution-only by design (its variants differ
    by RNG stream, not by a tunable config space), and serve_forward
    (ISSUE 15) is a closed named wire family the SERVING tier gates
    through the ledger — it carries a contract but no searched space
    or bench (there is nothing to time outside a serving round). `lrn`
    has two lowerings and no axes: platform (`variants.resolve`) and
    shape (`pallas_kernels.lrn_view`) choose between them; so have `hc`
    (ISSUE 34; `pallas_kernels.hc_view`) and `dsa` (ISSUE 35;
    `pallas_kernels.dsa_view`)."""
    covered = set(templates.template_ops())
    assert covered == set(variants.ops()) - {"dropout", "serve_forward",
                                             "lrn", "hc", "dsa"}
    for op in covered:
        assert op in templates.CONTRACTS and op in templates.BENCHES
    assert "serve_forward" in templates.CONTRACTS


@pytest.mark.parametrize("op,name", [
    ("maxpool", "gen[algo=slices,fold=tree]"),
    ("maxpool", "gen[algo=reduce_window,fold=linear]"),
    ("conv_stem", "gen[pack=s2d,acc=f32,epi=none]"),
    ("conv_stem", "gen[pack=direct,acc=native,epi=none]"),
])
def test_new_template_points_pass_contracts(op, name):
    rec = templates.check_equivalence(op, name, force=True)
    assert rec["status"] == "pass", rec


def test_conv_unit_consumes_generated_winner():
    """The conv stem's fused path routes auto-mode applicable layers
    through the registry apply, so a generated winner's packing (and
    accumulator pin) actually traces; the granular boolean parses the
    pack axis."""
    from veles_tpu.znicz.conv import Conv
    u = Conv.__new__(Conv)
    u.s2d = "auto"
    u.stride = (4, 4)
    prev = variants.selected("conv_stem")
    try:
        variants.select("conv_stem", "gen[pack=s2d,acc=f32,epi=none]")
        assert u._use_s2d(3) is True
        variants.select("conv_stem", "gen[pack=direct,acc=native,epi=none]")
        assert u._use_s2d(3) is False
        variants.select("conv_stem", "s2d")
        assert u._use_s2d(3) is True
        assert u._use_s2d(16) is False      # applicability gate holds
    finally:
        if prev is None:
            variants.clear_selection("conv_stem")
        else:
            variants.select("conv_stem", prev)


# ---------------------------------------------------------------------------
# search + cache plumbing for the collective family
# ---------------------------------------------------------------------------

def test_grad_reduce_search_and_apply_cached(tmp_path, monkeypatch):
    """The budgeted search covers grad_reduce (microbench over the
    link geometry), persists under a geometry-salted key, and
    apply_cached re-applies the winner with zero timing — while a
    DIFFERENT geometry misses the cache (the per-link-geometry
    contract)."""
    from veles_tpu.ops import autotune as at
    monkeypatch.setenv(LOCAL_ENV, "4")
    templates.clear_ledger()
    cache = at.AutotuneCache(str(tmp_path / "c.json"))
    rep = at.search_op("grad_reduce", budget=7, cache=cache,
                       workflow_sigs=at.link_geometry_signature())
    assert rep["source"] == "searched" and rep["trials"] == 7
    winner = rep["variant"]
    timed = [t for t in rep["trace"] if t["outcome"] == "timed"]
    assert timed and all(
        templates.passed("grad_reduce", t["variant"]) for t in timed)
    variants.clear_selection("grad_reduce")
    # apply_cached probes the geometry+space key for template-only ops
    from tests.test_variants_autotune import _tiny_workflow
    wf = _tiny_workflow()
    applied = at.apply_cached(wf, cache=cache)
    assert applied.get("grad_reduce") == winner
    assert variants.effective("grad_reduce") == winner
    # a different link geometry: the key changes, no silent carryover
    variants.clear_selection("grad_reduce")
    monkeypatch.setenv(LOCAL_ENV, "2")
    applied2 = at.apply_cached(wf, cache=at.AutotuneCache(
        str(tmp_path / "c.json")))
    assert "grad_reduce" not in applied2


def test_zero_ef_plan_helper():
    plan = zero_plan({"w": np.zeros((5, 3)), "b": np.zeros(7)}, 4)
    lens = zero_ef_plan(plan, lambda padded: padded // 2)
    assert lens == {"w": 8, "b": 4}
    assert variants.grad_reduce_resid_len("f32", 16, 4) is None
    assert variants.grad_reduce_resid_len("int8_ef", 16, 4) == 16
