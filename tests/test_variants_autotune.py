"""Lowering-variant registry + persistent autotuner (ISSUE 2 tentpole).

Three contracts, all CPU-runnable (Pallas via interpret mode):
1. EQUIVALENCE — every registered variant of every tunable op matches
   `ops.reference` forward AND backward (the registry's admission bar:
   a variant that can't pass this must not be selectable).
2. CACHE — autotune decisions persist: miss -> timed -> written; second
   run is a PURE cache hit (re-timing is an assertion failure); corrupt
   cache files degrade to re-tuning, never to an error.
3. LOWERING — a registry selection actually changes what the fused step
   traces (HLO-level proof), and the legacy class-attribute knobs are
   deprecation shims that write through to the registry.
"""

import json

import jax
import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.ops import autotune as at
from veles_tpu.ops import reference as ref
from veles_tpu.ops import variants
from veles_tpu.znicz.standard_workflow import StandardWorkflow


@pytest.fixture(autouse=True)
def _isolated_selection():
    """The selection table is process-global: snapshot + restore around
    every test so tuning/shim tests can't leak into each other (or into
    the rest of the tier-1 suite)."""
    snap = variants.selection_table()
    yield
    variants.clear_selection()
    for op, name in snap.items():
        variants.select(op, name)


def _unique_abs(rs, shape):
    """Values with pairwise-distinct absolute values (k + 0.25 for
    integer k): argmax/abs-argmax winners are unique, so every pooling
    lowering and the reference agree exactly (no tie-break dependence)."""
    n = int(np.prod(shape))
    return (rs.permutation(n) - n // 2 + 0.25).astype(
        np.float32).reshape(shape)


# ---------------------------------------------------------------------------
# 1. equivalence vs ops.reference (fwd + bwd; pallas in interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["banded_matmul", "pallas_one_pass"])
def test_lrn_variants_match_reference(name):
    rs = np.random.RandomState(3)
    x = rs.randn(2, 3, 3, 16).astype(np.float32)
    g = rs.randn(2, 3, 3, 16).astype(np.float32)
    k, alpha, beta, n = 2.0, 1e-4, 0.75, 5
    v = variants.get("lrn", name)
    with variants.pallas_interpret():
        y, vjp = jax.vjp(
            lambda xx: v.apply(xx, k=k, alpha=alpha, beta=beta, n=n), x)
        (dx,) = vjp(g)
    np.testing.assert_allclose(
        np.asarray(y), ref.lrn_forward(x, k, alpha, beta, n), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(dx), ref.lrn_backward(x, g, k, alpha, beta, n),
        atol=2e-5)


#: the default `lrn` lowering follows what it can observe (ISSUE 27):
#: case -> (shape, unit allows pallas, kernels the trace must hold)
LRN_DEFAULT_CASES = {
    "batch_in_lanes": ((128, 2, 2, 96), True, 2),
    "channels_in_lanes": ((16, 2, 2, 256), True, 2),
    "falls_back_batch_100": ((100, 2, 2, 96), True, 0),
    "falls_back_channels_40": ((128, 2, 2, 40), True, 0),
    "falls_back_allow_pallas_cleared": ((128, 2, 2, 96), False, 0),
}


@pytest.mark.parametrize("case", sorted(LRN_DEFAULT_CASES))
def test_default_lrn_lowering_follows_the_shape(case):
    """No selection made: on a TPU (here: interpret mode) the op's
    default is the one-pass kernel pair where the shape has a lane-dense
    view, the XLA closed form for any other shape and for a unit whose
    `allow_pallas` is cleared (GSPMD) — and every case matches the
    reference, forward and backward."""
    import types
    shape, allow, n_kernels = LRN_DEFAULT_CASES[case]
    assert variants.selected("lrn") is None
    assert variants.effective("lrn") == "pallas_one_pass"
    rs = np.random.RandomState(11)
    x = rs.randn(*shape).astype(np.float32)
    g = rs.randn(*shape).astype(np.float32)
    k, alpha, beta, n = 2.0, 1e-4, 0.75, 5
    unit = types.SimpleNamespace(allow_pallas=allow)
    with variants.pallas_interpret():
        v = variants.resolve("lrn", unit=unit)
        assert v.name == ("pallas_one_pass" if allow else "banded_matmul")

        def fwd_bwd(xx, gg):
            y, vjp = jax.vjp(lambda a: v.apply(a, k=k, alpha=alpha,
                                               beta=beta, n=n), xx)
            return y, vjp(gg)[0]
        assert str(jax.make_jaxpr(fwd_bwd)(x, g)).count("veles_lrn_") \
            == n_kernels
        y, dx = fwd_bwd(x, g)
    np.testing.assert_allclose(
        np.asarray(y), ref.lrn_forward(x, k, alpha, beta, n), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(dx), ref.lrn_backward(x, g, k, alpha, beta, n),
        atol=2e-5)


def test_default_lrn_resolves_quietly_off_a_tpu(caplog):
    """The default names a Pallas lowering; off a TPU with no interpret
    mode asked for it resolves to `banded_matmul` with no warning (a
    SELECTED pallas variant warns once)."""
    import logging
    with caplog.at_level(logging.WARNING, logger="veles.variants"):
        assert variants.resolve("lrn").name == "banded_matmul"
    assert not caplog.records


@pytest.mark.parametrize("name", ["reduce_window", "slices"])
@pytest.mark.parametrize("use_abs", [False, True])
def test_maxpool_variants_match_reference(name, use_abs):
    rs = np.random.RandomState(5)
    x = _unique_abs(rs, (2, 7, 7, 3))
    ksize, stride = (3, 3), (2, 2)     # ceil-mode: edge windows truncate
    y_ref, idx = ref.maxpool_forward(x, ksize, stride, use_abs)
    v = variants.get("maxpool", name)
    y, vjp = jax.vjp(lambda xx: v.apply(xx, ksize, stride, use_abs), x)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-6)
    g = rs.randn(*y_ref.shape).astype(np.float32)
    (dx,) = vjp(g)
    np.testing.assert_allclose(
        np.asarray(dx), ref.maxpool_backward(g, idx, x.shape), atol=1e-6)


@pytest.mark.parametrize("name", ["direct", "s2d"])
def test_conv_stem_variants_match_reference(name):
    rs = np.random.RandomState(7)
    x = rs.randn(2, 11, 11, 3).astype(np.float32)
    w = (0.1 * rs.randn(5, 5, 3, 8)).astype(np.float32)
    b = (0.1 * rs.randn(8)).astype(np.float32)
    stride, padding, act = (2, 2), (1, 1), "strictrelu"
    y_ref = ref.conv2d_forward(x, w, b, stride, padding, act)
    v = variants.get("conv_stem", name)
    y, vjp = jax.vjp(
        lambda xx, ww: v.apply(xx, ww, b, stride, padding, act), x, w)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-4)
    # backward: both variants must transpose to the SAME gradients (the
    # s2d rewrite is exact) — checked against the direct lowering's vjp,
    # which test_ops_equivalence already pins to the reference backward
    g = rs.randn(*y_ref.shape).astype(np.float32)
    dx, dw = vjp(g)
    dref = variants.get("conv_stem", "direct")
    _, vjp_ref = jax.vjp(
        lambda xx, ww: dref.apply(xx, ww, b, stride, padding, act), x, w)
    dx_ref, dw_ref = vjp_ref(g)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               atol=1e-4)


@pytest.mark.parametrize("name", ["auto", "threefry", "rbg"])
def test_dropout_variants_structural(name):
    """Mask streams legitimately differ per impl (the reference had the
    same xorshift-vs-numpy split) — the contract is structural: values
    are exactly {0, 1/keep}, the keep rate is statistically right, and
    applying the mask is the reference dropout_forward."""
    v = variants.get("dropout", name)
    keep = 0.5
    mask = np.asarray(v.apply(jax.random.PRNGKey(9), (64, 64), 1 - keep,
                              np.float32))
    assert set(np.unique(mask)) <= {0.0, 1.0 / keep}
    assert abs((mask > 0).mean() - keep) < 0.05
    rs = np.random.RandomState(1)
    x = rs.randn(64, 64).astype(np.float32)
    np.testing.assert_allclose(ref.dropout_forward(x, mask), x * mask,
                               atol=0)


def test_registry_validation():
    with pytest.raises(KeyError):
        variants.get("lrn", "no_such_variant")
    with pytest.raises(KeyError):
        variants.select("no_such_op", "x")
    table = variants.selection_table(include_defaults=True)
    assert set(table) == {"lrn", "maxpool", "conv_stem", "dropout",
                          "grad_reduce", "flash_attn", "sgd_update",
                          "lrn_maxpool", "serve_forward", "hc", "dsa"}
    # pallas variants resolve to the op's non-pallas fallback on CPU...
    variants.select("lrn", "pallas_one_pass")
    assert variants.resolve("lrn").name == "banded_matmul"
    # ...unless interpret mode is on (the CPU autotune/test path)
    with variants.pallas_interpret():
        assert variants.resolve("lrn").name == "pallas_one_pass"


# ---------------------------------------------------------------------------
# 2. autotune: discovery, cache round-trip (hit / miss / corrupt)
# ---------------------------------------------------------------------------


def _tiny_workflow(n_kernels=8):
    prng.seed_all(1)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
        n_train=16, minibatch_size=4, noise=0.5)
    return StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": n_kernels, "kx": 5,
                 "ky": 5, "stride": (2, 2), "s2d": "auto",
                 "weights_stddev": 0.1},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2)},
                {"type": "dropout", "dropout_ratio": 0.5},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1}, name="TuneT1")


def test_discovery_covers_all_four_ops():
    wf = _tiny_workflow()
    wf.initialize(device=None)
    tun = at.discover_tunables(wf)
    assert set(tun) == {"lrn", "maxpool", "conv_stem", "dropout"}
    # explicit per-layer overrides opt OUT of tuning
    wf2 = _tiny_workflow()
    for u in wf2.forwards:
        if getattr(u, "variant_op", None) == "maxpool":
            u.variant_override = "slices"
    wf2.initialize(device=None)
    assert "maxpool" not in at.discover_tunables(wf2)


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    cache_path = str(tmp_path / "autotune.json")
    wf = _tiny_workflow()
    report = at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                                  cache_path=cache_path)
    assert set(report) == {"lrn", "maxpool", "conv_stem", "dropout"}
    assert all(r["source"] == "tuned" for r in report.values())
    # every candidate was actually timed — incl. pallas in interpret mode
    assert set(report["lrn"]["timings_s"]) == {
        "banded_matmul", "pallas_one_pass"}
    # winners are live registry selections
    for op, r in report.items():
        assert variants.selected(op) == r["variant"]
    with open(cache_path) as f:
        on_disk = json.load(f)
    assert len(on_disk["entries"]) == 4

    # second invocation: PURE cache hit — any timing is a failure
    def _boom(*a, **k):
        raise AssertionError("autotune re-timed on a cache hit")
    monkeypatch.setattr(at, "_time_variant", _boom)
    variants.clear_selection()
    wf2 = _tiny_workflow()
    report2 = at.autotune_workflow(wf2, steps=1, repeats=1, batch=4,
                                   cache_path=cache_path)
    assert all(r["source"] == "cache" for r in report2.values())
    assert {k: r["variant"] for k, r in report2.items()} \
        == {k: r["variant"] for k, r in report.items()}
    # force=True must attempt to re-time: the sentinel fires per
    # candidate and the per-candidate error guard records it (one broken
    # lowering must never abort a tuning run)
    report3 = at.autotune_workflow(wf2, steps=1, repeats=1, batch=4,
                                   cache_path=cache_path, force=True)
    assert all(r["source"] == "error" for r in report3.values())
    assert all("re-timed" in str(t)
               for r in report3.values()
               for t in r["timings_s"].values())


def test_cache_keys_are_batch_independent(tmp_path):
    """Tune-then-inherit: tools/autotune.py tunes at its own batch while
    bench/training run at another — the decision must still hit. The
    signatures therefore carry per-SAMPLE shapes only."""
    cache_path = str(tmp_path / "c.json")
    wf = _tiny_workflow()          # minibatch 4
    at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                         cache_path=cache_path)
    prng.seed_all(2)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
        n_train=16, minibatch_size=8, noise=0.5)   # DIFFERENT batch
    wf2 = StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 5,
                 "ky": 5, "stride": (2, 2), "s2d": "auto",
                 "weights_stddev": 0.1},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2)},
                {"type": "dropout", "dropout_ratio": 0.5},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1}, name="TuneT2")
    variants.clear_selection()
    applied = at.apply_cached(wf2, cache_path=cache_path)
    assert set(applied) == {"lrn", "maxpool", "conv_stem", "dropout"}


def test_autotune_cache_corrupt_file_falls_back(tmp_path, monkeypatch):
    cache_path = tmp_path / "autotune.json"
    cache_path.write_text("{definitely not json")
    c = at.AutotuneCache(str(cache_path))
    warned = []
    monkeypatch.setattr(c, "warning",
                        lambda msg, *a: warned.append(msg % a))
    assert c.get("anything") is None          # degrade, don't raise
    assert c.get("again") is None
    # ...and logs ONCE, not per get (the empty dict is cached)
    assert sum("re-tuning" in m for m in warned) == 1
    c.put("k1", {"variant": "x"})
    assert at.AutotuneCache(str(cache_path)).get("k1") == {"variant": "x"}
    # the written file carries the explicit schema tag at the current
    # version
    raw = json.loads(cache_path.read_text())
    assert raw["schema"] == at.AutotuneCache.SCHEMA
    assert raw["version"] == at.AutotuneCache.VERSION
    # unknown layout versions likewise degrade
    cache_path.write_text(json.dumps({"version": 999, "entries": {}}))
    assert at.AutotuneCache(str(cache_path)).get("k1") is None
    # a cached winner that no longer exists in the registry re-tunes
    # instead of crashing resolve()
    key = "TPU vX|lrn|f32|deadbeef"
    c2 = at.AutotuneCache(str(tmp_path / "c2.json"))
    c2.put(key, {"variant": "deleted_variant"})
    assert not variants.has("lrn", "deleted_variant")


def test_autotune_cache_version_skew_degrades(tmp_path, monkeypatch):
    """An old-schema cache (a v1 file from before the search PR, a
    future version, or a wrong schema tag) must behave as EMPTY — log
    once and re-tune, never crash, never serve stale-layout records."""
    cache_path = tmp_path / "autotune.json"
    # the exact v1 layout PR 2 wrote (no schema tag)
    cache_path.write_text(json.dumps(
        {"version": 1,
         "entries": {"TPU vX|lrn|f32|cafe": {"variant": "banded_matmul",
                                             "timings_s": {}}}}))
    c = at.AutotuneCache(str(cache_path))
    warned = []
    monkeypatch.setattr(c, "warning",
                        lambda msg, *a: warned.append(msg % a))
    assert c.get("TPU vX|lrn|f32|cafe") is None
    assert c.get("TPU vX|lrn|f32|cafe") is None
    assert sum("re-tuning" in m for m in warned) == 1
    assert "v1" in warned[0]                 # the skew is named
    # wrong schema tag at the right version also degrades
    cache_path.write_text(json.dumps(
        {"schema": "someone-elses-cache",
         "version": at.AutotuneCache.VERSION, "entries": {}}))
    assert at.AutotuneCache(str(cache_path)).get("x") is None
    # a put() on a skewed cache rewrites it cleanly at CURRENT version
    c3 = at.AutotuneCache(str(cache_path))
    c3.put("k", {"variant": "v"})
    raw = json.loads(cache_path.read_text())
    assert raw["schema"] == at.AutotuneCache.SCHEMA
    assert raw["version"] == at.AutotuneCache.VERSION
    assert at.AutotuneCache(str(cache_path)).get("k") == {"variant": "v"}


# ---------------------------------------------------------------------------
# 3. the registry choice changes the TRACED lowering; shims write through
# ---------------------------------------------------------------------------


def _lowered_text(wf):
    step = wf.build_fused_step()
    step._build()
    x = np.zeros((4, 12, 12, 3), np.float32)
    y = np.zeros(4, np.int64)
    w = np.ones(4, np.float32)
    state = step.init_state()
    return step._train_fn.lower(state, x, y, w).as_text(), step


def test_registry_choice_changes_traced_lowering():
    variants.select("maxpool", "reduce_window")
    wf = _tiny_workflow()
    wf.initialize(device=None)
    txt_rw, step_rw = _lowered_text(wf)
    assert step_rw.variant_table()["maxpool"] == "reduce_window"
    assert "select_and_scatter" in txt_rw      # the reduce_window bwd

    variants.select("maxpool", "slices")
    variants.select("conv_stem", "direct")
    wf2 = _tiny_workflow()
    wf2.initialize(device=None)
    txt_sl, step_sl = _lowered_text(wf2)
    assert step_sl.variant_table()["maxpool"] == "slices"
    assert "select_and_scatter" not in txt_sl  # selects + pads instead
    assert txt_sl != txt_rw                    # conv stem flipped too


def test_fused_step_gspmd_never_traces_pallas():
    """GSPMD auto-partitioning cannot shard a pallas_call: even with the
    pallas LRN selected (and resolvable), a gspmd-mode step must report
    and trace the non-pallas fallback (128 kernels: the LRN input then
    has a lane-dense view, so the local step does trace the kernels)."""
    import jax as _jax
    from veles_tpu.parallel.mesh import make_mesh
    variants.select("lrn", "pallas_one_pass")
    wf = _tiny_workflow(n_kernels=128)
    wf.initialize(device=None)
    mesh = make_mesh(_jax.devices()[:1])
    with variants.pallas_interpret():
        step = wf.build_fused_step(mesh=mesh, mode="gspmd")
        assert step.variant_table()["lrn"] == "banded_matmul"
        local = wf.build_fused_step()
        assert local.variant_table()["lrn"] == "pallas_one_pass"


def test_variant_table_names_the_lrn_lowering_the_shape_traces():
    """`pallas_one_pass` falls back by shape inside `lrn_pallas`: where
    resolve gives it but the LRN input has no lane-dense view (8
    channels, a batch of 4), the table names the XLA form that the step
    traces — a record never names a lowering no unit traced."""
    wf = _tiny_workflow()
    wf.initialize(device=None)
    with variants.pallas_interpret():
        step = wf.build_fused_step()
        lrn = next(u for u in step.forwards
                   if getattr(u, "variant_op", None) == "lrn")
        assert variants.resolve("lrn", unit=lrn).name == "pallas_one_pass"
        assert step.variant_table()["lrn"] == "banded_matmul"
        x = np.zeros(lrn.input.shape, np.float32)
        assert "veles_lrn_" not in str(jax.make_jaxpr(
            lambda a: lrn.fused_apply(None, a))(x))


def test_pre_registry_pickles_resolve_without_variant_override():
    """Instances restored from snapshots written BEFORE this PR lack
    `variant_override` in __dict__ — the class-level default must keep
    resolution/reporting/discovery working (launcher's automatic
    apply_cached path runs on every resumed --fused workflow)."""
    wf = _tiny_workflow()
    wf.initialize(device=None)
    pool = next(u for u in wf.forwards
                if getattr(u, "variant_op", None) == "maxpool")
    pool.__dict__.pop("variant_override", None)   # simulate old pickle
    assert pool.variant_signature() is not None
    assert pool.lowering == variants.effective("maxpool")
    assert variants.resolve("maxpool", unit=pool).name \
        == variants.effective("maxpool")
    assert "maxpool" in at.discover_tunables(wf)


def test_variant_table_reports_traced_conv_lowering():
    """A per-layer s2d="on"/"off" override bypasses the registry; the
    reported table must name what the layer actually traces, not the
    raw registry resolution (record-accuracy contract)."""
    variants.select("conv_stem", "s2d")
    prng.seed_all(3)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
        n_train=16, minibatch_size=4, noise=0.5)
    wf = StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 5,
                 "ky": 5, "stride": (2, 2), "s2d": "off",
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1}, name="ConvOff")
    wf.initialize(device=None)
    step = wf.build_fused_step()
    assert step.variant_table()["conv_stem"] == "direct"
    # and an auto stem the rewrite can't apply to reports nothing
    wf2 = StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 3,
                 "ky": 3, "stride": (1, 1), "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=SyntheticClassifierLoader(
            n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
            n_train=16, minibatch_size=4, noise=0.5),
        loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1}, name="ConvStride1")
    wf2.initialize(device=None)
    assert "conv_stem" not in wf2.build_fused_step().variant_table()


def test_per_layer_override_beats_registry():
    variants.select("maxpool", "reduce_window")
    wf = _tiny_workflow()
    for u in wf.forwards:
        if getattr(u, "variant_op", None) == "maxpool":
            u.variant_override = "slices"
    wf.initialize(device=None)
    txt, step = _lowered_text(wf)
    assert "select_and_scatter" not in txt
    assert step.variant_table()["maxpool"] == "slices"
