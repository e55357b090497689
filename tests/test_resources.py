"""Static resource analyzer (analysis pass 6, ISSUE 14): the kernel
VMEM ledger that prunes the search, and the workflow HBM model behind
the Launcher pre-flight / --verify-workflow=resources.

The contracts, all CPU-runnable:
1. FOOTPRINTS — each template's `vmem_footprint` rule tracks its
   kernel's BlockSpecs (tile-monotone, io-dtype-width aware, clamped to
   the geometry the kernel would actually run).
2. PRUNING — an over-budget generated point is statically infeasible:
   skipped WITHOUT timing or budget cost (outcome "pruned", metrics +
   per-point log), and `_timed_trial` refuses it structurally even when
   the prune branch is bypassed (the ledger-bypass precedent in
   test_kernel_search.py). A pruned search times strictly fewer trials
   than an unpruned one while electing the SAME winner.
3. CACHE REFUSAL — apply_cached refuses a persisted winner whose
   footprint no longer fits the current device budget.
4. HBM MODEL — seeded+clean per rule (over-limit errors, fitting plans
   clean), the run_fused pre-flight refuses an over-limit run before
   compiling, and predicted resident bytes match the memstats-measured
   live set within 25% on the 8-device CPU mesh under fused dp + ZeRO
   (divisible AND ragged plans).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from veles_tpu import prng
from veles_tpu.analysis import resources as res
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.ops import autotune as at
from veles_tpu.ops import templates, variants
from veles_tpu.parallel import memstats
from veles_tpu.parallel.mesh import make_mesh
from veles_tpu.znicz.standard_workflow import StandardWorkflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_selection():
    """Selection table / equivalence ledger are process-global (the
    test_kernel_search contract); the resource env overrides must not
    leak between tests either."""
    snap = variants.selection_table()
    yield
    variants.clear_selection()
    for op, name in snap.items():
        variants.select(op, name)
    templates.clear_ledger()
    os.environ.pop(res.VMEM_BUDGET_ENV, None)
    os.environ.pop(res.HBM_LIMIT_ENV, None)


def _fc_workflow(width=32, name="ResT", batch=16, sample=100):
    prng.seed_all(3)
    loader = SyntheticClassifierLoader(
        n_classes=8, sample_shape=(sample,), n_validation=batch,
        n_train=4 * batch, minibatch_size=batch, noise=0.5)
    return StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": width},
                {"type": "softmax", "output_sample_shape": 8}],
        loader=loader, loss="softmax", n_classes=8,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name=name)


# ---------------------------------------------------------------------------
# 1. footprint rules and verdicts
# ---------------------------------------------------------------------------


def test_vmem_budget_table_and_overrides(monkeypatch):
    # the limit the kernels COMPILE under (Mosaic's default scoped
    # limit — no pallas_call passes vmem_limit_bytes), not the chip's
    # physical 128 MiB: one number, shared with the kernels' heuristics
    assert res.vmem_budget("TPU v5 lite") == res.SCOPED_VMEM_LIMIT \
        == 16 << 20
    assert res.vmem_budget("TPU v4") == 16 << 20
    # CPU interpret mode / unknown kinds have NO static budget: pruning
    # inactive unless explicitly overridden (existing CPU searches must
    # not silently change behavior)
    assert res.vmem_budget("cpu") is None
    assert res.vmem_budget(None) is None
    monkeypatch.setenv(res.VMEM_BUDGET_ENV, str(1 << 20))
    assert res.vmem_budget("cpu") == 1 << 20
    assert res.vmem_budget("cpu", override=77) == 77   # arg beats env


def test_flash_footprint_clamps_like_the_kernel():
    """A requested block that flash_fit_block would shrink at the given
    S must cost exactly what the shrunken kernel costs — the pruned
    geometry IS the traced geometry."""
    want = res.kernel_footprint(
        "flash_attn", "pallas[blk_q=512,blk_k=512,kv_order=fwd,drop=0]",
        shapes={"s": 512, "d": 64})
    clamped = res.kernel_footprint(
        "flash_attn",
        "pallas[blk_q=512,blk_k=1024,kv_order=fwd,drop=0]",
        shapes={"s": 512, "d": 64})
    assert clamped == want
    # the fused dropout mask streams a fourth (blk_q, d) forward block
    # — it can only grow the verdict (the backward grids, which often
    # dominate the max, never see the mask)
    dropped = res.kernel_footprint(
        "flash_attn", "pallas[blk_q=512,blk_k=512,kv_order=fwd,drop=1]",
        shapes={"s": 8192, "d": 64})
    plain = res.kernel_footprint(
        "flash_attn", "pallas[blk_q=512,blk_k=512,kv_order=fwd,drop=0]",
        shapes={"s": 8192, "d": 64})
    assert dropped >= plain
    # and block size grows the footprint monotonically
    small = res.kernel_footprint(
        "flash_attn", "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=0]",
        shapes={"s": 8192, "d": 64})
    assert small < plain
    # hand-written incumbents carry no declarative rule: unknown, and
    # unknown is never pruned
    assert res.kernel_footprint("lrn", "banded_matmul") is None
    assert res.kernel_footprint("lrn", "pallas_one_pass") is None


def test_fused_composed_point_has_zero_footprint():
    assert res.kernel_footprint("lrn_maxpool",
                                "fused[rt=8,io=f32,fuse=0]") == 0
    assert res.kernel_footprint(
        "lrn_maxpool", "fused[rt=8,io=f32,fuse=1]",
        shapes={"h": 55, "w": 55, "c": 96}) > 0


def test_kernel_verdict_seeded_and_clean():
    over = res.kernel_verdict("sgd_update", "pallas_rows[rt=1024]",
                              budget=1 << 20)
    assert over is not None
    assert over["footprint"] > over["vmem_budget"] == 1 << 20
    assert res.kernel_verdict("sgd_update", "pallas_rows[rt=32]",
                              budget=1 << 20) is None
    # no budget -> no verdict, ever
    assert res.kernel_verdict("sgd_update", "pallas_rows[rt=1024]") is None


def test_vmem_over_budget_finding_seeded_and_clean(monkeypatch):
    """Pass-6 kernel ledger over the CURRENT registry selections: a
    selected over-budget generated point is an error finding; default
    (hand-written) selections are clean."""
    wf = _fc_workflow(name="VmemF")
    clean = res.kernel_findings(wf, device_kind="cpu",
                                budget=1 << 20)
    assert [f for f in clean if f.rule == "vmem-over-budget"] == []
    point = "fused[rt=8,io=f32,fuse=1]"
    variants.get("lrn_maxpool", point)   # materialize
    variants.select("lrn_maxpool", point)
    band = {"sample_shape": [27, 27, 96]}
    seeded = res.kernel_findings(
        wf, sigs={"lrn_maxpool": [{"lrn": band, "maxpool": band}]},
        device_kind="cpu", budget=1 << 20)
    hits = [f for f in seeded if f.rule == "vmem-over-budget"]
    assert len(hits) == 1 and hits[0].severity == "error"
    assert f"lrn_maxpool/{point}" in hits[0].unit


def test_shapes_from_signatures_takes_the_worst_instance():
    sigs = [{"sample_shape": [55, 55, 96]},
            {"sample_shape": [27, 27, 256]}]
    # an op no footprint rule reads shapes for carries none
    assert res.shapes_from_signatures("lrn", sigs) == {}
    # the fused pair blocks whole bands: the worst is the largest
    # lane-padded one, kept together (55x55x96 pads to 128 lanes)
    sp = res.shapes_from_signatures(
        "lrn_maxpool", [{"lrn": g, "maxpool": g} for g in sigs])
    assert sp == {"h": 55, "w": 55, "c": 96}
    s2 = res.shapes_from_signatures(
        "lrn_maxpool",
        [{"lrn": {"sample_shape": [13, 13, 16]},
          "maxpool": {"sample_shape": [13, 13, 16]},
         }])
    assert s2 == {"c": 16, "h": 13, "w": 13}
    # the pair signature's POOL side carries the real window geometry;
    # across instances the worst case wins (largest window, smallest
    # stride — the biggest padded recompute canvas)
    s2b = res.shapes_from_signatures(
        "lrn_maxpool",
        [{"lrn": {"sample_shape": [13, 13, 16]},
          "maxpool": {"sample_shape": [6, 6, 16],
                      "params": {"ksize": [2, 2], "stride": [2, 2]}}},
         {"lrn": {"sample_shape": [27, 27, 16]},
          "maxpool": {"sample_shape": [13, 13, 16],
                      "params": {"ksize": [3, 3], "stride": [1, 2]}}}])
    assert s2b["ksize"] == (3, 3) and s2b["stride"] == (1, 2)
    # and the fused footprint actually consumes it: a bigger window at
    # a smaller stride pads a bigger recompute canvas
    base = res.kernel_footprint(
        "lrn_maxpool", "fused[rt=4,io=f32,fuse=1]",
        shapes={"h": 13, "w": 13, "c": 16,
                "ksize": (2, 2), "stride": (2, 2)})
    wide = res.kernel_footprint(
        "lrn_maxpool", "fused[rt=4,io=f32,fuse=1]",
        shapes={"h": 13, "w": 13, "c": 16,
                "ksize": (3, 3), "stride": (1, 1)})
    assert wide > base
    s3 = res.shapes_from_signatures(
        "flash_attn", [{"sample_shape": [4096, 512], "head_dim": 64},
                       {"sample_shape": [8192, 256], "head_dim": 32}])
    assert s3 == {"s": 8192, "d": 64}


# ---------------------------------------------------------------------------
# 2. search pruning
# ---------------------------------------------------------------------------


#: the op the pruning tests search: one axis (`rt`, 8..1024 rows of the
#: update grid) and a footprint rule that needs no shapes — 5,120 B a
#: row, so a 2 MiB budget makes exactly rt=512 and rt=1024 infeasible
PRUNED_OP = "sgd_update"
PRUNE_BUDGET = 2 << 20
PRUNED_POINTS = {"pallas_rows[rt=512]", "pallas_rows[rt=1024]"}


def _deterministic_timer():
    """In-graph-timer stand-in keyed on the SELECTED config — both the
    pruned and unpruned searches elect the same winner deterministically
    (real timings are noise; this test pins the pruning mechanics)."""
    t = templates.templates_for(PRUNED_OP)[0]

    def timer():
        cfg = t.parse(variants.effective(PRUNED_OP))
        if cfg is None:                      # a hand-written incumbent
            return 0.5
        return abs(cfg["rt"] - 128) / 1e5
    return timer


def test_pruned_search_times_fewer_trials_same_winner(tmp_path):
    """The acceptance run: a budget-48 CPU search with pruning enabled
    times strictly fewer trials than without, selects the SAME winner,
    never times a pruned point, and spends NO budget on pruned points;
    outcomes route through veles_autotune_trials_total{outcome}."""
    counter = at._trials_counter()
    before = counter.labels(op=PRUNED_OP, outcome="pruned").value
    templates.clear_ledger()
    free = at.search_op(PRUNED_OP, budget=48,
                        cache=at.AutotuneCache(str(tmp_path / "a.json")),
                        in_graph_timer=_deterministic_timer())
    assert free["source"] == "searched" and free["pruned"] == []

    variants.clear_selection(PRUNED_OP)
    templates.clear_ledger()
    pruned = at.search_op(
        PRUNED_OP, budget=48,
        cache=at.AutotuneCache(str(tmp_path / "b.json")),
        in_graph_timer=_deterministic_timer(),
        vmem_budget=PRUNE_BUDGET)
    assert pruned["source"] == "searched"
    # 2 MiB makes exactly the rt=512 and rt=1024 points infeasible
    # (512 rows * 5,120 B = 2.5 MiB; rt=256 is 1.25 MiB)
    assert set(pruned["pruned"]) == PRUNED_POINTS
    assert pruned["variant"] == free["variant"]          # same winner
    assert pruned["trials"] < free["trials"]             # fewer timed
    # no budget burnt on pruned points: every counted trial is a real
    # evaluation, and the pruned rows carry footprint/budget instead
    prows = [t for t in pruned["trace"] if t["outcome"] == "pruned"]
    assert len(prows) == 2
    for row in prows:
        assert row["footprint"] > row["vmem_budget"] == PRUNE_BUDGET
    assert pruned["trials"] == len(
        [t for t in pruned["trace"] if t["outcome"] != "pruned"])
    assert counter.labels(op=PRUNED_OP, outcome="pruned").value \
        == before + 2


def test_pruned_point_is_never_timed_property(tmp_path):
    """Property over the whole trace: a name the verdict rejects never
    appears with a timed outcome, and the persisted record carries the
    pruned list (no silent caps)."""
    templates.clear_ledger()
    rep = at.search_op(
        PRUNED_OP, budget=48,
        cache=at.AutotuneCache(str(tmp_path / "c.json")),
        in_graph_timer=_deterministic_timer(),
        vmem_budget=PRUNE_BUDGET)
    timed = {t["variant"] for t in rep["trace"]
             if t["outcome"] == "timed"}
    assert timed and not (timed & set(rep["pruned"]))
    for name in rep["pruned"]:
        assert res.kernel_verdict(PRUNED_OP, name,
                                  budget=PRUNE_BUDGET) is not None
    with open(tmp_path / "c.json") as f:
        persisted = list(json.load(f)["entries"].values())[0]
    assert set(persisted["pruned"]) == set(rep["pruned"])


def test_prune_bypass_raises_infeasible_error(tmp_path, monkeypatch):
    """The hard gate (the test_kernel_search ledger-bypass precedent):
    even with the prune branch monkeypatched away, `_timed_trial`'s
    independent verdict refuses to time an over-budget point —
    structurally, not by convention."""
    monkeypatch.setattr(at, "_prune_verdict",
                        lambda *a, **k: None)
    templates.clear_ledger()
    with pytest.raises(res.InfeasibleCandidateError):
        at.search_op(PRUNED_OP, budget=48,
                     cache=at.AutotuneCache(str(tmp_path / "d.json")),
                     in_graph_timer=_deterministic_timer(),
                     vmem_budget=PRUNE_BUDGET)


def test_search_op_cache_hit_refuses_unfitting_winner(tmp_path):
    """The budget is NOT part of the cache key: a winner persisted
    under a roomier budget must not short-circuit a tightened re-run —
    search_op's cache-hit fast path applies the SAME refusal rule as
    apply_cached and falls through to a fresh (pruned) search."""
    cache = at.AutotuneCache(str(tmp_path / "cache.json"))
    templates.clear_ledger()
    free = at.search_op(PRUNED_OP, budget=48, cache=cache,
                        in_graph_timer=_deterministic_timer())
    assert free["source"] == "searched"
    # loosened re-run: the persisted winner fits -> pure cache hit
    hit = at.search_op(PRUNED_OP, budget=48, cache=cache,
                       in_graph_timer=_deterministic_timer(),
                       vmem_budget=64 << 20)
    assert hit["source"] == "cache" and hit["trials"] == 0
    # tightened re-run below the persisted winner's footprint: the hit
    # is refused and a real search runs, electing a point that fits
    win_fp = res.kernel_footprint(PRUNED_OP, free["variant"])
    tight = max(1, win_fp - 1)
    rerun = at.search_op(PRUNED_OP, budget=48, cache=cache,
                         in_graph_timer=_deterministic_timer(),
                         vmem_budget=tight)
    assert rerun["source"] == "searched" and rerun["trials"] > 0
    assert free["variant"] in rerun["pruned"]
    assert res.kernel_verdict(PRUNED_OP, rerun["variant"],
                              budget=tight) is None


def test_apply_cached_refuses_unfitting_winner(tmp_path, monkeypatch):
    """Cache-refusal rule: a persisted winner tuned under a roomier
    budget is NOT applied when its footprint no longer fits the current
    device budget — the selection stands instead of electing a point
    that would fail at compile time on-chip."""
    wf = _fc_workflow(name="CacheRef")
    wf.initialize(device=None)
    cache = at.AutotuneCache(str(tmp_path / "cache.json"))
    device_kind = jax.devices()[0].device_kind
    name = "pallas_rows[rt=1024]"           # 5.2 MB footprint
    variants.get("sgd_update", name)        # materialize
    key = at.op_cache_key(device_kind, "sgd_update",
                          templates.space_signature("sgd_update"), None)
    cache.put(key, {"variant": name})
    applied = at.apply_cached(wf, cache=cache)
    assert applied.get("sgd_update") == name   # no budget: applies
    variants.clear_selection("sgd_update")
    monkeypatch.setenv(res.VMEM_BUDGET_ENV, str(1 << 20))
    applied = at.apply_cached(wf, cache=cache)
    assert "sgd_update" not in applied         # refused under 1 MiB
    assert variants.selected("sgd_update") is None


# ---------------------------------------------------------------------------
# 3. workflow HBM model
# ---------------------------------------------------------------------------


def test_liveness_walk_counts_intermediates_not_inputs():
    def f(a, b):
        big = a @ b                     # (64, 64) f32 intermediate
        c = big.sum()
        return c

    closed = jax.make_jaxpr(f)(np.zeros((64, 32), np.float32),
                               np.zeros((32, 64), np.float32))
    peak = res._liveness_highwater(closed.jaxpr)
    assert peak >= 64 * 64 * 4                 # sees the intermediate
    assert peak < 64 * 64 * 4 + 64 * 32 * 8    # but never the inputs


def test_hbm_findings_seeded_and_clean():
    wf = _fc_workflow(name="HbmF")
    # over-HBM plan: errors, with the per-component breakdown in the
    # message (the operator-facing half of the rule)
    finds, rep = res.workflow_resource_findings(wf, limit=10_000)
    errs = [f for f in finds if f.rule == "hbm-over-limit"]
    assert len(errs) == 1 and errs[0].severity == "error"
    assert "params=" in errs[0].message
    assert rep["highwater_per_device"] > 10_000
    assert rep["limit_per_device"] == 10_000
    # near-limit: warn, not error
    near = int(rep["highwater_per_device"] / 0.9)
    finds2, _ = res.workflow_resource_findings(wf, limit=near)
    assert [f.rule for f in finds2
            if f.rule.startswith("hbm")] == ["hbm-near-limit"]
    # fitting plan: clean
    finds3, rep3 = res.workflow_resource_findings(wf, limit=1 << 34)
    assert [f for f in finds3 if f.rule.startswith("hbm")] == []
    # the report decomposes per component, trace included
    assert set(rep3["components"]) >= {"params", "optimizer_state",
                                       "feed", "activations"}
    assert rep3["static_only"] is False


@pytest.mark.parametrize("width", [200, 101])
def test_predicted_vs_measured_hbm_zero_mesh(eight_devices, width):
    """Acceptance: predicted resident bytes/device within 25% of the
    memstats-measured live set on the 8-device CPU mesh under fused dp
    + ZeRO — divisible (width 200) and ragged (width 101) plans. CPU
    has no allocator peak, so the comparison pairs the resident model
    with live-array accounting (the same `memstats.bytes_per_device`
    ledger every measured memory number rides)."""
    wf = _fc_workflow(width=width, name=f"Pred{width}")
    wf.initialize(device=None)
    mesh = make_mesh(jax.devices()[:8])
    step = wf.build_fused_step(mesh=mesh, mode="dp", zero_sharding="on")
    assert step.zero_active
    state = step.init_state()
    loader = wf.loader
    x = np.asarray(loader.minibatch_data.mem, np.float32)
    y = np.asarray(loader.minibatch_labels.mem)
    w = np.ones(x.shape[0], np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    ys = jax.device_put(y, NamedSharding(mesh, P("data")))
    ws = jax.device_put(w, NamedSharding(mesh, P("data")))
    for _ in range(2):
        state, _ = step.train(state, xs, ys, ws)
    jax.block_until_ready(state["params"])
    rep = res.step_resource_report(step, x, y, w, feed_batches=1,
                                   trace=True)
    arrs = [a for a in jax.tree_util.tree_leaves(state) + [xs, ys, ws]
            if isinstance(a, jax.Array)]
    measured = max(memstats.bytes_per_device(arrs).values())
    predicted = rep["resident_per_device"]
    assert measured > 0
    assert abs(predicted - measured) / measured < 0.25, \
        (predicted, measured, rep["components"])
    # the traced high-water strictly exceeds the resident set (it adds
    # the transient step state) and the components decompose it
    assert rep["highwater_per_device"] > predicted
    assert rep["components"]["optimizer_state"] < \
        rep["components"]["params"]          # the ZeRO 1/N cut


def test_preflight_refuses_over_limit_run(monkeypatch):
    """Launcher pre-flight: an over-limit (model, mesh, batch) combo is
    refused BEFORE compiling, with the report attached; a fitting run
    proceeds and stashes the report for the heartbeat."""
    monkeypatch.setenv(res.HBM_LIMIT_ENV, "10000")
    wf = _fc_workflow(name="PreflightOver")
    with pytest.raises(res.ResourcePreflightError) as ei:
        wf.run_fused(epochs=1)
    assert "breakdown" in str(ei.value)
    assert ei.value.report["highwater_per_device"] > 10_000

    monkeypatch.setenv(res.HBM_LIMIT_ENV, str(1 << 32))
    wf2 = _fc_workflow(name="PreflightFit")
    wf2.run_fused(epochs=1)
    rep = wf2.resource_report
    assert rep and rep["limit_per_device"] == 1 << 32
    assert rep["static_only"] is False
    # the prediction must NOT ride snapshots (it embeds the host's
    # device limit, which another host must not restore)
    assert "resource_report" not in wf2.__getstate__()

    monkeypatch.delenv(res.HBM_LIMIT_ENV)
    wf3 = _fc_workflow(name="PreflightNoLimit")
    wf3.run_fused(epochs=1)
    # no limit known: the cheap static model still runs (heartbeat
    # payload), the traced walk is skipped
    assert wf3.resource_report["static_only"] is True
    assert wf3.resource_report["limit_per_device"] is None


def test_supervisor_memory_delta_pairs_like_with_like():
    from veles_tpu.resilience.supervisor import memory_delta
    mem = {"live_bytes_max": 1000,
           "predicted": {"resident_per_device": 1100,
                         "highwater_per_device": 2000}}
    d = memory_delta(mem)
    assert d["basis"] == "live_vs_resident"
    assert d["predicted_per_device"] == 1100
    assert d["delta_frac"] == 0.1
    mem["peak_bytes_max"] = 1600
    d2 = memory_delta(mem)
    assert d2["basis"] == "peak_vs_highwater"
    assert d2["predicted_per_device"] == 2000
    # one-sided payloads never fabricate a comparison
    assert memory_delta({"live_bytes_max": 5}) is None
    assert memory_delta(None) is None


def test_serving_capacity_hint(monkeypatch):
    wf = _fc_workflow(name="ServeCap")
    wf.initialize(device=None)
    cap = res.serving_capacity(wf, max_batch=64)
    assert cap["model_bytes"] > 0 and cap["batch_bytes"] > 0
    assert cap["headroom_batches"] is None     # CPU: no limit known
    monkeypatch.setenv(res.HBM_LIMIT_ENV, str(1 << 30))
    cap2 = res.serving_capacity(wf, max_batch=64)
    assert cap2["headroom_batches"] == \
        ((1 << 30) - cap2["model_bytes"]) // cap2["batch_bytes"]
    # /healthz carries the hint (computed once, liveness never blocked)
    from veles_tpu.serving import InferenceServer
    srv = InferenceServer(wf)
    payload = srv.health()
    assert payload["status"] == "ok"
    assert payload["capacity"]["model_bytes"] == cap2["model_bytes"]
    assert payload["capacity"] is srv.health()["capacity"]  # cached


def test_fused_resource_profile_matches_plan():
    """The static profile is the SAME geometry the traced state uses:
    ZeRO optimizer bytes = sum of plan local slices x 4 (pad included),
    params modeled replicated."""
    wf = _fc_workflow(width=101, name="ProfT")
    wf.initialize(device=None)
    mesh = make_mesh(jax.devices()[:8])
    step = wf.build_fused_step(mesh=mesh, mode="dp", zero_sharding="on")
    prof = step.resource_profile()
    assert prof["zero_active"] and prof["n_data_shards"] == 8
    want_opt = sum(lp.local for plan in step.zero_plans()
                   for lp in plan.values()) * 4
    assert prof["optimizer_state_bytes"] == want_opt
    state = step.init_state()
    vel_elems = sum(int(a.size) for a in
                    jax.tree_util.tree_leaves(state["vel"])
                    if hasattr(a, "size"))
    # the live flat vectors are GLOBAL (padded,) arrays sharded 8 ways:
    # per-shard model bytes x 8 shards == global vel bytes
    assert want_opt * 8 == vel_elems * 4


# ---------------------------------------------------------------------------
# 4. CLI smoke: --verify-workflow=resources on the shipped AlexNet
# ---------------------------------------------------------------------------


def test_verify_workflow_cli_resources_mode():
    """The resources section rides the one --verify-workflow stream:
    marker line + breakdown printed, 0 errors on the shipped AlexNet
    workflow (scaled-down root overrides keep the CI cost bounded; the
    pass itself is identical)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(REPO, "veles_tpu", "samples", "alexnet.py"),
         "--verify-workflow=resources",
         "root.alexnet.loader.minibatch_size=8",
         "root.alexnet.loader.n_train=16",
         "root.alexnet.loader.n_validation=8",
         "root.alexnet.loader.input_hw=67",
         "root.alexnet.n_classes=16"],
        capture_output=True, text=True, timeout=420, cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "verify-workflow: 0 error(s)" in out.stdout
    # resources-only markers: proof the pass actually ran, with the
    # per-component breakdown an operator would read
    assert "verify-workflow: resources section (0 finding(s))" \
        in out.stdout
    assert "resources predicted" in out.stdout
    assert "params=" in out.stdout and "activations=" in out.stdout
