"""Budgeted kernel search over generated Pallas candidates (ISSUE 9).

The contracts, all CPU-runnable (Pallas via interpret mode):
1. TEMPLATES — each template op exposes a typed config space (>=8
   generated candidates), names round-trip (parse -> materialize), and
   generated points pass the ops.reference equivalence contract.
2. GATE — the search is STRUCTURALLY unable to time a candidate without
   a passing equivalence record: a failing contract yields an untimed
   `equiv_fail` trial, and a ledger bypass raises UngatedCandidateError.
3. SEARCH — runs end-to-end on CPU across >=3 ops with >=8 generated
   candidates timed each, trials <= budget (budget bounds WORK), trial
   outcomes route through veles_autotune_trials_total{op,outcome}, and a
   second run is a PURE cache hit (any timing is an assertion failure).
4. CONSUMERS — a searched winner changes what the fused step / the
   attention unit actually trace, trajectory-equivalent to the default.

Three searches that run 30-40 generated candidates end to end, each an
interpret-mode kernel to compile (90-110 s a test under tier-1's six
workers), are marked `slow` and run by name (`pytest -m slow
tests/test_kernel_search.py`, ISSUE 39): the acceptance run, the
fusion families' sweep and the in-graph fusion search. Each names its
own ops, budget and cache file and asserts on the process-global ledger
and selection, so no two of them read one search; the smaller searches
beside them hold the same contracts on every run.
"""

import json
import os

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.ops import autotune as at
from veles_tpu.ops import templates
from veles_tpu.ops import variants
from veles_tpu.znicz.standard_workflow import StandardWorkflow

SEARCH_OPS = ["lrn_maxpool", "flash_attn", "sgd_update"]


@pytest.fixture(autouse=True)
def _interpret_mode():
    """No TPU here: this file ASKS for interpret-mode kernels (they
    never fall into it by themselves), through the one switch there is:
    `variants.resolve` and `variants.pallas_ok` read it too."""
    import veles_tpu.ops.pallas_kernels as pk
    prev, pk._FORCE_INTERPRET = pk._FORCE_INTERPRET, True
    yield
    pk._FORCE_INTERPRET = prev


@pytest.fixture(autouse=True)
def _isolated_selection():
    """Selection table and equivalence ledger are process-global:
    snapshot/clear around every test (same contract as
    test_variants_autotune)."""
    snap = variants.selection_table()
    yield
    variants.clear_selection()
    for op, name in snap.items():
        variants.select(op, name)
    templates.clear_ledger()


def _tiny_workflow(name="SearchT"):
    prng.seed_all(1)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
        n_train=16, minibatch_size=4, noise=0.5)
    return StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 5,
                 "ky": 5, "stride": (2, 2), "s2d": "auto",
                 "weights_stddev": 0.1},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2)},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name=name)


# ---------------------------------------------------------------------------
# 1. templates: spaces, naming, materialization, equivalence
# ---------------------------------------------------------------------------


def test_template_spaces_cover_three_ops_with_eight_plus_candidates():
    assert set(templates.template_ops()) >= set(SEARCH_OPS)
    for op in SEARCH_OPS:
        ts = templates.templates_for(op)
        assert ts, op
        assert sum(t.size for t in ts) >= 8, op
        assert op in templates.CONTRACTS and op in templates.BENCHES


def test_generated_name_round_trip_and_rejection():
    t = templates.templates_for("flash_attn")[0]
    cfg = {"blk_q": 256, "blk_k": 512, "kv_order": "rev", "drop": 0}
    name = t.name(cfg)
    assert t.parse(name) == cfg
    # out-of-space values, unknown axes, foreign bases: all rejected
    assert t.parse(
        "pallas[blk_q=999,blk_k=512,kv_order=rev,drop=0]") is None
    assert t.parse(
        "pallas[blk_q=256,blk_k=512,kv_order=rev,drop=0,x=1]") is None
    assert t.parse(
        "other[blk_q=256,blk_k=512,kv_order=rev,drop=0]") is None
    assert t.parse("pallas[blk_q=256]") is None          # missing axes
    with pytest.raises(ValueError):
        t.name({"blk_q": 999, "blk_k": 512, "kv_order": "rev",
                "drop": 0})


def test_materialize_from_name_alone():
    """A persisted winner's NAME is enough to rebuild the variant in a
    fresh process — variants.get falls through to the templates."""
    name = "pallas_rows[rt=256]"
    spec_vars = {v.name for v in variants.variants_for("sgd_update")}
    v = variants.get("sgd_update", name)
    assert v.generated and v.pallas and v.op == "sgd_update"
    assert variants.has("sgd_update", name)
    assert not variants.has("sgd_update", "pallas_rows[rt=7]")
    # and it is now a first-class registry entry (selectable)
    variants.select("sgd_update", name)
    assert variants.effective("sgd_update") == name
    assert name not in spec_vars  # it really was materialized on demand


@pytest.mark.parametrize("op,name", [
    ("maxpool", "gen[algo=slices,fold=linear]"),
    ("conv_stem", "gen[pack=direct,acc=f32,epi=none]"),
    ("flash_attn", "pallas[blk_q=128,blk_k=256,kv_order=rev,drop=0]"),
    ("flash_attn", "pallas[blk_q=512,blk_k=1024,kv_order=fwd,drop=0]"),
    ("sgd_update", "pallas_rows[rt=8]"),
    ("sgd_update", "pallas_rows[rt=1024]"),
])
def test_generated_candidates_pass_reference_contract(op, name):
    rec = templates.check_equivalence(op, name, force=True)
    assert rec["status"] == "pass", rec


@pytest.mark.parametrize("op,name", [
    # the three FUSION families (ISSUE 13) — each fused point gated on
    # its COMPOSED ops.reference golden, fwd+bwd, interpret on CPU
    ("lrn_maxpool", "fused[rt=1,io=native,fuse=1]"),
    ("lrn_maxpool", "fused[rt=2,io=f32,fuse=1]"),
    ("lrn_maxpool", "fused[rt=4,io=native,fuse=0]"),   # composed point
    ("conv_stem", "gen[pack=s2d,acc=native,epi=lrn]"),
    ("conv_stem", "gen[pack=direct,acc=f32,epi=lrn]"),
    ("flash_attn", "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=1]"),
    ("flash_attn", "pallas[blk_q=256,blk_k=256,kv_order=rev,drop=1]"),
])
def test_fused_points_pass_composed_golden_contract(op, name):
    rec = templates.check_equivalence(op, name, force=True)
    assert rec["status"] == "pass", rec


def test_fusion_structure_helpers():
    """fusion_config is the one rule deciding whether a name CLAIMS a
    neighbor: fuse-axis-on points only; composed/foreign names never."""
    assert templates.fusion_members("lrn_maxpool") == ("lrn", "maxpool")
    assert templates.fusion_members("lrn") == ()
    assert templates.fusion_config(
        "lrn_maxpool", "fused[rt=2,io=native,fuse=1]")["fuse"] == 1
    assert templates.fusion_config(
        "lrn_maxpool", "fused[rt=2,io=native,fuse=0]") is None
    assert templates.fusion_config("lrn_maxpool", "composed") is None
    assert templates.fusion_config(
        "conv_stem", "gen[pack=s2d,acc=native,epi=lrn]") is not None
    assert templates.fusion_config(
        "conv_stem", "gen[pack=s2d,acc=native,epi=none]") is None
    assert templates.fusion_config(
        "flash_attn",
        "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=1]") is not None
    # the composed lrn_maxpool incumbent is a live registry entry
    assert variants.has("lrn_maxpool", "composed")


# ---------------------------------------------------------------------------
# 2. the gate: no passing equivalence record -> not timeable
# ---------------------------------------------------------------------------


def test_failing_contract_means_untimed_equiv_fail(tmp_path, monkeypatch):
    """Break the sgd contract: every candidate records equiv_fail and
    the timing path is NEVER entered (the microbench is a tripwire)."""
    def bad_contract(apply):
        raise AssertionError("injected mismatch")
    monkeypatch.setitem(templates.CONTRACTS, "sgd_update", bad_contract)

    def tripwire(*a, **k):
        raise AssertionError("timed an ungated candidate")
    monkeypatch.setitem(templates.BENCHES, "sgd_update", tripwire)
    templates.clear_ledger()
    rep = at.search_op("sgd_update", budget=6,
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert rep["source"] == "error"           # nothing measurable
    assert rep["trials"] == 6
    assert all(t["outcome"] == "equiv_fail" for t in rep["trace"])


def test_ledger_bypass_raises_ungated_error(tmp_path, monkeypatch):
    """Even if check_equivalence CLAIMS a pass, timing consults the
    LEDGER itself — a bypass that never recorded the pass is refused
    structurally, not by convention."""
    monkeypatch.setattr(templates, "check_equivalence",
                        lambda op, name, force=False: {"status": "pass"})
    templates.clear_ledger()
    with pytest.raises(templates.UngatedCandidateError):
        at.search_op("sgd_update", budget=4,
                     cache=at.AutotuneCache(str(tmp_path / "c.json")))


def test_every_timed_trial_was_gated_first(tmp_path):
    """Property over a real search: for every trial with outcome
    "timed", a passing ledger record exists, and within the trace no
    candidate is timed before its equivalence entry (check-then-time is
    the only path — equiv_fail rows prove the check ran and blocked)."""
    templates.clear_ledger()
    rep = at.search_workflow(budget=9, ops=SEARCH_OPS,
                             cache=at.AutotuneCache(
                                 str(tmp_path / "c.json")))
    timed = 0
    for op, r in rep.items():
        for trial in r["trace"]:
            if trial["outcome"] == "timed":
                timed += 1
                assert templates.passed(op, trial["variant"]), \
                    (op, trial)
                assert r["equivalence"][trial["variant"]] == "pass"
    assert timed > 0


# ---------------------------------------------------------------------------
# 3. the search end-to-end: budget, cache purity, metrics
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_search_end_to_end_cpu(tmp_path, monkeypatch):
    """The acceptance run: >=3 ops searched on CPU (interpret mode),
    >=8 generated candidates timed per op, trials <= budget, winners
    persisted; the SECOND run is a pure cache hit — zero timing."""
    from veles_tpu.telemetry import metrics as tm
    templates.clear_ledger()
    cache_path = str(tmp_path / "cache.json")
    counter = at._trials_counter()
    before = {op: counter.labels(op=op, outcome="timed").value
              for op in SEARCH_OPS}
    rep = at.search_workflow(budget=36, ops=SEARCH_OPS,
                             cache=at.AutotuneCache(cache_path))
    assert set(rep) == set(SEARCH_OPS)
    total = 0
    for op, r in rep.items():
        assert r["source"] == "searched"
        assert r["trials"] <= r["budget"]
        total += r["trials"]
        generated_timed = [t for t in r["trace"]
                           if t["outcome"] == "timed"
                           and "[" in t["variant"]]
        assert len(generated_timed) >= 8, (op, r["trace"])
        # the winner is live in the registry and resolvable
        assert variants.effective(op) == r["variant"]
        assert variants.has(op, r["variant"])
        # trial outcomes landed on the metrics plane
        assert counter.labels(op=op, outcome="timed").value \
            > before[op]
    assert total <= 36
    # persisted at the explicit schema/version with the trial trace
    with open(cache_path) as f:
        raw = json.load(f)
    assert raw["schema"] == at.AutotuneCache.SCHEMA
    assert raw["version"] == at.AutotuneCache.VERSION
    assert len(raw["entries"]) == 3
    for rec in raw["entries"].values():
        assert rec["trace"] and rec["budget"]

    # second run: PURE cache hit — any timing is a failure
    def boom(*a, **k):
        raise AssertionError("search re-timed on a cache hit")
    monkeypatch.setattr(at, "_time_variant", boom)
    for op in SEARCH_OPS:
        monkeypatch.setitem(templates.BENCHES, op, boom)
    variants.clear_selection()
    rep2 = at.search_workflow(budget=36, ops=SEARCH_OPS,
                              cache=at.AutotuneCache(cache_path))
    assert all(r["source"] == "cache" for r in rep2.values())
    assert {op: r["variant"] for op, r in rep2.items()} \
        == {op: r["variant"] for op, r in rep.items()}
    # cache hits re-select the winners (generated names re-materialize)
    for op, r in rep2.items():
        assert variants.effective(op) == r["variant"]


def test_budget_bounds_work_not_successes(tmp_path):
    templates.clear_ledger()
    rep = at.search_op("flash_attn", budget=3,
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert rep["trials"] == 3
    assert len(rep["trace"]) == 3


def test_microbench_aliased_configs_not_double_timed(tmp_path):
    """flash_attention_pallas clamps requested blocks to divisors of S
    (fit()), so at the bench shapes distinct configs can alias to ONE
    effective kernel. The search must time each effective kernel once —
    no budget burned re-timing duplicates, and the winner names a
    config that actually executed."""
    templates.clear_ledger()
    rep = at.search_op("flash_attn", budget=12,
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    t = templates.templates_for("flash_attn")[0]
    keys = [t.bench_key(t.parse(tr["variant"]))
            for tr in rep["trace"]
            if tr["outcome"] == "timed" and "[" in tr["variant"]]
    assert keys
    assert len(keys) == len(set(keys))
    # the winner (if generated) maps to a kernel that really ran
    cfg = rep.get("config")
    if cfg is not None:
        assert t.bench_key(cfg) in keys


def test_zero_budget_is_skipped_not_error(tmp_path):
    """A total budget too small to floor every op allocates zero trials
    somewhere — that op reports 'skipped' (selection untouched), never
    'error', and nothing is cached for it."""
    rep = at.search_op("sgd_update", budget=0,
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert rep["source"] == "skipped"
    assert rep["trials"] == 0 and rep["trace"] == []
    assert variants.selected("sgd_update") is None
    assert not os.path.exists(str(tmp_path / "c.json"))


def test_empty_ops_list_searches_nothing(tmp_path):
    """ops=[] (an --ops restriction naming no template op) must search
    NOTHING — only ops=None means 'all template ops'."""
    rep = at.search_workflow(budget=8, ops=[],
                             cache=at.AutotuneCache(
                                 str(tmp_path / "c.json")))
    assert rep == {}


def test_autotune_workflow_budget_searches_in_graph(tmp_path):
    """--autotune --autotune-budget path: every template-backed op the
    workflow names rides the budgeted search IN-GRAPH (since ISSUE 12
    that is the whole discovered registry here — maxpool/conv_stem
    gained templates, closing the carried ROADMAP item), sgd_update and
    grad_reduce ride the same budget via their microbenches, and the
    whole report stays one dict — `lrn`, which has no template (its
    two lowerings are chosen by platform and shape), rides the flat
    enumeration into it. The budget is deliberately too small
    to floor every op: allocation is priority-ordered, so the
    first-discovered ops search and the tail reports 'skipped' — never
    'error'."""
    templates.clear_ledger()
    wf = _tiny_workflow("InGraphT")
    rep = at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                               cache_path=str(tmp_path / "c.json"),
                               budget=6)
    # discovery order (conv first in the layer list) wins the scarce
    # budget; the in-graph timer serves the workflow-discovered ops
    assert rep["conv_stem"]["source"] == "searched"
    assert rep["conv_stem"]["timer"] == "in_graph"
    assert rep["maxpool"]["source"] == "searched"
    assert rep["maxpool"]["timer"] == "in_graph"
    assert rep["maxpool"]["trials"] <= 6
    # hand-written incumbents were timed first
    first = rep["maxpool"]["trace"][0]["variant"]
    assert "[" not in first
    # the remaining ops ride the same budget — with 6 total trials
    # they are allocated zero and SKIP, never error
    for op in ("lrn_maxpool", "sgd_update", "grad_reduce"):
        assert rep[op]["source"] in ("searched", "skipped"), (op, rep[op])
    assert rep["lrn"]["source"] == "tuned"
    assert set(rep["lrn"]["timings_s"]) == {"banded_matmul",
                                            "pallas_one_pass"}
    for op in ("lrn", "maxpool", "conv_stem"):
        assert variants.effective(op) == rep[op]["variant"]


def test_autotune_workflow_budget_covers_whole_registry(tmp_path):
    """With a budget large enough to floor every op, the search covers
    the WHOLE discovered registry plus the below-graph sgd_update and
    grad_reduce spaces (the ISSUE-12 carried item: no registry op left
    un-searched)."""
    templates.clear_ledger()
    wf = _tiny_workflow("FullCoverT")
    rep = at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                               cache_path=str(tmp_path / "c.json"),
                               budget=19)
    for op in ("lrn_maxpool", "maxpool", "conv_stem", "sgd_update",
               "grad_reduce"):
        assert rep[op]["source"] == "searched", (op, rep[op])
    assert rep["lrn"]["source"] == "tuned"
    assert rep["maxpool"]["timer"] == "in_graph"
    assert rep["grad_reduce"]["timer"] == "microbench"
    # the grad_reduce key is salted with the link geometry: the same
    # space under a different (hosts x local) request hashes apart
    import os as _os

    from veles_tpu.ops.variants import GRAD_REDUCE_LOCAL_ENV
    prev_env = _os.environ.get(GRAD_REDUCE_LOCAL_ENV)
    try:
        _os.environ[GRAD_REDUCE_LOCAL_ENV] = "2"
        other = at.op_cache_key(
            "cpu", "grad_reduce",
            at.link_geometry_signature()
            + templates.space_signature("grad_reduce"), None)
    finally:
        if prev_env is None:
            _os.environ.pop(GRAD_REDUCE_LOCAL_ENV, None)
        else:
            _os.environ[GRAD_REDUCE_LOCAL_ENV] = prev_env
    assert other != rep["grad_reduce"]["key"]


# ---------------------------------------------------------------------------
# priority order + budget allocation (LAYER_PROFILE.json consumption)
# ---------------------------------------------------------------------------


def test_priority_order_reads_layer_profile(tmp_path):
    prof = tmp_path / "LAYER_PROFILE.json"
    prof.write_text(json.dumps(
        {"ops": {"lrn": 0.24, "sgd_update": 0.02, "dropout": 0.06}}))
    ordered = at.priority_order(["sgd_update", "flash_attn", "lrn"],
                                str(prof))
    assert [op for op, _ in ordered] == ["lrn", "sgd_update",
                                         "flash_attn"]
    assert ordered[0][1] == 0.24
    # missing file: given order, zero shares, no error
    ordered2 = at.priority_order(["a", "b"], str(tmp_path / "nope.json"))
    assert ordered2 == [("a", 0.0), ("b", 0.0)]
    # corrupt file likewise degrades
    prof.write_text("{not json")
    assert at.priority_order(["a"], str(prof)) == [("a", 0.0)]


def test_budget_allocation_weights_by_share():
    ordered = [("lrn", 0.6), ("flash_attn", 0.2), ("sgd_update", 0.0)]
    alloc = at.allocate_budget(ordered, 32)
    assert sum(alloc.values()) == 32
    assert alloc["lrn"] > alloc["flash_attn"] > 0
    assert alloc["sgd_update"] >= 2          # the floor: always probed
    # no shares -> equal split
    alloc2 = at.allocate_budget([("a", 0.0), ("b", 0.0)], 10)
    assert alloc2 == {"a": 5, "b": 5}
    # budget smaller than the floor x ops: first (highest-share) op wins
    alloc3 = at.allocate_budget(ordered, 3)
    assert sum(alloc3.values()) == 3
    assert alloc3["lrn"] >= alloc3["sgd_update"]
    # per-op floors: an op with 2 incumbents gets room for its hand
    # set PLUS a generated point even at zero share
    assert at.incumbent_floor("flash_attn") == 3    # xla_mha, pallas, +1
    assert at.incumbent_floor("sgd_update") == 2    # xla_tree, +1
    alloc4 = at.allocate_budget(
        [("lrn", 0.9), ("flash_attn", 0.0)], 10,
        floors={"lrn": at.incumbent_floor("lrn"),
                "flash_attn": at.incumbent_floor("flash_attn")})
    assert alloc4["flash_attn"] >= 3
    assert sum(alloc4.values()) == 10


def test_search_spends_budget_by_profile_priority(tmp_path):
    templates.clear_ledger()
    prof = tmp_path / "prof.json"
    prof.write_text(json.dumps({"ops": {"sgd_update": 0.8,
                                        "flash_attn": 0.1}}))
    rep = at.search_workflow(
        budget=16, ops=SEARCH_OPS, profile_path=str(prof),
        cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert rep["sgd_update"]["priority_share"] == 0.8
    assert rep["sgd_update"]["budget"] > rep["flash_attn"]["budget"]
    # an op the profile does not name still gets its incumbent and one
    # generated point
    assert rep["lrn_maxpool"]["budget"] >= 2


# ---------------------------------------------------------------------------
# layer_profile: machine-readable output the search consumes
# ---------------------------------------------------------------------------


def _load_layer_profile_module():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "layer_profile.py")
    spec = importlib.util.spec_from_file_location("layer_profile", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_layer_profile_writes_search_consumable_json(tmp_path,
                                                     monkeypatch):
    lp = _load_layer_profile_module()
    wf = _tiny_workflow("ProfT")
    wf.initialize(device=None)
    records = lp.profile_workflow(wf, steps=2)
    out = tmp_path / "LAYER_PROFILE.json"
    rec = lp.write_profile(records, str(out), meta={"batch": 4})
    assert rec["schema"] == "veles-layer-profile"
    # per-op shares exist for the workflow's tunable ops and include
    # the GD twins' time (lrn backward counts as lrn)
    assert {"lrn", "maxpool", "conv_stem"} <= set(rec["ops"])
    assert all(0.0 <= v <= 1.0 for v in rec["ops"].values())
    lrn_units = [u for u in rec["units"] if u["op"] == "lrn"]
    assert len(lrn_units) >= 2               # forward AND backward
    # the file is exactly what priority_order consumes
    ordered = at.priority_order(["lrn", "flash_attn"], str(out))
    assert ordered[0][0] == "lrn" and ordered[0][1] > 0
    # env override is the default path
    monkeypatch.setenv("VELES_LAYER_PROFILE_PATH", str(out))
    assert lp.default_profile_path() == str(out)
    assert at.default_profile_path() == str(out)


def test_layer_profile_folds_trace_spans(tmp_path):
    lp = _load_layer_profile_module()
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "step", "dur": 2e6},
        {"ph": "X", "name": "step", "dur": 1e6},
        {"ph": "X", "name": "feed.device_put", "dur": 5e5},
        {"ph": "M", "name": "meta"},
    ]}))
    rec = lp.write_profile([], str(tmp_path / "p.json"),
                           trace_json=str(trace))
    assert rec["driver_spans"]["step"] == {"total_s": 3.0, "count": 2}
    assert rec["driver_spans"]["feed.device_put"]["count"] == 1
    # unreadable trace degrades to no driver_spans, never an error
    rec2 = lp.write_profile([], str(tmp_path / "p2.json"),
                            trace_json=str(tmp_path / "missing.json"))
    assert "driver_spans" not in rec2


# ---------------------------------------------------------------------------
# 4. consumers: the winners change what actually traces
# ---------------------------------------------------------------------------


def test_fused_step_traces_selected_sgd_pallas_variant():
    """Selecting a generated sgd_update point changes the step's update
    lowering — trajectory-equivalent to the xla_tree default (same math
    in f32), and the variant_table names it."""
    import jax

    def run(variant):
        variants.clear_selection()
        if variant:
            variants.select("sgd_update", variant)
        wf = _tiny_workflow(f"SgdT_{variant or 'default'}")
        wf.initialize(device=None)
        with variants.pallas_interpret():
            step = wf.build_fused_step()
            state = step.init_state()
            rs = np.random.RandomState(5)
            x = rs.randn(4, 12, 12, 3).astype(np.float32)
            y = rs.randint(0, 4, 4)
            table = step.variant_table()
            for _ in range(2):
                state, _ = step.train(state, x, y)
            params = jax.tree_util.tree_map(np.asarray,
                                            state["params"])
        return params, table

    p_ref, tab_ref = run(None)
    assert tab_ref["sgd_update"] == "xla_tree"
    p_gen, tab_gen = run("pallas_rows[rt=16]")
    assert tab_gen["sgd_update"] == "pallas_rows[rt=16]"
    flat_ref = jax.tree_util.tree_leaves(p_ref)
    flat_gen = jax.tree_util.tree_leaves(p_gen)
    for a, b in zip(flat_ref, flat_gen):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


import jax  # noqa: E402  (used by the trajectory test above)


def test_attention_unit_traces_selected_flash_variant():
    """The attention unit's local path consults the registry: a selected
    generated point runs (interpret mode) and matches the einsum."""
    import jax.numpy as jnp

    import veles_tpu.ops.pallas_kernels as pk
    from veles_tpu.ops import attention as oa
    from veles_tpu.znicz.attention import MultiHeadAttention

    pk._FORCE_INTERPRET = True
    try:
        rs = np.random.RandomState(9)
        n, s, e = 2, 64, 16
        x = jnp.asarray(rs.randn(n, s, e).astype(np.float32))
        params = {k: jnp.asarray(0.2 * w) for k, w in zip(
            ("wq", "wk", "wv", "wo"),
            rs.randn(4, e, e).astype(np.float32))}
        unit = MultiHeadAttention(None, n_heads=2, causal=True,
                                  use_flash="on", name="mha")
        unit.head_dim = e // 2
        variants.select("flash_attn",
                        "pallas[blk_q=128,blk_k=128,kv_order=rev,drop=0]")
        got = np.asarray(unit._apply(params, x))
        gold = np.asarray(unit._apply(params, x, allow_flash=False))
        np.testing.assert_allclose(got, gold, rtol=5e-4, atol=5e-5)
        # auto mode on CPU (no interpret context): einsum fallback, and
        # variant_effective reports what would actually trace
        unit.use_flash = "auto"
        unit.input = type("A", (), {"shape": (n, s, e)})()
        assert unit.variant_effective() == "xla_mha"
    finally:
        pk._FORCE_INTERPRET = False


def test_apply_cached_inherits_searched_winners(tmp_path, monkeypatch):
    """A standalone --fused start inherits SEARCHED decisions:
    apply_cached probes the searched key (workflow sigs + space
    signature) and applies below-graph ops (sgd_update/flash_attn) by
    their space key — zero timing, generated names re-materialize."""
    templates.clear_ledger()
    cache_path = str(tmp_path / "c.json")
    wf = _tiny_workflow("ApplyT")
    at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                         cache_path=cache_path, budget=5)  # conv_stem
    at.search_op("sgd_update", budget=4,
                 cache=at.AutotuneCache(cache_path))
    searched = {op: variants.effective(op)
                for op in ("conv_stem", "sgd_update")}
    variants.clear_selection()

    def boom(*a, **k):
        raise AssertionError("apply_cached timed something")
    monkeypatch.setattr(at, "_time_variant", boom)
    for op in SEARCH_OPS:
        monkeypatch.setitem(templates.BENCHES, op, boom)
    wf2 = _tiny_workflow("ApplyT2")
    applied = at.apply_cached(wf2, cache_path=cache_path)
    assert applied["conv_stem"] == searched["conv_stem"]
    assert applied["sgd_update"] == searched["sgd_update"]
    for op, name in applied.items():
        assert variants.effective(op) == name


# ---------------------------------------------------------------------------
# 5. searched cross-op fusion (ISSUE 13)
# ---------------------------------------------------------------------------


def test_fusion_ledger_bypass_raises_ungated_error(tmp_path,
                                                   monkeypatch):
    """The fusion families ride the SAME structural gate: a bypass that
    never recorded a pass is refused for lrn_maxpool too."""
    monkeypatch.setattr(templates, "check_equivalence",
                        lambda op, name, force=False: {"status": "pass"})
    templates.clear_ledger()
    with pytest.raises(templates.UngatedCandidateError):
        at.search_op("lrn_maxpool", budget=4,
                     cache=at.AutotuneCache(str(tmp_path / "c.json")))


@pytest.mark.slow
def test_search_times_fused_candidate_per_family(tmp_path):
    """The acceptance sweep: one budgeted search over the three fusion
    families times >=1 FUSED candidate (fuse axis on) per family, every
    timed fused point carrying a passing composed-golden ledger record —
    the gate is the only path to a timing."""
    templates.clear_ledger()
    rep = at.search_workflow(
        budget=30, ops=["lrn_maxpool", "conv_stem", "flash_attn"],
        cache=at.AutotuneCache(str(tmp_path / "c.json")))
    for op in ("lrn_maxpool", "conv_stem", "flash_attn"):
        fused_timed = [
            t for t in rep[op]["trace"]
            if t["outcome"] == "timed"
            and templates.fusion_config(op, t["variant"]) is not None]
        assert fused_timed, (op, rep[op]["trace"])
        for t in fused_timed:
            assert templates.passed(op, t["variant"]), (op, t)


def test_discover_fusions_finds_adjacent_pair():
    wf = _tiny_workflow("FuseDiscT")
    wf.initialize(device=None)
    found = at.discover_fusions(wf)
    assert set(found) == {"lrn_maxpool"}
    (sig,) = found["lrn_maxpool"]
    assert set(sig) == {"lrn", "maxpool"}
    # a per-layer override on either member blocks the claim
    wf.forwards[2].variant_override = "slices"
    assert at.discover_fusions(wf) == {}
    wf.forwards[2].variant_override = None
    # ...as does the maxabs flavor
    wf.forwards[2].use_abs = True
    assert at.discover_fusions(wf) == {}


def test_fused_winner_changes_step_trace_and_table():
    """Selecting the fused lrn_maxpool winner makes the normalization
    unit claim its pooling successor (fusion_pairs names the pair, the
    pooling unit passes through), the trajectory matches the composed
    path at rtol 1e-5, and variant_table reports the fused winner for
    BOTH member ops — reported == traced."""
    import jax

    def run(sel):
        variants.clear_selection()
        if sel:
            variants.select(*sel)
        wf = _tiny_workflow(f"FuseT_{sel[1] if sel else 'composed'}")
        wf.initialize(device=None)
        with variants.pallas_interpret():
            step = wf.build_fused_step()
            state = step.init_state()
            rs = np.random.RandomState(5)
            x = rs.randn(4, 12, 12, 3).astype(np.float32)
            y = rs.randint(0, 4, 4)
            pairs = [(i, j, v.name) for i, j, v in step.fusion_pairs()]
            table = step.variant_table()
            for _ in range(3):
                state, _ = step.train(state, x, y)
            params = jax.tree_util.tree_map(np.asarray,
                                            state["params"])
        return params, pairs, table

    p_ref, pairs_ref, tab_ref = run(None)
    assert pairs_ref == []
    assert "lrn_maxpool" not in tab_ref

    name = "fused[rt=2,io=native,fuse=1]"
    p_f, pairs_f, tab_f = run(("lrn_maxpool", name))
    assert pairs_f == [(1, 2, name)]          # norm claims its pool
    assert tab_f["lrn_maxpool"] == name
    assert tab_f["lrn"] == f"lrn_maxpool/{name}"
    assert tab_f["maxpool"] == f"lrn_maxpool/{name}"
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_f)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    # the conv-stem epilogue family: the conv claims the SAME norm unit
    # (left-to-right precedence), trajectory still equal
    cname = "gen[pack=s2d,acc=native,epi=lrn]"
    p_c, pairs_c, tab_c = run(("conv_stem", cname))
    assert pairs_c == [(0, 1, cname)]
    assert tab_c["conv_stem"] == cname
    assert tab_c["lrn"] == f"conv_stem/{cname}"
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_c)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fusion_precedence_conv_epilogue_wins_the_shared_lrn():
    """When BOTH a conv epilogue winner and a fused lrn_maxpool winner
    want the same norm unit, pairs claim left-to-right: the conv takes
    the norm, the pool stays unfused — a unit joins at most one pair."""
    variants.clear_selection()
    variants.select("conv_stem", "gen[pack=s2d,acc=native,epi=lrn]")
    variants.select("lrn_maxpool", "fused[rt=2,io=native,fuse=1]")
    wf = _tiny_workflow("FusePrecT")
    wf.initialize(device=None)
    with variants.pallas_interpret():
        step = wf.build_fused_step()
        pairs = [(i, j) for i, j, _ in step.fusion_pairs()]
    assert pairs == [(0, 1)]


def test_fusion_gates_block_claim(monkeypatch):
    """No claim under GSPMD (a pallas_call cannot be auto-partitioned),
    under a member override, or for the maxabs flavor."""
    # (this file's fixture asks for interpret mode; there is one switch,
    # so the gate off a TPU is read with it off)
    monkeypatch.setattr("veles_tpu.ops.pallas_kernels._FORCE_INTERPRET",
                        False)
    variants.select("lrn_maxpool", "fused[rt=2,io=native,fuse=1]")
    wf = _tiny_workflow("FuseGateT")
    wf.initialize(device=None)
    with variants.pallas_interpret():
        step = wf.build_fused_step()
        assert step.fusion_pairs()
        # member override pins a member lowering: the pair is off
        wf.forwards[2].variant_override = "reduce_window"
        assert step.fusion_pairs() == []
        wf.forwards[2].variant_override = None
        assert step.fusion_pairs()
    # outside the interpret context on CPU, resolve() falls back to the
    # composed incumbent: no claim (same gate as every pallas variant)
    assert step.fusion_pairs() == []


def test_search_charges_fused_candidate_combined_share(tmp_path):
    """priority_order gives the PURE fusion op the combined share of
    its members (the profile attributes time per member op)."""
    import json as _json
    prof = tmp_path / "prof.json"
    prof.write_text(_json.dumps(
        {"ops": {"lrn": 0.2, "maxpool": 0.15, "conv_stem": 0.1}}))
    ordered = dict(at.priority_order(
        ["lrn", "maxpool", "lrn_maxpool", "conv_stem"], str(prof)))
    assert ordered["lrn_maxpool"] == pytest.approx(0.35)
    assert ordered["lrn"] == pytest.approx(0.2)
    assert ordered["conv_stem"] == pytest.approx(0.1)


def test_layer_profile_splits_fused_share_back_to_members():
    """A fused kernel's time in a profile record is attributed back to
    its member ops by the pre-fusion share ratio (equal split when the
    members carry no shares of their own) — the search's priority order
    stays meaningful after a fusion winner lands."""
    lp = _load_layer_profile_module()
    split = lp.split_fused_shares(
        {"lrn_maxpool": 0.3, "lrn": 0.2, "maxpool": 0.1,
         "conv_stem": 0.05})
    assert "lrn_maxpool" not in split
    assert split["lrn"] == pytest.approx(0.4)       # 0.2 + 0.3*(2/3)
    assert split["maxpool"] == pytest.approx(0.2)   # 0.1 + 0.3*(1/3)
    assert split["conv_stem"] == pytest.approx(0.05)
    # no member shares: equal split
    split2 = lp.split_fused_shares({"lrn_maxpool": 0.4})
    assert split2["lrn"] == pytest.approx(0.2)
    assert split2["maxpool"] == pytest.approx(0.2)
    # no fused key: untouched
    assert lp.split_fused_shares({"lrn": 0.1}) == {"lrn": 0.1}
    # write_profile applies the split and keeps the raw form
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        rec = lp.write_profile(
            [{"name": "u", "class": "U", "op": "lrn_maxpool",
              "run_time_s": 0.4, "run_count": 1},
             {"name": "v", "class": "V", "op": None,
              "run_time_s": 0.6, "run_count": 1}],
            os.path.join(td, "p.json"))
    assert "lrn_maxpool" not in rec["ops"]
    assert rec["ops"]["lrn"] == pytest.approx(0.2)
    assert rec["ops_raw"]["lrn_maxpool"] == pytest.approx(0.4)


@pytest.mark.slow
def test_autotune_workflow_searches_fusion_in_graph(tmp_path):
    """--autotune --autotune-budget: the workflow's adjacent (lrn,
    maxpool) pair makes lrn_maxpool searchable IN-GRAPH, and
    apply_cached re-applies a searched fused winner in a fresh process
    with zero timing."""
    templates.clear_ledger()
    cache_path = str(tmp_path / "c.json")
    wf = _tiny_workflow("FuseSearchT")
    rep = at.autotune_workflow(wf, steps=1, repeats=1, batch=4,
                               cache_path=cache_path, budget=40,
                               ops=["lrn_maxpool"])
    assert rep["lrn_maxpool"]["source"] == "searched"
    assert rep["lrn_maxpool"]["timer"] == "in_graph"
    fused_timed = [
        t for t in rep["lrn_maxpool"]["trace"]
        if t["outcome"] == "timed"
        and templates.fusion_config("lrn_maxpool",
                                    t["variant"]) is not None]
    assert fused_timed
    winner = rep["lrn_maxpool"]["variant"]
    assert variants.effective("lrn_maxpool") == winner
    # fresh process twin: apply_cached probes the fusion-pair key
    variants.clear_selection()
    wf2 = _tiny_workflow("FuseSearchT2")
    applied = at.apply_cached(wf2, cache_path=cache_path)
    assert applied.get("lrn_maxpool") == winner


def test_member_search_suspends_fusion_claim(monkeypatch, tmp_path):
    """While a MEMBER op (lrn) times in-graph, a selected fused
    lrn_maxpool winner stands down — otherwise the claimed pair makes
    every member candidate trace the same program and a noise-picked
    'winner' persists under the member's cache key. Restored after."""
    variants.select("lrn_maxpool", "fused[rt=2,io=native,fuse=1]")
    seen = []

    def spy_timer(wf, mesh, compute_dtype, steps, repeats, batch):
        seen.append(variants.selected("lrn_maxpool"))
        return 0.001

    monkeypatch.setattr(at, "_time_variant", spy_timer)
    templates.clear_ledger()
    wf = _tiny_workflow("SuspendT")
    at.search_workflow(wf, ops=["maxpool"], budget=4,
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert seen and all(s is None for s in seen)
    assert variants.selected("lrn_maxpool") \
        == "fused[rt=2,io=native,fuse=1]"


def test_members_tune_before_their_fusion_op(tmp_path, monkeypatch):
    """search_workflow orders MEMBER ops before the fusion op that
    composes them (even when the combined share ranks the fusion op
    first): the fusion decision competes against tuned members."""
    import json as _json
    prof = tmp_path / "prof.json"
    prof.write_text(_json.dumps({"ops": {"lrn": 0.3, "maxpool": 0.2}}))
    order = []
    orig = at.search_op

    def spy(op, **kw):
        order.append(op)
        return orig(op, **kw)

    monkeypatch.setattr(at, "search_op", spy)
    templates.clear_ledger()
    at.search_workflow(budget=8, ops=["lrn_maxpool", "lrn", "maxpool"],
                       profile_path=str(prof),
                       cache=at.AutotuneCache(str(tmp_path / "c.json")))
    assert order.index("lrn_maxpool") > order.index("maxpool")
    assert "lrn" not in order       # no template: nothing to search


def test_variant_table_keeps_unclaimed_sibling_entry():
    """A chain with TWO (norm, pool) pairs where only the first is
    claimable (the second pool carries a per-layer override): the
    op-level maxpool entry must keep the still-composed sibling's
    override name — the pair's claim reports through the lrn_maxpool
    entry, never by clobbering a lowering another unit really traced."""
    prng.seed_all(1)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(20, 20, 3), n_validation=8,
        n_train=16, minibatch_size=4, noise=0.5)
    wf = StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 5,
                 "ky": 5, "stride": (2, 2), "s2d": "off",
                 "weights_stddev": 0.1},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2)},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2),
                 "lowering": "slices"},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="MixedPairT")
    wf.initialize(device=None)
    name = "fused[rt=2,io=native,fuse=1]"
    variants.select("lrn_maxpool", name)
    with variants.pallas_interpret():
        step = wf.build_fused_step()
        pairs = [(i, j) for i, j, _ in step.fusion_pairs()]
        table = step.variant_table()
    assert pairs == [(1, 2)]              # only the override-free pair
    assert table["lrn_maxpool"] == name
    # the claimed pair's member report fills in ONLY where no unclaimed
    # unit traces: the second (overridden) pool keeps its own name, the
    # second norm keeps the plain lrn resolution
    assert table["maxpool"] == "slices"
    assert "lrn_maxpool/" not in table["lrn"]


def test_unclaimed_conv_stem_reports_epi_none_twin():
    """An UNCLAIMED applicable auto stem under an epi=lrn conv_stem
    winner traces the epilogue-less program (no epilogue is passed), so
    variant_effective must report the epi=none twin — the conv-side
    mirror of the attention drop=0-twin rule."""
    wf = _tiny_workflow("ConvTwinT")
    wf.initialize(device=None)
    conv = wf.forwards[0]
    variants.select("conv_stem", "gen[pack=s2d,acc=f32,epi=lrn]")
    assert conv.variant_effective() == "gen[pack=s2d,acc=f32,epi=none]"
    variants.select("conv_stem", "gen[pack=s2d,acc=f32,epi=none]")
    assert conv.variant_effective() == "gen[pack=s2d,acc=f32,epi=none]"
    variants.select("conv_stem", "s2d")
    assert conv.variant_effective() == "s2d"


def test_attention_reports_drop_zero_twin_of_fused_winner():
    """The attention unit feeds no dropout mask, so a selected drop=1
    flash winner traces the UNFUSED program — variant_effective must
    name the drop=0 twin (reported == traced)."""
    from veles_tpu.znicz.attention import MultiHeadAttention
    unit = MultiHeadAttention(None, n_heads=2, causal=True,
                              use_flash="on", name="mha_drop")
    unit.input = type("A", (), {"shape": (1, 4096, 16)})()
    with variants.pallas_interpret():
        variants.select(
            "flash_attn",
            "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=1]")
        assert unit.variant_effective() \
            == "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=0]"
        variants.select(
            "flash_attn",
            "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=0]")
        assert unit.variant_effective() \
            == "pallas[blk_q=128,blk_k=128,kv_order=fwd,drop=0]"


def test_launcher_rejects_budget_without_autotune():
    from veles_tpu.launcher import Launcher
    with pytest.raises(SystemExit):
        Launcher(fused=True, autotune=False, autotune_budget=8)
    with pytest.raises(SystemExit):
        Launcher(fused=True, autotune=True, autotune_budget=0)
