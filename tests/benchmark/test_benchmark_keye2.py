"""The `keye2_lm` session, its plain reference and its metrics, at a size
the CPU holds: `fixtures/keye2/` is a benchmark of one cell whose
configuration names the session; the session, the reference, the seeded
weights, the counts and the readers are the real tree's, found through
`paths`. Sound runs are correct, the float8 control is not, and timed
paths broken underneath are not: a selection that ignores the indexer,
an index loss that is dropped, a step that leaves its state unchanged."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import FIXTURES, ROOT

from benchmark import keye2_ops_count, keye2_reference, keye2_seeded, manifest
from test_benchmark_reference import Wrapped, failed

KEYE2 = os.path.join(FIXTURES, "keye2")
CELL = "keye2_tiny.long"
REAL = "keye2_ep8.long16k"


@pytest.fixture
def run_cell(monkeypatch):
    from veles_tpu import caches
    from veles_tpu.telemetry import metrics
    monkeypatch.setattr(caches, "enable_compilation_cache", lambda: "off")
    metrics.reset_default_registry()
    from benchmark import run as bench_run

    def go(trace=False, seed=2 ** 31 + 35, **kw):
        lines = []
        result = bench_run.run_cell(
            KEYE2, CELL, seed=seed, seconds=0.3, trace=trace,
            t_start=time.perf_counter(), say=lines.append, **kw)
        return result, lines
    yield go
    metrics.reset_default_registry()


def test_the_fixture_and_the_real_manifest_keep_the_contract():
    assert manifest.problems(manifest.Manifest(KEYE2)) == []
    real = manifest.Manifest(ROOT)
    assert manifest.problems(real) == []
    cell = real.cell(REAL)
    assert real.session_name(cell) == "keye2_lm"
    assert (cell["chips"], cell["traffic"]) == (1, "long16k")
    cfg = cell["config_data"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["reduced"] == real._entry("configs", "keye2_ep8")["reduced"]
    assert keye2_ops_count.n_params(cfg) == cfg["n_params"] == 659190016
    assert set(real.session(cell).LIMITS) | {"_set_from"} \
        >= set(cell["limits"])
    assert set(real.session(cell).LIMITS) <= set(cell["limits"])
    names = {m["name"] for m in real.metrics("per_layer", REAL)}
    assert names >= {"step_dsa_ms", "step_indexer_ms",
                     "dsa_scored_pair_share", "dsa_attend_mxu_share",
                     "step_moe_ms", "moe_held_slot_share", "step_mxu_share",
                     "step_unscoped_share", "hbm_peak_gb", "dispatch_ms",
                     "compile_s", "veles_gmm_roofline",
                     "veles_tgmm_roofline"}
    # their readers go by keys this configuration does not carry
    assert not names & {"moe_fullest_expert_load", "moe_experts_mxu_share",
                        "step_attn_ms", "step_hc_ms"}
    tr = cell["traffic_data"]
    assert (tr["driver"], tr["rate_metric"], tr["warmup_steps"],
            tr["steps_in_flight"], tr["span_steps"], tr["trace_steps"]) == (
        "train", "train_samples_per_s_per_chip", 8, 2, 4, 4)
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (16384, 1)


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    but the four in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = manifest.Manifest(ROOT).cell(REAL)["config_data"]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (
        2048, 32, 4, 128, 768, 8)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}


def test_a_sound_run_is_correct_and_counts_its_pairs(run_cell):
    result, lines = run_cell()
    assert result["correct"] is True, lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    checks = result["checks"]
    assert set(checks) == set(manifest.Manifest(KEYE2).session(
        manifest.Manifest(KEYE2).cell(CELL)).LIMITS) | {"compiled_in_window"}
    assert max(checks[n]["value"] for n in (
        "loss_rel_gap", "grad_norm_gap", "grad_rel_err",
        "head_grad_rel_err", "dparam_norm_gap")) < 1e-4, lines
    assert checks["route_mismatch_share"]["value"] == 0
    assert checks["select_mismatch_share"]["value"] == 0
    assert checks["slots_dropped"]["value"] == 0
    assert checks["compiled_in_window"]["value"] == 0
    assert any(ln.startswith("drift:") for ln in lines) \
        or result["attempted"] <= 4


def test_a_traced_run_reports_the_counters(run_cell, monkeypatch):
    """The CPU has no device trace: the profiler is stubbed out, so the
    scope readers find nothing and their metrics are left out; the
    counters are read."""
    from benchmark import trace_reduce
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(
        trace_reduce, "reduce_dir",
        lambda d, n_devices: {"busy_s": 0.9, "window_s": 1.0,
                              "step_device_s": 0.004,
                              "breakdown": {"device_ops": [["op", 0.9]],
                                            "idle_gaps": []}})
    result, lines = run_cell(trace=True)
    assert result["correct"] is True, lines
    got = result["metrics"]
    assert 20.0 < got["moe_held_slot_share"]["value"] < 80.0   # 4 of 8
    assert got["dsa_scored_pair_share"]["value"] == 100.0
    assert not {"step_dsa_ms", "step_indexer_ms",
                "dsa_attend_mxu_share"} & set(got)
    from veles_tpu.telemetry import metrics
    steps = metrics.family_values("veles_dsa_steps_total")
    assert set(steps) == {("L01",), ("L02",)}
    assert all(v >= result["attempted"] for v in steps.values())
    s, k = 32, 8
    for name, per_seq in (
            ("veles_dsa_pairs_causal_total", keye2_ops_count.pairs_causal(s)),
            ("veles_dsa_pairs_scored_total", keye2_ops_count.pairs_causal(s)),
            ("veles_dsa_pairs_selected_total",
             keye2_ops_count.pairs_selected(s, k))):
        for layer, v in metrics.family_values(name).items():
            assert v == steps[layer] * 2 * per_seq, (name, layer)


def test_the_readers_find_nothing_in_a_program_without_the_counters():
    """On the parent commit the benchmark's files lie over a program that
    has no `veles_dsa_*` family: each reader returns None, none raises."""
    from veles_tpu.telemetry import metrics
    metrics.reset_default_registry()
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": None,
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    for name in ("step_dsa_ms", "step_indexer_ms", "dsa_scored_pair_share",
                 "dsa_attend_mxu_share", "veles_dsa_pmean_roofline",
                 "veles_gmm_roofline", "veles_tgmm_roofline"):
        assert man.layer_metric(name).read(ctx) is None, name


def test_a_grouped_kernels_roofline_counts_the_held_slots(monkeypatch):
    """`veles_gmm` runs nine of a (token, slot) pair's 2 x 2048 x 768
    products a layer and step, `veles_tgmm` three: at the even load of
    16,384 slots a layer, 6 layers, in 20 and 10 ms of the kernel, 54.0 and
    36.0 % of a v5e's 197 TFLOP/s; no kernel time, nothing to read."""
    from benchmark import keye2_scopes
    from veles_tpu.telemetry import metrics
    from veles_tpu.znicz import lm
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": {},
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    metrics.reset_default_registry()
    count = {"steps": 10, "slots": 10 * 131072, "held": 10 * 16384,
             "fullest": 0, "dropped": 0}
    lm.publish_moe_counters({f"L{i:02d}": dict(count) for i in range(1, 7)})
    seconds = {"veles_gmm": 0.020, "veles_tgmm": 0.010, "other": None}
    monkeypatch.setattr(keye2_scopes, "kernel_seconds",
                        lambda ctx, kernel: seconds[kernel])
    work = 6 * 16384 * 2 * 2048 * 768
    for kernel, share in (("veles_gmm", 9 * work / 0.020 / 197e12),
                          ("veles_tgmm", 3 * work / 0.010 / 197e12)):
        got = man.layer_metric(kernel + "_roofline").read(ctx)
        assert got == pytest.approx(100 * share, rel=1e-6) and got < 100
    assert keye2_scopes.grouped_roofline(ctx, "other") is None
    metrics.reset_default_registry()


def test_the_float8_control_is_not_correct():
    """The reference in the precision below, put in the program's place,
    fails the head's gradient (and more) at the fixture's limits."""
    man = manifest.Manifest(KEYE2)
    cell = man.cell(CELL)
    cfg = cell["config_data"]
    key = jax.random.key(3)
    params0 = lambda: keye2_seeded.make_params(cfg, key)  # noqa: E731
    batches = [keye2_seeded.make_batch(cfg, 2, key, k) for k in range(3)]
    low = keye2_reference.reference_steps(
        cfg, params0(), batches, precision="float8", keep_first_grad=True)
    low["slots_dropped"] = 0
    ref = keye2_reference.reference_steps(
        cfg, params0(), batches,
        first_grad_of_program=low.pop("first_grad"))
    rows = {r["name"]: r for r in keye2_reference.compare(
        cfg, low, ref, cell["limits"])}
    assert not rows["head_grad_rel_err"]["ok"]
    assert rows["head_grad_rel_err"]["value"] > 1e-2
    assert rows["slots_dropped"]["ok"]


def test_a_frozen_step_is_not_correct(run_cell):
    def frozen(step):
        def train(state, x, y, w=None):
            _, out = step.train(jax.tree.map(jnp.copy, state), x, y, w)
            return state, out
        return Wrapped(step, train)
    result, lines = run_cell(sabotage=frozen)
    assert result["correct"] is False
    assert "dparam_norm_gap" in failed(lines), lines


def test_a_selection_that_ignores_the_indexer_is_not_correct(run_cell,
                                                             monkeypatch):
    """Every query attends to its last `topk` keys, a sliding window,
    whatever the index scores say."""
    from veles_tpu.ops import attention as oa

    def window(index, causal, topk, thr=None):
        pos = jnp.arange(causal.shape[1])[None, :]
        last = causal.sum(axis=1, keepdims=True) - 1
        return causal & (pos > last - topk), jnp.zeros(
            causal.shape[:1], jnp.uint32)
    monkeypatch.setattr(oa, "select_topk", window)
    result, lines = run_cell()
    assert result["correct"] is False
    assert "select_mismatch_share" in failed(lines), lines


def test_an_index_loss_that_is_dropped_is_not_correct(run_cell):
    def deaf(step):
        head = step.forwards[-1]
        head.term_weights = dict(head.term_weights, index=0.0)
        return step
    result, lines = run_cell(sabotage=deaf)
    assert result["correct"] is False
    # the indexer's leaves get no gradient: the worst leaf's error is whole
    assert "grad_rel_err" in failed(lines), lines


def test_the_same_seed_gives_the_same_tokens_and_a_large_one_works():
    cfg = manifest.Manifest(KEYE2).cell(CELL)["config_data"]
    from benchmark import seeded
    key = seeded.stream_key(2 ** 31 + 9, "inputs")
    a = keye2_seeded.make_batch(cfg, 2, key, 4)
    b = keye2_seeded.make_batch(cfg, 2, key, 4)
    c = keye2_seeded.make_batch(cfg, 2, key, 5)
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert a[1].shape == (2, cfg["seq_len"])
    assert int(a[0].max()) < cfg["vocab_size"]
    # the target is the stream shifted by one
    assert np.array_equal(a[1][:, :-1], a[0][:, 1:])
