"""The `qwen3next_lm` session, its plain reference and its metrics, at a
size the CPU holds: `fixtures/qwen3next/` is a benchmark of one cell whose
configuration names the session; the session, the reference, the seeded
weights, the counts and the readers are the real tree's, found through
`paths`. Sound runs are correct, the float8 control is not, and timed
paths broken underneath are not: a scan whose decay and state are held in
bfloat16, a shared expert without its gate, a step that leaves its state
unchanged."""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import FIXTURES, ROOT

from benchmark import (manifest, qwen3next_ops_count, qwen3next_reference,
                       qwen3next_seeded)
from test_benchmark_reference import Wrapped, failed

QWEN = os.path.join(FIXTURES, "qwen3next")
CELL = "qwen3next_tiny.seq"
REAL = "qwen3next_ep16.seq8k"


@pytest.fixture
def run_cell(monkeypatch):
    from veles_tpu import caches
    from veles_tpu.telemetry import metrics
    monkeypatch.setattr(caches, "enable_compilation_cache", lambda: "off")
    metrics.reset_default_registry()
    from benchmark import run as bench_run

    def go(trace=False, seed=2 ** 31 + 41, **kw):
        lines = []
        result = bench_run.run_cell(
            QWEN, CELL, seed=seed, seconds=0.3, trace=trace,
            t_start=time.perf_counter(), say=lines.append, **kw)
        return result, lines
    yield go
    metrics.reset_default_registry()


def test_the_fixture_and_the_real_manifest_keep_the_contract():
    assert manifest.problems(manifest.Manifest(QWEN)) == []
    real = manifest.Manifest(ROOT)
    assert manifest.problems(real) == []
    cell = real.cell(REAL)
    assert real.session_name(cell) == "qwen3next_lm"
    assert (cell["chips"], cell["traffic"]) == (1, "seq8k")
    cfg = cell["config_data"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["reduced"] == real._entry("configs",
                                         "qwen3next_ep16")["reduced"]
    assert {"deployment", "assumed", "published"} <= set(cfg)
    assert qwen3next_ops_count.n_params(cfg) == cfg["n_params"] == 625667136
    assert set(real.session(cell).LIMITS) | {"_set_from"} \
        >= set(cell["limits"])
    assert set(real.session(cell).LIMITS) <= set(cell["limits"])
    names = {m["name"] for m in real.metrics("per_layer", REAL)}
    assert names >= {"step_gdn_ms", "gdn_scan_ms", "gdn_scan_mxu_share",
                     "step_gattn_ms", "step_moe_ms", "moe_held_slot_share",
                     "step_mxu_share", "step_unscoped_share", "hbm_peak_gb",
                     "dispatch_ms", "compile_s", "step_device_ms"}
    # their readers go by keys this configuration does not carry, or by
    # another family's scope
    assert not names & {"moe_fullest_expert_load", "moe_experts_mxu_share",
                        "step_attn_ms", "step_hc_ms", "veles_gmm_roofline",
                        "veles_tgmm_roofline", "veles_flash_fwd_roofline"}
    assert {m["name"] for m in real.metrics("end_to_end", REAL)} == {
        "train_samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    tr = cell["traffic_data"]
    assert (tr["driver"], tr["rate_metric"], tr["warmup_steps"],
            tr["steps_in_flight"], tr["span_steps"], tr["trace_steps"]) == (
        "train", "train_samples_per_s_per_chip", 8, 2, 4, 4)
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 4)


def _one_line_texts(data):
    for c in data["configs"]:
        yield f"configs {c['name']} why", c["why"]
        yield f"configs {c['name']} source", c["source"]
    for w in data["workloads"]:
        yield f"workloads {w['name']} why", w["why"]
    for m in data["per_layer"]:
        yield f"per_layer {m['name']} layer", m["layer"]
    for word in data["command"]:
        yield "command", word


@pytest.mark.parametrize("root", [ROOT, QWEN], ids=["real", "fixture"])
def test_every_one_line_text_of_the_manifest_fits_its_200_characters(root):
    """`manifest.problems()` holds a cell's `why` to the contract's 200
    characters and not a configuration's, which is how this PR's first
    entry came to 204 and was refused before any run."""
    data = manifest.Manifest(root).data
    for what, text in _one_line_texts(data):
        assert 1 <= len(text) <= 200, (what, len(text))
        assert text.isprintable(), what    # no newline, no tab
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
    for m in data["end_to_end"] + data["per_layer"]:
        assert set(m) - {"workloads"} == (
            {"name", "unit", "better", "source"}
            | ({"layer", "moves"} if "moves" in m else {"bound"})), m["name"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    but the three in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    cfg = manifest.Manifest(ROOT).cell(REAL)["config_data"]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_experts"]) == (
        2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 512, 10, 32)


def test_a_sound_run_is_correct_and_counts_its_slots_and_chunks(run_cell):
    result, lines = run_cell()
    assert result["correct"] is True, lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    checks = result["checks"]
    man = manifest.Manifest(QWEN)
    assert set(checks) == set(man.session(man.cell(CELL)).LIMITS) \
        | {"compiled_in_window"}
    assert max(checks[n]["value"] for n in (
        "loss_rel_gap", "grad_norm_gap", "grad_rel_err",
        "head_grad_rel_err", "gdn_out_grad_rel_err", "gdn_state_rel_err",
        "dparam_norm_gap")) < 1e-4, lines
    assert checks["route_mismatch_share"]["value"] == 0
    assert checks["slots_dropped"]["value"] == 0
    assert checks["compiled_in_window"]["value"] == 0
    assert any(ln.startswith("gdn: L01 state rms") for ln in lines)


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
    assert 5.0 < got["moe_held_slot_share"]["value"] < 60.0     # 4 of 16
    assert not {"step_gdn_ms", "gdn_scan_ms", "gdn_scan_mxu_share",
                "step_gattn_ms", "step_moe_ms"} & set(got)
    from veles_tpu.telemetry import metrics
    steps = metrics.family_values("veles_gdn_steps_total")
    assert set(steps) == {("L01",), ("L02",), ("L03",)}   # L04 is full
    assert all(v >= result["attempted"] for v in steps.values())
    for layer, v in metrics.family_values("veles_gdn_tokens_total").items():
        assert v == steps[layer] * 2 * 32
    for layer, v in metrics.family_values("veles_gdn_chunks_total").items():
        assert v == steps[layer] * 2 * 4
    assert all(v > 0 for v in
               metrics.family_values("veles_gdn_state_rms").values())
    assert all(v < 0 for v in
               metrics.family_values("veles_gdn_decay_min").values())
    assert set(metrics.family_values("veles_moe_steps_total")) == {
        ("L01",), ("L02",), ("L03",), ("L04",)}


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """On the parent commit the benchmark's files lie over a program that
    has neither the scopes nor the counters: each reader returns None,
    none raises."""
    from veles_tpu.telemetry import metrics
    metrics.reset_default_registry()
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": None,
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    for name in ("step_gdn_ms", "gdn_scan_ms", "gdn_scan_mxu_share",
                 "step_gattn_ms", "step_moe_ms", "moe_held_slot_share"):
        assert man.layer_metric(name).read(ctx) is None, name


def test_the_scans_share_of_the_peak_counts_the_recurrence(monkeypatch):
    """6 dk dv operations a token and value head, three forwards' worth,
    three linear layers: 0.93 TFLOP a step of the real cell; in 100 ms of
    `gdn/scan` that is 4.7 % of a v5e's 197 TFLOP/s."""
    from benchmark import qwen3next_scopes
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": {},
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    work = 3 * 3 * 32768 * 32 * 6 * 128 * 128
    assert qwen3next_ops_count.gdn_scan_flops(ctx["cell"]["config_data"],
                                              4) == work
    monkeypatch.setattr(qwen3next_scopes, "scan_seconds", lambda ctx: 0.1)
    got = man.layer_metric("gdn_scan_mxu_share").read(ctx)
    assert got == pytest.approx(100 * work / 0.1 / 197e12, rel=1e-6)
    assert man.layer_metric("gdn_scan_ms").read(ctx) == pytest.approx(100.0)
    monkeypatch.setattr(qwen3next_scopes, "scan_seconds", lambda ctx: None)
    assert man.layer_metric("gdn_scan_mxu_share").read(ctx) is None
    assert qwen3next_scopes.SCAN.search("jit(train_step)/L01.hc_block/gdn/"
                                        "scan/while/body/dot_general")
    assert qwen3next_scopes.SCAN.search(
        "transpose(jvp(L02.hc_block))/gdn/while/body/closed_call/checkpoint/"
        "rematted_computation/scan/out/mul")
    assert not qwen3next_scopes.SCAN.search("L01.hc_block/gdn/scan")
    assert not qwen3next_scopes.SCAN.search("L01.hc_block/gdn/conv/mul")
    assert not qwen3next_scopes.SCAN.search("L01.hc_block/scan/while")


def test_the_float8_control_is_not_correct():
    """The reference in the precision below, put in the program's place,
    fails the head's gradient and the linear layers' (and more) at the
    fixture's limits."""
    man = manifest.Manifest(QWEN)
    cell = man.cell(CELL)
    cfg = cell["config_data"]
    key = jax.random.key(3)
    params0 = lambda: qwen3next_seeded.make_params(cfg, key)  # noqa: E731
    batches = [qwen3next_seeded.make_batch(cfg, 2, key, k) for k in range(3)]
    low = qwen3next_reference.reference_steps(
        cfg, params0(), batches, precision="float8", keep_first_grad=True)
    low["slots_dropped"] = 0
    ref = qwen3next_reference.reference_steps(
        cfg, params0(), batches,
        first_grad_of_program=low.pop("first_grad"))
    rows = {r["name"]: r for r in qwen3next_reference.compare(
        cfg, low, ref, cell["limits"])}
    assert not rows["head_grad_rel_err"]["ok"]
    assert rows["head_grad_rel_err"]["value"] > 1e-2
    assert not rows["gdn_out_grad_rel_err"]["ok"]
    assert not rows["gdn_state_rel_err"]["ok"]
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


def test_a_scan_held_in_bfloat16_is_not_correct(run_cell, monkeypatch):
    """The linear layers' gates, cumulative decays and state in bfloat16,
    everything else float32: the number set aside for it fails."""
    from veles_tpu.ops import linear_attention as la
    monkeypatch.setattr(la, "gated_delta_net", functools.partial(
        la.gated_delta_net, scan_dtype=jnp.bfloat16))
    result, lines = run_cell()
    assert result["correct"] is False
    assert {"gdn_out_grad_rel_err", "gdn_state_rel_err"} <= set(
        failed(lines)), lines
    assert result["checks"]["gdn_out_grad_rel_err"]["value"] > 3e-3
    assert result["checks"]["gdn_state_rel_err"]["value"] > 3e-3


def test_a_shared_expert_without_its_gate_is_not_correct(run_cell):
    """The shared expert added whole, as the other two models add theirs:
    the gate's leaf gets no gradient and every gradient behind it moves."""
    def ungated(step):
        for u in step.forwards:
            spec = getattr(u, "spec", None)
            if spec is not None and spec.shared_gate:
                spec.shared_gate = False
        return step
    result, lines = run_cell(sabotage=ungated)
    assert result["correct"] is False
    assert {"grad_rel_err", "loss_rel_gap"} <= set(failed(lines)), lines


def test_the_same_seed_gives_the_same_weights_and_a_large_one_works():
    cfg = manifest.Manifest(QWEN).cell(CELL)["config_data"]
    from benchmark import seeded
    key = seeded.stream_key(2 ** 31 + 9, "weights")
    a = qwen3next_seeded.make_params(cfg, key)
    b = qwen3next_seeded.make_params(cfg, key)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(a), jax.tree.leaves(b)))
    shapes = qwen3next_ops_count.shapes_of(cfg)
    assert [{k: v.shape for k, v in layer.items()} for layer in a] == shapes
    lin, full = a[1], a[4]
    assert "attn_w_qkvz" in lin and "attn_w_q" in full
    # zero-centred norms from 0, the gated norm and dt_bias from 1, the
    # decay rate on (0, 16)
    assert not np.any(lin["attn_norm"]) and not np.any(full["attn_q_norm"])
    assert not np.any(a[-1]["final_norm"])
    assert np.all(lin["attn_o_norm"] == 1) and np.all(lin["attn_dt_bias"] == 1)
    rate = np.exp(np.asarray(lin["attn_a_log"]))
    assert np.all(rate > 0) and np.all(rate < 16)
    assert float(np.std(lin["attn_conv"])) == pytest.approx(0.05, rel=0.2)
