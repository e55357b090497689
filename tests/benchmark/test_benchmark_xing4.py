"""The `xing4_lm` session, its plain reference and its metrics, at a size
the CPU holds: `fixtures/xing4/` is a benchmark of one cell whose
configuration names the session; the session, the reference, the seeded
weights, the counts and the readers are the real tree's, found through
`paths`. Sound runs are correct, the float8 control is not, and two
timed paths broken underneath are not: a step that leaves its state
unchanged, and a router that does not hear its selection bias."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import FIXTURES, ROOT

from benchmark import manifest, xing4_ops_count, xing4_reference, xing4_seeded
from test_benchmark_reference import Wrapped, failed

XING4 = os.path.join(FIXTURES, "xing4")
CELL = "xing4_tiny.step"


@pytest.fixture
def run_cell(monkeypatch):
    from veles_tpu import caches
    from veles_tpu.telemetry import metrics
    monkeypatch.setattr(caches, "enable_compilation_cache", lambda: "off")
    metrics.reset_default_registry()
    from benchmark import run as bench_run

    def go(trace=False, seed=2 ** 31 + 32, **kw):
        lines = []
        result = bench_run.run_cell(
            XING4, CELL, seed=seed, seconds=0.3, trace=trace,
            t_start=time.perf_counter(), say=lines.append, **kw)
        return result, lines
    yield go
    metrics.reset_default_registry()


def test_the_fixture_and_the_real_manifest_keep_the_contract():
    assert manifest.problems(manifest.Manifest(XING4)) == []
    real = manifest.Manifest(ROOT)
    assert manifest.problems(real) == []
    cell = real.cell("xing4_ep8.step")
    assert real.session_name(cell) == "xing4_lm"
    cfg = cell["config_data"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "num_attention_heads", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert xing4_ops_count.n_params(cfg) == cfg["n_params"] == 789610308
    assert set(real.session(cell).LIMITS) | {"_set_from"} \
        >= set(cell["limits"])
    names = {m["name"] for m in real.metrics("per_layer", "xing4_ep8.step")}
    assert names >= {"step_attn_ms", "step_moe_ms", "step_hc_ms",
                     "moe_held_slot_share", "moe_fullest_expert_load",
                     "moe_experts_mxu_share", "step_mxu_share",
                     "hbm_peak_gb", "dispatch_ms", "compile_s"}


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    but the four in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    cfg = manifest.Manifest(ROOT).cell("xing4_ep8.step")["config_data"]
    assert cfg["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_a_sound_run_is_correct_and_counts_its_slots(run_cell):
    result, lines = run_cell()
    assert result["correct"] is True, lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    checks = result["checks"]
    assert set(checks) == set(manifest.Manifest(XING4).session(
        manifest.Manifest(XING4).cell(CELL)).LIMITS) | {"compiled_in_window"}
    assert max(checks[n]["value"] for n in (
        "loss_rel_gap", "grad_norm_gap", "grad_rel_err",
        "head_grad_rel_err", "dparam_norm_gap")) < 1e-4, lines
    assert checks["route_mismatch_share"]["value"] == 0
    assert checks["balance_bias_gap"]["value"] == 0
    assert checks["slots_dropped"]["value"] == 0
    assert any(ln.startswith("balance:") for ln in lines)


def test_a_traced_run_reports_the_expert_layers_counters(run_cell,
                                                         monkeypatch):
    """The CPU has no device trace: the profiler is stubbed out, so the
    scope readers find nothing; the counters are read."""
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
    share = result["metrics"]["moe_held_slot_share"]["value"]
    assert 5.0 < share < 60.0          # 2 of 8 held: 25 at balance
    assert result["metrics"]["moe_fullest_expert_load"]["value"] >= 0.9
    from veles_tpu.telemetry import metrics
    steps = metrics.family_values("veles_moe_steps_total")
    assert set(steps) == {("L02",), ("L03",), ("mtp",)}
    assert all(v >= result["attempted"] for v in steps.values())


def test_the_balance_sweep_follows_seeds_with_one_program(monkeypatch,
                                                          capsys):
    """`read_balance.py`: per seed and multiple of 20 warm-up steps the
    held share of every expert layer over the steps before the window
    would open; a second seed starts from its own weights and tokens."""
    from benchmark import read_balance
    from veles_tpu import caches
    monkeypatch.setattr(caches, "enable_compilation_cache", lambda: "off")
    monkeypatch.setattr(read_balance, "ROOT", XING4)
    assert read_balance.main(["--workload", CELL, "--seeds",
                              f"5,{2 ** 31 + 7}", "--steps", "20"]) == 0
    rows = [json.loads(ln[len("BALANCE "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("BALANCE ")]
    assert [r["seed"] for r in rows] == [5, 2 ** 31 + 7]
    for r in rows:
        at = r["at"]["20"]
        assert set(at["shares"]) == {"L02", "L03", "mtp"}
        assert all(0.0 < s < 1.0 for s in at["shares"].values())
        assert at["reached"] == (at["worst_off"] <= 0.02)
        assert r["dropped"] == 0 and r["steps"] == 23
        assert r["fullest_layer_step"] >= 1.0
    assert rows[0]["at"] != rows[1]["at"]


def test_the_float8_control_is_not_correct():
    """The reference in the precision below, put in the program's place,
    fails the head's gradient (and more) at the fixture's limits."""
    man = manifest.Manifest(XING4)
    cell = man.cell(CELL)
    cfg = cell["config_data"]
    key = jax.random.key(3)
    params0 = lambda: xing4_seeded.make_params(cfg, key)  # noqa: E731
    batches = [xing4_seeded.make_batch(cfg, 2, key, k) for k in range(3)]
    held = cfg["held_experts_first"]
    low = xing4_reference.reference_steps(
        cfg, params0(), batches, held_first=held, precision="float8",
        keep_first_grad=True)
    low["slots_dropped"] = 0
    ref = xing4_reference.reference_steps(
        cfg, params0(), batches, held_first=held,
        first_grad_of_program=low.pop("first_grad"))
    rows = {r["name"]: r for r in xing4_reference.compare(
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


def test_a_router_that_ignores_its_bias_is_not_correct(run_cell):
    def deaf(step):
        for u in step.forwards:
            spec = getattr(u, "spec", None)
            if spec is not None and spec.ffn == "experts":
                spec._experts = (
                    lambda p, h, bias, inner=spec._experts:
                    inner(p, h, bias * 0.0))
        return step
    result, lines = run_cell(sabotage=deaf)
    assert result["correct"] is False
    assert "route_mismatch_share" in failed(lines), lines


def test_the_same_seed_gives_the_same_tokens_and_a_large_one_works():
    cfg = manifest.Manifest(XING4).cell(CELL)["config_data"]
    from benchmark import seeded
    a = xing4_seeded.make_batch(cfg, 2, seeded.stream_key(2 ** 31 + 9,
                                                          "inputs"), 4)
    b = xing4_seeded.make_batch(cfg, 2, seeded.stream_key(2 ** 31 + 9,
                                                          "inputs"), 4)
    c = xing4_seeded.make_batch(cfg, 2, seeded.stream_key(2 ** 31 + 9,
                                                          "inputs"), 5)
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert a[1].shape == (2, cfg["seq_len"], 2)
    assert int(a[0].max()) < cfg["vocab_size"]
    # targets are the stream shifted by one and by two
    assert np.array_equal(a[1][:, :-1, 0], a[0][:, 1:])
    assert np.array_equal(a[1][:, :-2, 1], a[0][:, 2:])
