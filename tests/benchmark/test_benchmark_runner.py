"""A cell is files plus one manifest entry: the fixture manifest's cells
are picked up by name and run through the real `train` driver and
`StandardWorkflow`, on the CPU, called as a function (the command line
refuses to run off a TPU, test_benchmark_manifest.py)."""

import json
import os

import pytest

from bench_paths import FIXTURES, ROOT

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", ["tiny.step", "tiny.feed", "tiny.dp4"])
def test_a_fixture_cell_runs_and_is_correct(run_fixture_cell, cell):
    result, lines = run_fixture_cell(cell)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_samples_per_s_per_chip",
                                      "step_ms_p95", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)
    checked = [ln for ln in lines if ln.startswith("check:")]
    assert any("grad_norm_gap" in ln and "limit" in ln for ln in checked)
    assert ("fed_rows_wrong" in " ".join(checked)) == (cell == "tiny.feed")


def test_the_fixture_needs_no_file_of_the_benchmark_changed():
    """Its configuration, traffic, limits and its own per-layer metric are
    files of the fixture; the driver is the benchmark's, found by name."""
    from benchmark.manifest import Manifest
    man = Manifest(FIXTURES)
    bench = os.path.join(ROOT, "benchmark")
    cell = man.cell("tiny.step")
    assert cell["config_data"]["name"] == "tiny"
    assert man.find("layer_metrics", "steps_counted.py").startswith(FIXTURES)
    assert man.find("traffic", "tiny_resident.json").startswith(FIXTURES)
    assert man.find("drivers", "train.py").startswith(bench)
    assert man.find("layer_metrics", "compile_s.py").startswith(bench)
    assert not os.path.exists(os.path.join(bench, "configs", "tiny.json"))


def test_a_traced_run_reports_the_cells_per_layer_metrics(
        run_fixture_cell, monkeypatch):
    """The CPU has no device trace: the profiler is stubbed out and the
    reduction returns a canned result; the rest of the traced path runs."""
    import jax

    from benchmark import trace_reduce
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(
        trace_reduce, "reduce_dir",
        lambda d, n_devices: {"busy_s": 0.9, "window_s": 1.0,
                              "breakdown": {"device_ops": [["op", 0.9]],
                                            "idle_gaps": []}})
    result, _ = run_fixture_cell("tiny.step", trace=True)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["metrics"]) == {"steps_counted", "compile_s"}
    assert result["metrics"]["steps_counted"]["value"] \
        == result["attempted"]
    assert result["device"]["busy_s"] == 0.9
    assert result["device"]["window_s"] == 1.0


def test_the_same_seed_gives_the_same_first_steps(run_fixture_cell):
    def losses(seed):
        _, lines = run_fixture_cell("tiny.step", seed=seed, seconds=0.05)
        return next(ln for ln in lines if "program losses" in ln) \
            .split("program losses")[1]
    assert losses(5) == losses(5)
    assert losses(5) != losses(2 ** 31 + 5)
