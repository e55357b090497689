"""BENCHMARK.json keeps the contract as far as files can show it, and the
command-line runner refuses to measure off a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import FIXTURES, ROOT

from benchmark import manifest

REAL = manifest.Manifest(ROOT)
CELLS = [w["name"] for w in REAL.data["workloads"]]


@pytest.mark.parametrize("root", [ROOT, FIXTURES])
def test_manifest_has_nothing_the_contract_refuses(root):
    assert manifest.problems(manifest.Manifest(root)) == []


def test_paths_and_command_are_the_benchmarks_own():
    d = REAL.data
    assert d["paths"] == ["benchmark", "tests/benchmark"]
    assert d["command"] == ["python3", "benchmark/run.py"]
    assert len(json.dumps(d)) < 64 * 1024
    assert 1 <= d["run_seconds"] <= 51


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in REAL.data["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(REAL.data["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    c = REAL.cell(cell)
    assert c["config_data"]["name"] == c["config"]
    assert c["config_data"]["reduced"] == []
    assert set(c["limits"]) >= {"loss_rel_gap", "grad_norm_gap",
                                "grad_rel_err", "head_grad_rel_err",
                                "dparam_norm_gap"}
    assert hasattr(REAL.driver(c["traffic_data"]["driver"]), "run")
    e2e = [m["name"] for m in REAL.metrics("end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in REAL.metrics("per_layer", cell):
        assert callable(REAL.layer_metric(m["name"]).read)


def test_a_broken_manifest_is_told_apart():
    m = manifest.Manifest(FIXTURES)
    m.data = json.loads(json.dumps(m.data))
    m.data["end_to_end"][0]["unit"] = "samples per second"
    m.data["workloads"][0]["chips"] = 2
    m.data["per_layer"][0]["name"] = "no_such_reader"
    m.data["end_to_end"][1]["workloads"] = ["tiny.step"]
    m.data["per_layer"][1]["moves"] = "step_ms_p95"
    found = " ".join(manifest.problems(m))
    assert "bad unit" in found and "chips 2" in found
    assert "no_such_reader" in found
    assert "cell tiny.feed does not report step_ms_p95" in found


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_cli_off_a_tpu_exits_nonzero_and_prints_no_result():
    done = _cli(ROOT)
    assert done.returncode != 0
    assert "TPU" in done.stderr
    assert "metrics" not in done.stdout and "correct" not in done.stdout


def test_the_cli_in_a_bare_directory_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in REAL.data["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _cli(str(tmp_path))
    assert done.returncode != 0
    assert "metrics" not in done.stdout
