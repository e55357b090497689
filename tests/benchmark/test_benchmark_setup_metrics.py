"""The seven `setup_*` per-layer metrics (PR 37): each reader on a registry
filled by hand with the arithmetic written out, None on a program without
phases, and the entries as `BENCHMARK.json` holds them."""

import json
import os

import pytest

from bench_paths import ROOT

from benchmark.manifest import Manifest, problems
from veles_tpu.telemetry import metrics

SEVEN = ("setup_before_program_s", "setup_initialize_s", "setup_state_s",
         "setup_trace_s", "setup_lower_s", "setup_cache_read_s",
         "setup_cache_misses")
SIX = ("alexnet.step", "vgg16.step", "alexnet.feed", "vgg16.dp4",
       "xing4_ep8.step", "keye2_ep8.long16k")


@pytest.fixture
def registry():
    metrics.reset_default_registry()
    yield metrics.default_registry()
    metrics.reset_default_registry()


def fill(reg) -> None:
    """One set-up as a program would have counted it, plus what the
    harness and the reference compiled outside every phase."""
    h = metrics.setup_handles(reg)
    h.age_at_import.set(12.0)
    for phase, secs in (("setup.import", 0.5), ("setup.backend", 0.25),
                        ("setup.initialize", 3.0), ("setup.loader", 0.75),
                        ("setup.build_step", 0.125), ("setup.init_state", 2.0),
                        ("setup.first_dispatch", 20.0)):
        h.seconds.labels(phase=phase).inc(secs)
    c = metrics.compile_handles(reg)
    for stage, during, secs in (
            ("trace", "setup.first_dispatch", 6.0),
            ("lower", "setup.first_dispatch", 2.5),
            ("backend", "setup.first_dispatch", 11.0),
            ("trace", "setup.init_state", 0.25),
            ("lower", "setup.init_state", 0.125),
            ("backend", "setup.init_state", 0.5),
            ("trace", "setup.loader", 0.0625),
            ("lower", "setup.loader", 0.0625),
            ("backend", "setup.loader", 0.25),
            ("trace", "none", 100.0), ("lower", "none", 50.0),
            ("backend", "none", 200.0)):
        c.seconds.labels(stage=stage, during=during).inc(secs)
    c.cache.labels(result="hit", during="setup.first_dispatch").inc(1)
    c.cache.labels(result="miss", during="setup.first_dispatch").inc(2)
    c.cache.labels(result="miss", during="none").inc(5)
    c.cache_read_s.labels(during="setup.first_dispatch").inc(10.5)
    c.cache_read_s.labels(during="none").inc(70.0)


@pytest.mark.parametrize("name, by_hand", [
    ("setup_before_program_s", 12.0 + 0.5 + 0.25),
    # every phase metric: own seconds less the stages counted under it
    ("setup_initialize_s", 3.0 + 0.75 - (0.0625 + 0.0625 + 0.25)),
    ("setup_state_s", 0.125 + 2.0 - (0.25 + 0.125 + 0.5)),
    ("setup_trace_s", 6.0 + 0.25 + 0.0625),
    ("setup_lower_s", 2.5 + 0.125 + 0.0625),
    ("setup_cache_read_s", 10.5),
    ("setup_cache_misses", 2.0),
])
def test_a_reader_on_a_registry_filled_by_hand(registry, name, by_hand):
    fill(registry)
    assert Manifest(ROOT).layer_metric(name).read({}) == \
        pytest.approx(by_hand, abs=1e-12)


@pytest.mark.parametrize("name", SEVEN)
def test_a_reader_finds_nothing_in_a_program_without_phases(registry, name):
    """The parent's program: standard families, none of set-up."""
    reader = Manifest(ROOT).layer_metric(name)
    assert reader.read({}) is None
    # what the harness compiled alone is no phase of the program either
    metrics.compile_handles(registry).seconds.labels(
        stage="trace", during="none").inc(3.0)
    assert reader.read({}) is None


def test_a_cold_program_with_phases_reads_zero_from_the_cache(registry):
    """Phases and no cache event yet: 0, not a metric left out."""
    metrics.setup_handles(registry).seconds.labels(
        phase="setup.initialize").inc(1.0)
    man = Manifest(ROOT)
    assert man.layer_metric("setup_cache_read_s").read({}) == 0.0
    assert man.layer_metric("setup_cache_misses").read({}) == 0.0
    assert man.layer_metric("setup_trace_s").read({}) == 0.0
    assert man.layer_metric("setup_before_program_s").read({}) == 0.0


def test_the_seven_entries_are_appended_and_move_setup_s():
    """Found by name, so that a later PR may append metrics after them
    and cells to their `workloads` (test_benchmark_addition.py)."""
    man = Manifest(ROOT)
    assert problems(man) == []
    per_layer = man.data["per_layer"]
    names = [e["name"] for e in per_layer]
    at = [names.index(name) for name in SEVEN]
    # appended: after what PR 36 left, in the order ISSUE 37 gives them
    assert at == sorted(at)
    assert at[0] > names.index("veles_dsa_index_bwd_roofline")
    assert names[0] == "compile_s"
    for i in at:
        e = per_layer[i]
        assert e["moves"] == "setup_s" and e["better"] == "lower"
        assert e["source"] == "program_counter"
        assert e["layer"] == "launcher and compile"
        assert tuple(e["workloads"][:len(SIX)]) == SIX
        assert e["unit"] == ("programs" if e["name"].endswith("misses")
                             else "s")
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
    assert len(json.dumps(man.data)) < 64 * 1024
