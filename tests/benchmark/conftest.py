"""Shared by the benchmark's tests: where things are, and a run of a
fixture cell on the CPU (the harness's look for a chip skipped)."""

import time

import pytest

from bench_paths import FIXTURES


@pytest.fixture
def run_fixture_cell(monkeypatch):
    """run_cell on the fixture manifest. The persistent compile cache
    stays off: a test run shares no cache directory with a real one."""
    from veles_tpu import caches
    monkeypatch.setattr(caches, "enable_compilation_cache", lambda: "off")
    from benchmark import run as bench_run

    def go(cell, seed=2 ** 31 + 77, seconds=0.3, trace=False, **kw):
        lines = []
        result = bench_run.run_cell(
            FIXTURES, cell, seed=seed, seconds=seconds, trace=trace,
            t_start=time.perf_counter(), say=lines.append, **kw)
        return result, lines
    return go
