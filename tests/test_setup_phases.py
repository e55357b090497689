"""Set-up measured inside the program (PR 37; docs/OBSERVABILITY.md):
`tracer.phase` and its cause, the `veles_setup_*` counters, jax's compile
stages as a union under the phase that caused them, the persistent cache's
hits and misses, the producers from `Workflow.initialize` to a step's first
dispatch, and the set-up ring in the `--trace PATH` export."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_telemetry import make_workflow
from veles_tpu.telemetry import compile_stages, metrics, tracer


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """An empty set-up ring, registry and stack of phases a test."""
    tracer.uninstall()
    monkeypatch.setattr(tracer, "_SETUP",
                        tracer.Tracer(tracer._SETUP_CAPACITY))
    tracer._OPEN.stack = []
    metrics.reset_default_registry()
    compile_stages.listen()
    yield
    tracer.uninstall()
    metrics.reset_default_registry()


def seconds(**labels):
    """One child of `veles_compile_seconds_total` (0 where absent)."""
    fam = metrics.family_values("veles_compile_seconds_total") or {}
    return fam.get((labels["stage"], labels["during"]), 0.0)


def by_phase(family):
    return {k[0]: v for k, v in
            (metrics.family_values(family) or {}).items()}


# -- phases ---------------------------------------------------------------------

def test_a_phases_cause_is_the_innermost_open_phase_of_its_thread():
    other = {}

    def elsewhere():
        with tracer.phase("setup.loader") as p:
            other["cause"], other["current"] = p.cause, \
                tracer.current_phase()

    assert tracer.current_phase() is None
    with tracer.phase("setup.initialize") as outer:
        with tracer.phase("setup.loader") as inner:
            assert tracer.current_phase() == "setup.loader"
            t = threading.Thread(target=elsewhere)
            t.start()
            t.join()
        assert tracer.current_phase() == "setup.initialize"
    assert (outer.cause, inner.cause) == (None, "setup.initialize")
    # a second thread's phases have no cause: the stack is the thread's
    assert other == {"cause": None, "current": "setup.loader"}
    events = tracer.setup_ring().events()
    assert [(e[0], e[1], e[6]["cause"]) for e in events] == [
        ("setup.loader", "setup", "none"),
        ("setup.loader", "setup", "setup.initialize"),
        ("setup.initialize", "setup", "none")]
    assert len({e[4] for e in events}) == 2          # two threads


def test_the_counters_grow_by_phase_with_each_phases_own_seconds():
    with tracer.phase("a"):
        time.sleep(0.02)
        with tracer.phase("b"):
            time.sleep(0.03)
    with tracer.phase("b"):
        pass
    own, n = by_phase("veles_setup_seconds_total"), \
        by_phase("veles_setup_phases_total")
    assert n == {"a": 1.0, "b": 2.0}
    assert 0.03 <= own["b"] < 0.045 and 0.02 <= own["a"] < 0.03
    # own seconds add up to the time the phases covered; the ring's span
    # keeps the whole duration
    a = [e for e in tracer.setup_ring().events() if e[0] == "a"][0]
    assert a[3] / 1e6 == pytest.approx(own["a"] + own["b"], abs=1e-3)


def test_in_phase_and_first_call_open_the_phase_once():
    calls = []

    @tracer.in_phase("setup.build_step")
    def build(x):
        calls.append(tracer.current_phase())
        return x + 1

    class Holder:
        fn = None

    h = Holder()
    h.fn = tracer.FirstCall(build, lambda f: setattr(h, "fn", f))
    assert h.fn.__name__ == "build"             # everything else is fn's
    assert (h.fn(1), h.fn(2)) == (2, 3) and h.fn is build
    assert calls == ["setup.build_step"] * 2
    assert by_phase("veles_setup_phases_total") == {
        "setup.first_dispatch": 1.0, "setup.build_step": 2.0}
    first = [e for e in tracer.setup_ring().events()
             if e[0] == "setup.build_step"][0]
    assert first[6] == {"cause": "setup.first_dispatch"}


def test_the_hot_span_is_still_the_shared_noop():
    with tracer.phase("setup.initialize"):
        assert tracer.span("train.dispatch", "step", 1) is tracer._OFF
    assert tracer.span("train.dispatch", "step", 2) is tracer._OFF


def test_the_process_age_comes_from_the_kernels_record():
    age = tracer.process_age_s()
    assert age is not None and 0.0 <= age < 24 * 3600
    tracer.mark_import_age()
    assert metrics.family_values(
        "veles_process_age_at_import_seconds")[()] == pytest.approx(age,
                                                                    abs=1.0)


# -- jax's stages -----------------------------------------------------------------

def test_uncovered_is_the_union():
    from collections import deque
    covered = deque()
    take = compile_stages.uncovered
    assert take(covered, 1.0, 2.0) == 1.0               # inner, first
    assert take(covered, 3.0, 4.0) == 1.0               # its sibling
    assert take(covered, 0.0, 5.0) == 3.0               # the outer: rest
    assert list(covered) == [(0.0, 5.0)]
    assert take(covered, 6.0, 7.0) == 1.0               # the next program
    assert take(covered, 4.5, 6.5) == pytest.approx(1.0)    # straddles
    assert list(covered) == [(0.0, 7.0)]


def test_two_nested_jits_traced_once_count_as_the_union():
    @jax.jit
    def inner(x):
        for _ in range(40):
            x = jnp.sin(x) * 1.5 + jnp.cos(x)
        return x

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2.0) + 1.0

    spans = []

    def hear(event, start, end, **kw):
        if event.endswith("jaxpr_trace_duration"):
            spans.append((kw.get("fun_name"), start, end))

    jax.monitoring.register_event_time_span_listener(hear)
    x = jnp.ones((4, 7), jnp.float32)
    with tracer.phase("setup.first_dispatch"):
        outer(x).block_until_ready()
    took = {n: e - s for n, s, e in spans if n in ("inner", "outer")}
    assert took["inner"] > 0 and took["outer"] > took["inner"]
    traced = seconds(stage="trace", during="setup.first_dispatch")
    # a plain sum would count the inner trace twice
    assert took["outer"] <= traced + 1e-9
    assert traced < took["outer"] + took["inner"]
    programs = metrics.family_values("veles_compile_programs_total")
    assert programs[("setup.first_dispatch",)] >= 1
    # a second call traces nothing and compiles nothing
    with tracer.phase("setup.first_dispatch"):
        outer(x).block_until_ready()
    assert seconds(stage="trace", during="setup.first_dispatch") == traced
    assert metrics.family_values(
        "veles_compile_programs_total") == programs


def test_a_compile_outside_every_phase_lands_under_none():
    ring = tracer.install()

    @jax.jit
    def alone(x):
        for _ in range(60):
            x = jnp.tanh(x) @ x
        return x

    alone(jnp.ones((5, 5))).block_until_ready()
    assert seconds(stage="backend", during="none") > 0
    assert seconds(stage="trace", during="none") > 0
    assert all(k[1] == "none" for k in metrics.family_values(
        "veles_compile_seconds_total"))
    # no phase caused it: its spans are the installed ring's, by name
    spans = {(e[0], e[6]["fun_name"]) for e in ring.events()
             if e[1] == "compile"}
    assert ("compile.backend", "jit(alone)") in spans
    assert not [e for e in tracer.setup_ring().events()
                if e[1] == "compile"]


def test_the_persistent_cache_counts_a_miss_then_a_hit(tmp_path):
    keep = {n: getattr(jax.config, n) for n in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    from jax._src import compilation_cache
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()

        def program(x):
            return jnp.cumsum(x * 3.25) - 0.125

        ones = jnp.ones(11)     # (a program of its own, before the phase)
        metrics.reset_default_registry()
        with tracer.phase("setup.first_dispatch"):
            jax.jit(program)(ones).block_until_ready()
        cache = metrics.family_values("veles_compile_cache_total")
        assert cache == {("miss", "setup.first_dispatch"): 1.0}
        jax.clear_caches()
        with tracer.phase("setup.init_state"):
            jax.jit(program)(ones).block_until_ready()
        cache = metrics.family_values("veles_compile_cache_total")
        assert cache == {("miss", "setup.first_dispatch"): 1.0,
                         ("hit", "setup.init_state"): 1.0}
        read = metrics.family_values(
            "veles_compile_cache_read_seconds_total")
        assert list(read) == [("setup.init_state",)]
        assert read[("setup.init_state",)] > 0
        # the set-up ring names the program that missed and the one read
        results = [(e[6]["cache"], e[6]["during"])
                   for e in tracer.setup_ring().events()
                   if e[0] == "compile.backend"
                   and e[6]["fun_name"] == "jit(program)"]
        assert results in ([("miss", "setup.first_dispatch"),
                            ("hit", "setup.init_state")],
                           # (a stage under a millisecond is not drawn)
                           [("miss", "setup.first_dispatch")])
    finally:
        for n, v in keep.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


# -- the producers ------------------------------------------------------------------

def test_a_tiny_workflow_shows_every_phase_and_one_first_dispatch(tmp_path):
    ring = tracer.install()
    wf = make_workflow()
    wf.initialize(device=None)
    step = wf.build_fused_step()
    state = step.init_state()
    x = np.zeros((16, 6), np.float32)
    y = np.zeros(16, np.int32)
    for _ in range(2):
        state, (loss, _n_err) = step.train(state, x, y)
    assert np.isfinite(float(loss))
    n = by_phase("veles_setup_phases_total")
    # build_step has two producers: build_fused_step and the step's _build
    assert n == {"setup.initialize": 1.0, "setup.loader": 1.0,
                 "setup.build_step": 2.0, "setup.init_state": 1.0,
                 "setup.first_dispatch": 1.0}
    own = by_phase("veles_setup_seconds_total")
    assert all(own[p] > 0 for p in n)
    # the loader's initialize ran inside the workflow's
    causes = {e[0]: e[6]["cause"] for e in tracer.setup_ring().events()
              if e[1] == "setup"}
    assert causes["setup.loader"] == "setup.initialize"
    assert causes["setup.first_dispatch"] == "none"
    # the step's trace, lowering and compile lie under its first dispatch
    for stage in ("trace", "lower", "backend"):
        assert 0 < seconds(stage=stage, during="setup.first_dispatch") \
            <= own["setup.first_dispatch"]
    # train()'s later calls run the jitted function itself
    assert not isinstance(step._train_fn, tracer.FirstCall)
    assert isinstance(step._eval_fn, tracer.FirstCall)    # never called
    step.evaluate(state, x, y)
    assert by_phase("veles_setup_phases_total")[
        "setup.first_dispatch"] == 2.0

    # the --trace PATH export: set-up to the left of the first dispatch
    doc = json.load(open(ring.export(str(tmp_path / "t.json"))))
    events = doc["traceEvents"]
    first = min(e["ts"] for e in events if e["name"] == "train.dispatch")
    setup = [e for e in events if e.get("cat") == "setup"]
    assert {e["name"] for e in setup} == set(n)
    dispatch = [e for e in setup if e["name"] == "setup.first_dispatch"]
    assert all(e["ts"] + e["dur"] <= first for e in setup
               if e["name"] != "setup.first_dispatch")
    # (the first dispatch's phase lies inside the first train.dispatch)
    assert first <= dispatch[0]["ts"]
    step_compile = [e for e in events if e["name"] == "compile.backend"
                    and e["args"]["fun_name"] == "jit(train_step)"]
    assert step_compile[0]["args"]["during"] == "setup.first_dispatch"
    assert dispatch[0]["ts"] <= step_compile[0]["ts"] + 2e3     # 2 ms
    assert doc["otherData"]["setup_dropped"] == 0


def test_a_step_that_was_released_compiles_under_a_phase_again():
    wf = make_workflow()
    wf.initialize(device=None)
    step = wf.build_fused_step()
    state = step.init_state()
    x, y = np.zeros((16, 6), np.float32), np.zeros(16, np.int32)
    state, _ = step.train(state, x, y)
    step.release()
    state, _ = step.train(state, x, y)
    assert by_phase("veles_setup_phases_total")[
        "setup.first_dispatch"] == 2.0
    assert metrics.family_values("veles_compile_programs_total").get(
        ("none",), 0.0) == 0.0
