"""jax's compile stages, counted under the set-up phase that caused them.

jax reports each stage of getting a program onto the device through
`jax.monitoring`: tracing a function to a jaxpr, lowering the jaxpr to a
StableHLO module, and the backend's compile (on a hit of the persistent
cache: the read and the deserialisation), each as a time span in unix
seconds with the function's name, on the thread that did the work, at the
stage's END; and the persistent cache's hits, misses and read time as
plain events inside the backend stage. `listen()` (called once a process by
`caches.enable_compilation_cache()`, which every entry point calls)
registers for them and writes the `veles_compile_*` families
(`metrics.compile_handles`), labelled `during`: the innermost
`tracer.phase` open on that thread, or ``none`` for everything no program
phase caused: a caller's own jits and, for an operator, a program that
compiled in the middle of a run.

A jitted function traced inside another's trace reports its own span
before the outer one does, so durations nest and a plain sum counts twice.
Seconds here are the UNION of a stage's spans on a thread: each span adds
the part of itself that no span recorded before it covers.

Each stage is also a span ``compile.trace`` / ``compile.lower`` /
``compile.backend`` carrying `fun_name`: in the set-up ring where a phase
caused it, else in the installed ring (``--trace PATH``), if any. A stage
under a millisecond (a `jnp` function traced inside a step's trace: a step
has hundreds) is counted and not drawn.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Deque, Dict, Tuple

from veles_tpu.telemetry import metrics, tracer

#: jax.monitoring's event -> the stage's name here
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
#: covered intervals kept a stage and thread. Spans arrive in the order of
#: their ends, so a new one can only cover the newest of them, which it
#: then replaces: the list holds what no later span has covered yet, and a
#: long run's old entries fall off its far end.
_MAX_COVERED = 4096
#: the shortest stage a ring holds, seconds
_MIN_SPAN_S = 1e-3

_LOCAL = threading.local()
_LISTENING = False
_LOCK = threading.Lock()
#: the registry the children below were bound in, and the children by
#: (family, label values): a step's trace reports thousands of `jnp`
#: functions, so an event pays a dictionary's lookup, not a registry's
_BOUND_IN = None
_BOUND: Dict[Tuple[str, ...], Any] = {}


def _child(family: str, **labels: str):
    """The bound child of one `metrics.compile_handles` family in the
    default registry of NOW (a test may have replaced it)."""
    global _BOUND_IN
    reg = metrics.default_registry()
    if reg is not _BOUND_IN:
        _BOUND.clear()
        _BOUND_IN = reg
    key = (family, *labels.values())
    child = _BOUND.get(key)
    if child is None:
        child = _BOUND[key] = getattr(
            metrics.compile_handles(reg), family).labels(**labels)
    return child


def uncovered(covered: Deque[Tuple[float, float]], start: float,
              end: float) -> float:
    """Seconds of [start, end] that no interval of `covered` holds, and
    `covered` with the span merged in. `covered` is disjoint and in the
    order of arrival, which is the order of the ends."""
    own, lo, hi = max(0.0, end - start), start, end
    while covered and covered[-1][1] > start:
        s, e = covered.pop()
        own -= max(0.0, min(e, end) - max(s, start))
        lo, hi = min(lo, s), max(hi, e)
    covered.append((lo, hi))
    return max(0.0, own)


def _on_span(event: str, start: float, end: float, **kw: Any) -> None:
    stage = STAGES.get(event)
    if stage is None:
        return
    by_stage: Dict[str, Deque] = _LOCAL.__dict__.setdefault("covered", {})
    covered = by_stage.get(stage)
    if covered is None:
        covered = by_stage[stage] = deque(maxlen=_MAX_COVERED)
    phase = tracer.current_phase()
    during = phase or "none"
    _child("seconds", stage=stage, during=during).inc(
        uncovered(covered, start, end))
    cache = None
    if stage == "backend":
        _child("programs", during=during).inc()
        # (a program under the cache's thresholds reports neither)
        cache = _LOCAL.__dict__.pop("cache", "none")
    ring = tracer.setup_ring() if phase else tracer.active()
    if ring is not None and end - start >= _MIN_SPAN_S:
        args = {"fun_name": str(kw.get("fun_name", "")), "during": during}
        if cache is not None:
            args["cache"] = cache
        ring.add_unix_span("compile." + stage, "compile", start, end, args)


def _on_event(event: str, **_kw: Any) -> None:
    result = _CACHE_RESULTS.get(event)
    if result is None:
        return
    # inside the backend stage, before its span arrives: the span says
    # which program it was
    _LOCAL.cache = result
    _child("cache", result=result,
           during=tracer.current_phase() or "none").inc()


def _on_duration(event: str, secs: float, **_kw: Any) -> None:
    if event == _CACHE_READ:
        _child("cache_read_s",
               during=tracer.current_phase() or "none").inc(max(0.0, secs))


def listen() -> None:
    """Register with jax.monitoring, once a process."""
    global _LISTENING
    with _LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring as mon
    mon.register_event_time_span_listener(_on_span)
    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)
