"""Device time of one step under a sub-scope of a unit, and the expert
layers' counters: what the `xing4_lm` family's per-layer metrics read.

`scope_reduce.py` sorts a trace's operations by unit and by phase; the
blocks of this family name their parts beneath the unit's scope
(`L02.hc_block/moe/experts`, `veles_tpu/znicz/lm.py`), forward, backward
and recomputed alike, so a part's time is the union of the operations
whose scope path holds that component, inside the traced window, per
step. Where no operation carries it (a program without such scopes, a run
that was not traced) there is nothing to read.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark import scope_reduce
from benchmark import trace_reduce as T


def component(*names: str):
    """Matches a scope path that holds one of `names` as a whole
    component, whatever a transformation wraps around the path."""
    return re.compile(r"(?<![A-Za-z0-9_.])(?:" + "|".join(
        re.escape(n) for n in names) + r")(?![A-Za-z0-9_.])")


@functools.lru_cache(maxsize=2)
def _scoped_ops(path: str):
    """(operations of device 0 inside the window with their scope paths,
    whole steps in the window), or None."""
    ev = T.events_of(path)
    if 0 not in ev["devices"]:
        return None
    ops, modules = (ev["devices"][0][k] for k in (T.OPS_LINE,
                                                  T.MODULES_LINE))
    base = T.reduce_device(ops, modules)
    if base is None:
        return None
    scopes = scope_reduce.module_scopes(path, base["step_module"])
    lo, hi = base["window"]
    rows = [(a, b, _placed(scope_reduce.scope_of_event(n, scopes)))
            for n, a, b in ops if a >= lo and b <= hi]
    return rows, base["steps"]


def _placed(scope: str) -> str:
    """The TPU compiler rewrites a `lax.ragged_dot` into kernels of its
    own and names them `ragged-dot-none` / `ragged-dot-metadata`, without
    the path of the operation they came from: compiled for a v5e, the
    custom calls carry `metadata={op_name="ragged-dot-none"}` whatever
    scope the product was traced under, so no wrapper keeps the path
    (chip run of PR 32; the `unscoped` phase of `scope_reduce.py` holds
    them). This reader places them BY NAME: the only grouped products of
    this family are the held experts', under `moe/experts`."""
    return "moe/experts/" + scope if scope.startswith("ragged-dot") \
        else scope


def scope_seconds(ctx: Dict[str, Any], pattern) -> Optional[float]:
    """Seconds a step of the traced run spent in operations whose scope
    path matches `pattern`; None where there is nothing to read."""
    if ctx.get("trace") is None:
        return None
    from veles_tpu.caches import cache_path
    trace_dir = os.path.join(
        cache_path("benchmark", ctx["cell"]["name"]), "trace")
    try:
        found = _scoped_ops(T.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    if found is None:
        return None
    rows, steps = found
    inside = [(a, b) for a, b, s in rows if pattern.search(s)]
    if not inside:
        return None
    return T.total(T.union(inside)) / steps


def moe_counters() -> Optional[Dict[str, Dict[str, float]]]:
    """{layer: {steps, slots, held, fullest}} of the window, from the
    program's `veles_moe_*` counters; None where the program has none (a
    program from before them, or without an expert layer)."""
    try:
        from veles_tpu.telemetry import metrics
        values = metrics.family_values
    except (ImportError, AttributeError):
        return None
    out: Dict[str, Dict[str, float]] = {}
    for key, name in (("steps", "veles_moe_steps_total"),
                      ("slots", "veles_moe_slots_total"),
                      ("held", "veles_moe_held_slots_total"),
                      ("fullest", "veles_moe_fullest_held_slots_total")):
        family = values(name)
        if not family:
            return None
        for (layer,), v in family.items():
            out.setdefault(layer, {})[key] = v
    return out


def held_experts_of(ctx: Dict[str, Any]) -> Tuple[int, int]:
    """(experts held here, experts the router chooses among)."""
    cfg = ctx["cell"]["config_data"]
    held = cfg["n_routed_experts"]
    return held, cfg.get("published", {}).get("n_routed_experts", held)
