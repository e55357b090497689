"""What the program itself counted of set-up, for the seven `setup_*`
per-layer metrics (`layer_metrics/setup_*.py`): the `veles_setup_*` and
`veles_compile_*` families of its default metrics registry, read in the
process as `scope_reduce.registry_ratio` reads the feed's.

`veles_setup_seconds_total{phase}` holds a phase's own seconds (its
duration less the phases it caused), `veles_compile_*{..., during}` jax's
trace, lower and backend stages and the persistent cache's reads and
misses under the phase that was open when they ended. Every phase metric
is `phase_seconds`: own seconds less the stages counted under the phase,
which `setup_trace_s` and `setup_lower_s` hold (the backend's, and
`setup.first_dispatch`'s own, are the accepted `compile_s`'s and the
device's, and in none of the seven). `during="none"` is
what no program phase caused (the harness's own jits, the reference after
the window) and is left out of every sum here. A program from before the
phases has no `veles_setup_seconds_total`: every reader then returns None
and the result line leaves the metric out. Where the program has phases, a
family nothing has written yet reads 0 (no cache hit on a cold run).
"""

from typing import Dict, Optional, Tuple

PHASE_SECONDS = "veles_setup_seconds_total"
AGE_AT_IMPORT = "veles_process_age_at_import_seconds"
COMPILE_SECONDS = "veles_compile_seconds_total"     # (stage, during)
CACHE_EVENTS = "veles_compile_cache_total"          # (result, during)
CACHE_READ_SECONDS = "veles_compile_cache_read_seconds_total"   # (during,)


def family(name: str) -> Optional[Dict[Tuple[str, ...], float]]:
    """{label values: value} of one family, or None where the program has
    none of that name."""
    from veles_tpu.telemetry import metrics
    return metrics.family_values(name)


def phase_seconds(*phases: str) -> Optional[float]:
    """Seconds of the named phases that are theirs alone: their own
    seconds, summed, less every compile stage counted under them
    (`setup_trace_s`, `setup_lower_s` and the backend hold those), so that
    no second of set-up is in two of the seven metrics. None where the
    program records no phases."""
    by_phase = family(PHASE_SECONDS)
    if by_phase is None:
        return None
    own = sum(by_phase.get((p,), 0.0) for p in phases)
    stages = sum(v for k, v in (family(COMPILE_SECONDS) or {}).items()
                 if k[-1] in phases)
    return max(0.0, own - stages)


def under_phases(name: str, *first: str) -> Optional[float]:
    """Sum of a `during`-labelled family over the children whose labels
    begin with `first` and whose `during` (the last label) is a program
    phase; None where the program records no phases."""
    if family(PHASE_SECONDS) is None:
        return None
    return sum(v for k, v in (family(name) or {}).items()
               if k[:len(first)] == first and k[-1] != "none")
