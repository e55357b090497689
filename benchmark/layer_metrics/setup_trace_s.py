"""Seconds Python spent tracing functions to jaxprs under the program's
set-up phases: `veles_compile_seconds_total{stage="trace"}` over every
`during` that is a phase; the union of the spans, so a kernel jitted once
and traced inside the step's trace counts once. It grows with the number of
kernel SITES where a kernel is inlined a site (PR 33). NOT comparable with
the `.trace()` seconds `tools/trace_cost.py` prints: a run's first trace
also imports what its kernels need (`veles_tpu/ops/pallas_kernels.py` and
128 modules of `jax.experimental.pallas` behind it, which the tool imports
before its clock starts), holds the traces that lowering causes, and on the
chip machine read 2-3 times the tool's for the rest (`PERF.md` section 7).
None where the program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.under_phases(S.COMPILE_SECONDS, "trace")
