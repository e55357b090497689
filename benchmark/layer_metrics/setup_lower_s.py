"""Seconds lowering jaxprs to StableHLO modules under the program's set-up
phases: `veles_compile_seconds_total{stage="lower"}` over every `during`
that is a phase. None where the program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.under_phases(S.COMPILE_SECONDS, "lower")
