"""Seconds reading and deserialising hits of the persistent compile cache
under the program's set-up phases
(`veles_compile_cache_read_seconds_total`); 0 on a cold run. None where the
program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.under_phases(S.CACHE_READ_SECONDS)
