"""Programs compiled and written to the persistent compile cache under the
program's set-up phases (`veles_compile_cache_total{result="miss"}`): 0 on
a warm run; where it is not, the set-up ring's `compile.backend` spans with
`cache: miss` name them. None where the program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.under_phases(S.CACHE_EVENTS, "miss")
