"""Seconds from the start of the process to the end of the program's own
start: the process's age when the package was first imported (the gauge
`veles_process_age_at_import_seconds`: interpreter start, `run.py`'s own
imports, jax's import and the TPU client's start, which `main()` causes
before it imports the program) + the phases `setup.import` and
`setup.backend` (`veles_tpu/__init__.py`, `backends.py`; less the compile
stages counted under them, as every phase metric:
`setup_counters.phase_seconds`). None where the program records no
phases."""

from benchmark import setup_counters as S


def read(ctx):
    phases = S.phase_seconds("setup.import", "setup.backend")
    if phases is None:
        return None
    age = S.family(S.AGE_AT_IMPORT) or {}
    return age.get((), 0.0) + phases
