"""Seconds of the phases `setup.initialize` (`Workflow.initialize`: the
units' granular host buffers) and `setup.loader` (the loader's `initialize`
with its preload, `DeviceFeed.for_step`) that are theirs alone: own seconds
less the compile stages counted under them (`setup_counters.phase_seconds`).
None where the program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.phase_seconds("setup.initialize", "setup.loader")
