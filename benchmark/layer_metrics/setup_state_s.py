"""Seconds of the phases `setup.build_step`
(`StandardWorkflow.build_fused_step`, `FusedTrainStep._build`) and
`setup.init_state` (`FusedTrainStep.init_state`: the parameters and the
optimizer's state put on the device) that are theirs alone: own seconds
less the compile stages counted under them (`setup_counters.phase_seconds`).
None where the program records no phases."""

from benchmark import setup_counters as S


def read(ctx):
    return S.phase_seconds("setup.build_step", "setup.init_state")
