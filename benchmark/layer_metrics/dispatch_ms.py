"""Mean duration of the program's own `train.dispatch` spans
(`FusedTrainStep.train`, through `telemetry.tracer`) in the trace's host
plane, inside the traced window: the host's side of one step. Nothing to
read where the capture keeps no host events (`host_tracer_level: 0`)."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    if r is None or not r["dispatch_s"]:
        return None
    return 1e3 * sum(r["dispatch_s"]) / len(r["dispatch_s"])
