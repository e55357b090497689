"""Device time of operations that carry no program scope, over the
step's device time: the instrument's own check. It rises when work
enters the step outside every `jax.named_scope`."""

from benchmark import scope_reduce


def read(ctx):
    r = scope_reduce.of_run(ctx)
    if r is None:
        return None
    return 100.0 * r["phase_s"]["unscoped"] / r["step_device_s"]
