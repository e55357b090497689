"""Device time of one step: the union of the intervals in which an
operation ran on device 0 during the traced steps, over their number."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 1e3 * t["step_device_s"]
