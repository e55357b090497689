"""Time per step inside collective operations on device 0 during which no
other operation runs there. Nothing to read on one chip."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["counters"]["chips"] < 2:
        return None
    return 1e3 * t["collective_exposed_s_per_step"]
