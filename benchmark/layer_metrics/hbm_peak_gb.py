"""`memory_stats()["peak_bytes_in_use"]` after the window, the highest
over the cell's devices, in GB (1e9 bytes)."""


def read(ctx):
    return ctx["counters"]["peak_bytes"] / 1e9
