"""Seconds XLA's backend spent compiling during set-up (jax.monitoring
`backend_compile_duration` events, counted by the driver's CompileClock)."""


def read(ctx):
    return ctx["counters"].get("compile_s")
