"""`step_ms_p95` of a fed cell: the 95th percentile, over every run of
`span_steps` consecutive steps in the window, of the mean interval between
step completions. Its runs spread by 3 to 5 %, too wide for a bound, so it
stands here and not among the end-to-end metrics."""


def read(ctx):
    return ctx["counters"].get("step_ms_p95")
