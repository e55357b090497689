"""Unified telemetry plane (docs/OBSERVABILITY.md).

Three coordinated layers, built once so every direction that needs
per-step cost data (quantized collectives' per-collective byte/time
attribution, the kernel search's priority order) consumes the same
producers:

- `telemetry.tracer` — step-timeline tracing: a low-overhead ring-buffer
  span recorder over the driver loop (feed pops, async dispatch, the
  in-flight device window, Decision/snapshot bookkeeping, cluster
  beats), exported as Chrome-trace/Perfetto-loadable ``trace.json``
  (CLI ``--trace PATH``); plus ``--profile-window N:M`` /
  ``POST /profile`` on-chip capture windows bracketing steps with
  ``jax.profiler``. Every span is also a ``TraceAnnotation`` while any
  profiler session is open, so a capture holds the program's spans on
  the device trace's own timeline. Set-up, from the package's import to
  a step's first dispatch, is recorded always, as ``tracer.phase`` spans
  in a small ring of its own and ``veles_setup_*`` counters.
- `telemetry.compile_stages` — jax's trace, lower and backend stages and
  the persistent cache's hits and misses, from ``jax.monitoring``, as
  ``veles_compile_*`` counters under the phase that caused them (imports
  jax only when `listen()` is called).
- `telemetry.metrics` — ONE metrics registry (counters / gauges /
  histograms) behind a Prometheus text-format ``GET /metrics`` on
  web_status, the cluster coordinator (fleet-aggregated from member
  heartbeats) and serving, with a JSONL append sink mirroring every
  flush for offline analysis next to bench records.
- wiring — the driver loop, DeviceFeed, supervisor heartbeats/exit
  reports, bench children and chaos scenarios all route through the
  one registry, so "the same number" has one producer.

Import-light on purpose: stdlib only at import time (the resilience
supervisor and cluster member — jax-free parents — use the registry
too); jax is touched only inside profile windows and by
`compile_stages.listen()`.
"""

from veles_tpu.telemetry import metrics, tracer  # noqa: F401

__all__ = ["metrics", "tracer"]
