"""Workflow: a container of units that self-schedules a pulse-driven graph.

Parity: reference `veles/workflow.py` (`Workflow`, `StartPoint`, `EndPoint`,
`Repeater`) — `initialize()` walks all units (device injection, allocation,
retrying units whose data links are not ready yet); `run()` fires the start
point and pumps pulses until the end point runs or `stop()` is called; a
per-unit accumulated run-time table is reported at the end (the reference's
built-in profiler).

Scheduling note (TPU-first): the reference used a thread pool because OpenCL
kernel enqueues block; jax dispatch is asynchronous already, so a
single-threaded event loop is both sufficient and faster (no GIL churn). The
loop is deterministic: units fire in pulse-arrival order.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Optional

from veles_tpu.telemetry import tracer as _tracer
from veles_tpu.units import Container, TrivialUnit, Unit


class StartPoint(TrivialUnit):
    pass


class EndPoint(TrivialUnit):
    """Running the end point stops the owning workflow's pump."""

    def run(self) -> None:
        self.workflow.on_end_point()


class Repeater(TrivialUnit):
    """OR-gate merge unit used to close training loops (parity: reference
    `Repeater` in `veles/workflow.py`)."""

    or_gate = True


class Workflow(Container):
    """A Unit that contains units and runs them as a pulse-driven graph."""

    def __init__(self, workflow: Optional[Unit] = None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.start_point = StartPoint(self)
        self.end_point = EndPoint(self)
        self.stopped = False
        self.device = None
        self._queue: deque = deque()
        self.run_total_time = 0.0

    # -- lifecycle -----------------------------------------------------------

    @_tracer.in_phase("setup.initialize")
    def initialize(self, device=None, **kwargs: Any) -> None:
        """Initialize all units. Units may return False to be retried after
        the others (mirrors the reference's deferred-initialization loop).

        `verify="error"|"warn"|"off"` (default "warn") runs the static
        graph verifier (analysis/graph.py) over the constructed graph
        first: "warn" logs every finding and continues, "error"
        additionally raises WorkflowVerifyError on error-severity
        findings, "off" skips the pass."""
        verify = kwargs.pop("verify", "warn")
        if verify not in ("off", "warn", "error"):
            raise ValueError(f"verify={verify!r}: expected "
                             "'error', 'warn' or 'off'")
        if verify != "off":
            from veles_tpu.analysis.graph import (WorkflowVerifyError,
                                                  verify_workflow)
            findings = verify_workflow(self)
            errs = []
            for f in findings:
                if f.severity == "error":
                    errs.append(f)
                    self.error("verify: %s", f.format())
                else:
                    self.warning("verify: %s", f.format())
            if errs and verify == "error":
                raise WorkflowVerifyError(errs)
        self.device = device
        super().initialize(**kwargs)
        pending = list(self.units)
        while pending:
            retry = []
            for unit in pending:
                if unit.initialize(device=device, **kwargs) is False:
                    retry.append(unit)
                else:
                    unit._initialized = True
            if len(retry) == len(pending):
                names = [u.name for u in retry]
                raise RuntimeError(
                    f"workflow initialization deadlock; unresolved: {names}")
            pending = retry

    def schedule(self, unit: Unit) -> None:
        self._queue.append(unit)

    def run(self) -> None:
        """Pump pulses from start_point until end_point or stop()."""
        self.stopped = False
        start = time.perf_counter()
        self._queue.clear()
        for unit in self.units:  # clear stale pulses from any previous run
            for src in unit._links_from:
                unit._links_from[src] = False
        self.schedule(self.start_point)
        try:
            while self._queue and not self.stopped:
                self._queue.popleft().fire()
        finally:
            # teardown must run even when a unit raised (Ctrl-C mid-run
            # used to leave prefetch/plotter threads alive): every unit's
            # stop() is invoked, failures logged, none masking the
            # original exception
            self.run_total_time += time.perf_counter() - start
            self._stop_units()

    def on_end_point(self) -> None:
        self.stopped = True

    def _stop_units(self) -> None:
        for unit in self.units:
            if unit is self:
                continue
            try:
                unit.stop()
            except Exception as e:   # noqa: BLE001 — teardown best-effort
                self.warning("stop() of %s failed: %s", unit.name, e)

    def stop(self) -> None:
        """Stop the pump loop AND release unit-owned background resources
        (prefetch pools, plotter renderer threads) — callable from any
        thread and idempotent."""
        self.stopped = True
        self._stop_units()

    # -- reporting -----------------------------------------------------------

    def print_stats(self) -> str:
        """Per-unit accumulated wall-time table (the reference's end-of-run
        profiler); returns the formatted table and logs it."""
        total = self.run_total_time
        rows = sorted((u for u in self.units if u.run_count),
                      key=lambda u: -u.run_time)
        lines = [f"{'unit':<32} {'runs':>8} {'time':>10} {'%':>6}"]
        for u in rows:
            pct = 100.0 * u.run_time / total if total > 0 else 0.0
            lines.append(
                f"{u.name:<32} {u.run_count:>8} {u.run_time:>9.3f}s {pct:>5.1f}%")
        lines.append(f"{'TOTAL':<32} {'':>8} {total:>9.3f}s")
        table = "\n".join(lines)
        self.info("run-time stats:\n%s", table)
        return table
