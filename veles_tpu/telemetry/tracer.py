"""Step-timeline tracing: where a step's wall-clock went, as spans.

The observability gap this closes: the PR-5 H2D-under-compute overlap
was *inferred* from counters (`loader_block_s` vs `device_sync_s`);
nothing showed WHERE inside one step the time sat. The `Tracer` records
host-side spans — feed pops, the async train dispatch, the in-flight
device window, the class-pass-boundary sync, Decision/snapshot
bookkeeping, the next batch's `device_put` — into a fixed-capacity ring
buffer and exports them as a Chrome-trace/Perfetto-loadable
``trace.json``, so the overlap becomes a picture: batch k+1's
``feed.device_put`` span visibly riding under step k's ``step`` span.

One clock with the device: `span()` (the module function every producer
in the program records through) opens, besides the ring event, a
``jax.profiler.TraceAnnotation`` whenever jax is already imported and a
profiler session is open — whoever opened it (``benchmark/run.py --trace
1``, ``--profile-window``, ``POST /profile``). The session's host plane
then holds the program's spans on the device trace's own timeline, and
an idle gap of the device can be named after the span covering it with
no alignment step. A span's `seq` (the batch's or step's sequence
number) rides the annotation as the event's ``seq`` stat and the ring
event as ``args.seq``: ``loader.produce#k -> feed.device_put#k ->
train.dispatch#k`` is one chain.

Design constraints (the hot-path contract):

- **Zero host-sync**: spans are host timestamps only
  (``time.perf_counter_ns``, one monotonic clock for the whole
  process); recording never touches a device value.
- **Off costs a call**: with no ring installed and no profiler session
  open `span()` returns one shared no-op object (a global load, one
  ``TraceMe.is_enabled()`` call; measured in docs/OBSERVABILITY.md), so
  producers write plain ``with tracer.span(...)`` blocks and a session
  opened mid-run is seen by the next span. The velint ``hot-metric``
  rule enforces pre-binding for metric records.
- **Bounded memory**: a ring buffer of `capacity` events; overflow
  overwrites the oldest and the export reports how many were dropped
  (``otherData.dropped``) instead of growing without bound on a long
  run.
- **Thread-safe**: one lock around the ring append; begin/end tokens
  carry their own timestamps so the lock is held for the append only.

Set-up (`phase()`): what happens once, between the package's import and
a step's first dispatch, is recorded ALWAYS, into one small ring this
module owns (`setup_ring()`; a process opens some dozens of phases) and
into ``veles_setup_*`` counters of the default registry. A phase knows its
cause, the innermost phase open on its thread when it began, and
`telemetry/compile_stages.py` counts jax's trace, lower and compile
stages under the phase that is open when they end. Every ring shares ONE
epoch pair (`perf_counter_ns`, `time_ns`, read when this module is
imported), so an export of the ``--trace`` ring holds the set-up ring's
events on its own timeline, to the left of the first ``train.dispatch``.

Profile windows (`ProfileController`): ``--profile-window N:M``
brackets driver steps N..M (inclusive) with ``jax.profiler``
start/stop — the on-chip capture path — and ``POST /profile`` on the
web-status control plane arms a window on a LIVE run. The driver calls
``controller.on_step(k)`` once per step; the disarmed path is a single
attribute check.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from veles_tpu.telemetry import metrics as _metrics

#: default ring capacity (events); env-overridable for long captures
_DEFAULT_CAPACITY = int(os.environ.get("VELES_TRACE_CAPACITY",
                                       str(1 << 16)))
#: the set-up ring's capacity: a process opens some dozens of phases and
#: jax reports a few hundred compile stages under them
_SETUP_CAPACITY = 4096
#: the ONE epoch pair of every ring of this process, (perf_counter_ns,
#: time_ns) read together: a span's ts is relative to the first, and a
#: span that arrives as unix seconds (jax's compile stages) is placed by
#: the second. The only place the two clocks meet.
_EPOCH = (time.perf_counter_ns(), time.time_ns())


class Tracer:
    """Fixed-capacity span recorder with Chrome-trace export."""

    def __init__(self, capacity: int = 0) -> None:
        self.capacity = max(256, int(capacity or _DEFAULT_CAPACITY))
        #: ring slots: (name, cat, ts_us, dur_us, tid, ph, seq); `seq` is
        #: a span's sequence number, or a dict of args (set-up events)
        self._ring: List[Optional[Tuple]] = [None] * self.capacity
        self._n = 0                      # total events ever recorded
        self._lock = threading.Lock()
        #: perf_counter_ns every ts is relative to, and its wall-clock
        #: twin (``time.time_ns()``), for correlating with logs, with a
        #: profiler capture and with jax's own unix stamps
        self._epoch_ns, self._epoch_unix_ns = _EPOCH
        self._pid = os.getpid()

    # -- recording ------------------------------------------------------------

    def begin(self, name: str, cat: str = "host",
              seq: Optional[int] = None) -> Tuple:
        """Open a span; returns the token `end()` closes. No lock —
        the token carries its own start timestamp."""
        return (name, cat, time.perf_counter_ns(),
                threading.get_ident(), seq)

    def end(self, token: Tuple) -> None:
        """Close a span opened by `begin()` and append it."""
        name, cat, t0, tid, seq = token
        t1 = time.perf_counter_ns()
        self._append((name, cat, (t0 - self._epoch_ns) / 1e3,
                      (t1 - t0) / 1e3, tid, "X", seq))

    def add_span(self, name: str, cat: str,
                 t0_s: float, t1_s: float) -> None:
        """Record a span from two `time.perf_counter()` readings the
        caller already took (the driver's boundary-sync timer) —
        perf_counter and perf_counter_ns share one clock, so no second
        timestamp is paid. Ring only: an annotation cannot be
        backdated."""
        self._append((name, cat, (t0_s * 1e9 - self._epoch_ns) / 1e3,
                      max(0.0, (t1_s - t0_s) * 1e6),
                      threading.get_ident(), "X", None))

    def add_unix_span(self, name: str, cat: str, start_unix_s: float,
                      end_unix_s: float, args: Dict[str, Any]) -> None:
        """Record a span the caller knows by its unix start and end
        (`time.time()`, as jax.monitoring reports a compile stage): placed
        on the ring's timeline through the epoch pair, so it is good to
        what the wall clock drifted from the monotonic one since the
        module was imported (docs/OBSERVABILITY.md)."""
        self._append((name, cat,
                      (start_unix_s * 1e9 - self._epoch_unix_ns) / 1e3,
                      max(0.0, (end_unix_s - start_unix_s) * 1e6),
                      threading.get_ident(), "X", args))

    def instant(self, name: str, cat: str = "host") -> None:
        """A zero-duration marker (Chrome-trace "i" event)."""
        self._append((name, cat,
                      (time.perf_counter_ns() - self._epoch_ns) / 1e3,
                      0.0, threading.get_ident(), "i", None))

    def span(self, name: str, cat: str = "host",
             seq: Optional[int] = None) -> "_Span":
        """A span in THIS ring (and the profiler session, if one is
        open)."""
        return _Span(self, name, cat, seq, _annotation())

    def _append(self, ev: Tuple) -> None:
        with self._lock:
            self._ring[self._n % self.capacity] = ev
            self._n += 1

    # -- export ---------------------------------------------------------------

    @property
    def dropped(self) -> int:
        return max(0, self._n - self.capacity)

    def events(self) -> List[Tuple]:
        """Recorded events, oldest first (ring unrolled)."""
        with self._lock:
            n = self._n
            if n <= self.capacity:
                return [e for e in self._ring[:n] if e is not None]
            head = n % self.capacity
            return [e for e in self._ring[head:] + self._ring[:head]
                    if e is not None]

    def trace_events(self) -> List[Dict[str, Any]]:
        """Chrome-trace event dicts (the `traceEvents` array)."""
        return self._chrome(self.events())

    def _chrome(self, events: List[Tuple]) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        tids = set()
        for name, cat, ts, dur, tid, ph, seq in events:
            tids.add(tid)
            ev: Dict[str, Any] = {"name": name, "cat": cat, "ph": ph,
                                  "ts": round(ts, 3),
                                  "pid": self._pid, "tid": tid}
            if ph == "X":
                ev["dur"] = round(dur, 3)
            else:
                ev["s"] = "t"           # instant scope: thread
            if seq is not None:
                ev["args"] = seq if isinstance(seq, dict) else {"seq": seq}
            out.append(ev)
        # thread-name metadata so Perfetto labels the tracks
        names = {t.ident: t.name for t in threading.enumerate()}
        for tid in sorted(tids):
            out.append({"name": "thread_name", "ph": "M",
                        "pid": self._pid, "tid": tid,
                        "args": {"name": names.get(tid, f"tid-{tid}")}})
        return out

    def export(self, path: str) -> str:
        """Write the Perfetto/chrome://tracing-loadable JSON (atomic
        replace — a killed run leaves the previous file intact, not a
        torn one). The installed ring's file (``--trace PATH``) holds
        the set-up ring's events too: every ring has one epoch, so set-up
        lies to the left of the first ``train.dispatch``. Returns `path`."""
        doc = {
            "traceEvents": self._chrome(
                (_SETUP.events() if self is _ACTIVE else []) + self.events()),
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "veles_tpu.telemetry.tracer",
                "clock": "perf_counter_ns (us since epoch_unix)",
                "epoch_unix": round(self._epoch_unix_ns / 1e9, 6),
                "epoch_unix_ns": self._epoch_unix_ns,
                "recorded": self._n,
                "dropped": self.dropped,
                "setup_recorded": _SETUP._n,
                "setup_dropped": _SETUP.dropped,
            },
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


# -- process-global tracer (the --trace flag's target) ------------------------

_ACTIVE: Optional[Tracer] = None
#: the set-up ring: always there, small, holds `phase()`s and the compile
#: stages counted under them
_SETUP = Tracer(_SETUP_CAPACITY)


def setup_ring() -> Tracer:
    """The ring that holds this process's set-up (never None)."""
    return _SETUP


def install(capacity: int = 0) -> Tracer:
    """Install (and return) the process tracer. Idempotent: a second
    install returns the existing tracer so nested drivers share one
    timeline."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = Tracer(capacity)
    return _ACTIVE


def active() -> Optional[Tracer]:
    """The installed ring, or None (`--trace PATH` off). For exporters
    and for `add_span`/`instant`; spans go through `span()`."""
    return _ACTIVE


def uninstall() -> Optional[Tracer]:
    """Remove and return the process tracer (tests; idempotent)."""
    global _ACTIVE
    tr, _ACTIVE = _ACTIVE, None
    return tr


#: jax.profiler.TraceAnnotation, bound the first time jax is found imported
_ANNOTATION = None


def _annotation():
    """`jax.profiler.TraceAnnotation` when jax is already imported and a
    profiler session is open, else None. This module imports nothing of
    jax itself (jax-free parents record here too)."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        jax = sys.modules.get("jax")
        ann = getattr(getattr(jax, "profiler", None), "TraceAnnotation",
                      None)
        if ann is None:
            return None
        _ANNOTATION = ann
    return ann if ann.is_enabled() else None


class _Span:
    """One open span: a ring event, a profiler annotation, or both."""

    __slots__ = ("_tr", "_tok", "_ann")

    def __init__(self, tr: Optional[Tracer], name: str, cat: str,
                 seq: Optional[int], ann) -> None:
        self._tr = tr
        self._tok = (name, cat, seq)
        if ann is None:
            self._ann = None
        elif seq is None:
            self._ann = ann(name)
        else:
            self._ann = ann(name, seq=seq)

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._tr is not None:
            self._tok = self._tr.begin(*self._tok)
        return self

    def __exit__(self, *exc) -> None:
        if self._tr is not None:
            self._tr.end(self._tok)
        if self._ann is not None:
            self._ann.__exit__(*exc)


#: what `span()` returns when nothing records
_OFF = contextlib.nullcontext()


def span(name: str, cat: str = "host", seq: Optional[int] = None):
    """THE producer call: a span in the installed ring (`--trace PATH`)
    and in any open profiler session. `seq` is the batch's or step's
    sequence number. With neither on it returns a shared no-op."""
    tr, ann = _ACTIVE, _annotation()
    if tr is None and ann is None:
        return _OFF
    return _Span(tr, name, cat, seq, ann)


# -- set-up phases ------------------------------------------------------------

#: the phases open on each thread, outermost first
_OPEN = threading.local()


def current_phase() -> Optional[str]:
    """The innermost phase open on this thread, or None."""
    stack = getattr(_OPEN, "stack", None)
    return stack[-1].name if stack else None


class _Phase:
    """One open set-up phase (see `phase()`)."""

    __slots__ = ("name", "cause", "_t0", "_caused_ns", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name
        self.cause: Optional[str] = None
        self._caused_ns = 0     # the phases this one caused, summed

    def __enter__(self) -> "_Phase":
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self.cause = stack[-1].name if stack else None
        stack.append(self)
        ann = _annotation()
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _OPEN.stack
        stack.remove(self)
        ring, dur = _SETUP, t1 - self._t0
        ring._append((self.name, "setup", (self._t0 - ring._epoch_ns) / 1e3,
                      dur / 1e3, threading.get_ident(), "X",
                      {"cause": self.cause or "none"}))
        if stack:
            stack[-1]._caused_ns += dur
        # by name, at every close: a phase is rare, and a registry a test
        # has reset must not be written through a stale handle
        h = _metrics.setup_handles()
        h.seconds.labels(phase=self.name).inc(
            max(0, dur - self._caused_ns) / 1e9)
        h.phases.labels(phase=self.name).inc()


def phase(name: str) -> _Phase:
    """A span of category ``setup`` that records ALWAYS: into the set-up
    ring (name, start, end, thread and its cause: the innermost phase
    open on this thread when it began), into any open profiler session,
    and at its close into ``veles_setup_seconds_total{phase}`` (its OWN
    seconds: its duration less the phases it caused, so the phases of a
    thread add up to the time they covered) and
    ``veles_setup_phases_total{phase}``. For what happens once; the hot
    spans go through `span()` and pay nothing for this."""
    return _Phase(name)


def in_phase(name: str):
    """Decorator: every call of the function is the phase `name`."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with _Phase(name):
                return fn(*args, **kwargs)
        return call
    return wrap


class FirstCall:
    """A compiled function before its first call. That call is the phase
    `name` (it is where jax traces, lowers and compiles or reads the
    cache) and hands the function itself to `put`, which stores it where
    this object stood: every later call is the plain path. Everything but
    the call (`lower`, `clear_cache`, ...) is the function's own."""

    __slots__ = ("_fn", "_put", "_name")

    def __init__(self, fn, put, name: str = "setup.first_dispatch") -> None:
        self._fn, self._put, self._name = fn, put, name

    @classmethod
    def on(cls, owner, attr: str) -> None:
        """Put the compiled function `owner.<attr>` behind its first
        call, which stores the function itself back there."""
        setattr(owner, attr, cls(getattr(owner, attr),
                                 functools.partial(setattr, owner, attr)))

    def __call__(self, *args):
        self._put(self._fn)
        with _Phase(self._name):
            return self._fn(*args)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


def process_age_s() -> Optional[float]:
    """Seconds since this process started, from the kernel's own record
    (`/proc/self/stat`'s start time against `/proc/uptime`); None where
    there is no such record."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing bracket: state is
            # the 3rd of the line, the start time (in ticks) the 22nd
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


def mark_import_age() -> None:
    """Set ``veles_process_age_at_import_seconds``: the package's first
    line calls this, so the gauge is what came before the program:
    interpreter start, the caller's own imports, and a backend the caller
    started first."""
    age = process_age_s()
    if age is not None:
        _metrics.setup_handles().age_at_import.set(age)


# -- profile windows ----------------------------------------------------------

class ProfileController:
    """Bracket driver steps N..M with jax.profiler start/stop.

    Armed from the CLI (``--profile-window N:M``) or at runtime over
    HTTP (``POST /profile`` on web_status -> `request()`, which opens a
    window of K steps at the next step boundary). The driver calls
    `on_step(k)` at the top of every iteration and `finalize()` on the
    way out; the disarmed fast path is one attribute check, no lock.

    `start_fn`/`stop_fn` default to jax.profiler (imported lazily so a
    jax-free process can hold a controller); tests inject fakes.
    """

    def __init__(self, start_fn=None, stop_fn=None) -> None:
        self._lock = threading.Lock()
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        #: fast-path gate: False = nothing armed, nothing running
        self._hot = False
        self._window: Optional[Tuple[int, int, str]] = None
        #: HTTP-armed request: (n_steps, out_dir) pending the next step
        self._pending: Optional[Tuple[int, str]] = None
        self._running = False
        self._running_dir = ""
        #: completed window records (observability / tests)
        self.windows: List[Dict[str, Any]] = []

    # -- arming ---------------------------------------------------------------

    @staticmethod
    def parse_spec(spec: str) -> Tuple[int, int]:
        """``"N:M"`` -> (N, M), validated. Raises ValueError."""
        lo, sep, hi = spec.partition(":")
        if not sep:
            raise ValueError(f"want N:M (got {spec!r})")
        start, stop = int(lo), int(hi)
        if start < 0 or stop < start:
            raise ValueError(
                f"want 0 <= N <= M (got {start}:{stop})")
        return start, stop

    def arm(self, start: int, stop: int, out_dir: str) -> None:
        """CLI path: capture steps `start`..`stop` inclusive."""
        with self._lock:
            self._window = (int(start), int(stop), out_dir)
            self._hot = True

    def request(self, n_steps: int, out_dir: str = "") -> Dict[str, Any]:
        """HTTP path: open a window of `n_steps` steps at the next step
        boundary of the live run. Returns the armed request (echoed to
        the client). A window already running/armed is replaced —
        last writer wins, like re-POSTing."""
        n = max(1, min(int(n_steps), 100_000))
        out = out_dir or self._default_dir()
        with self._lock:
            self._pending = (n, out)
            self._hot = True
        return {"steps": n, "dir": out}

    @staticmethod
    def _default_dir() -> str:
        return os.environ.get("VELES_PROFILE_DIR", "telemetry_profile")

    # -- driver hooks ---------------------------------------------------------

    def on_step(self, step: int) -> None:
        """Called at the top of every driver iteration with the global
        step index about to run."""
        if not self._hot:
            return
        with self._lock:
            if self._pending is not None:
                n, out = self._pending
                self._pending = None
                self._window = (step, step + n - 1, out)
            win = self._window
            if win is None:
                self._hot = self._running
                if not self._running:
                    return
            if win is not None and not self._running:
                if step > win[1]:
                    # run resumed past the window (e.g. restarted from a
                    # later snapshot): drop it rather than arm forever
                    self._window = None
                    self._hot = self._pending is not None
                elif win[0] <= step:
                    self._begin(win[2], step)
            elif self._running and win is not None and step > win[1]:
                self._finish(step - 1)
                self._window = None
                self._hot = self._pending is not None

    def finalize(self) -> None:
        """End-of-run: close a still-open window (a window whose M
        exceeds the run length still yields a capture)."""
        with self._lock:
            if self._running:
                self._finish(-1)
            self._window = None
            self._pending = None
            self._hot = False

    # -- jax.profiler plumbing (lock held by callers) -------------------------

    def _begin(self, out_dir: str, step: int) -> None:
        start = self._start_fn
        if start is None:
            import jax
            start = jax.profiler.start_trace
        try:
            os.makedirs(out_dir, exist_ok=True)
            start(out_dir)
        except Exception as e:  # noqa: BLE001 — profiling must never
            # kill training (double-start, backend without profiler...)
            self.windows.append({"error": str(e)[:200], "step": step})
            self._log().warning("profile window failed to start at "
                                "step %d: %s", step, e)
            # a start that failed once fails every step of the window
            # the same way (e.g. whole-run -p profiling already active):
            # drop the window instead of retrying per step — a 100k-step
            # HTTP window would otherwise flood the log and the windows
            # list at one entry per step
            self._window = None
            self._hot = self._pending is not None
            return
        self._running = True
        self._running_dir = out_dir
        self._t0 = time.perf_counter()
        self._step0 = step
        tr = _ACTIVE
        if tr is not None:
            tr.instant(f"profile_window.start@{step}", "profile")

    def _finish(self, step: int) -> None:
        stop = self._stop_fn
        if stop is None:
            import jax
            stop = jax.profiler.stop_trace
        try:
            stop()
        except Exception as e:  # noqa: BLE001
            self.windows.append({"error": str(e)[:200], "step": step})
            self._log().warning("profile window failed to stop at "
                                "step %d: %s", step, e)
        else:
            rec = {
                "dir": self._running_dir, "first_step": self._step0,
                "last_step": step,
                "wall_s": round(time.perf_counter() - self._t0, 6)}
            self.windows.append(rec)
            self._log().info(
                "profile window captured: steps %d..%s -> %s",
                self._step0, step if step >= 0 else "<run end>",
                self._running_dir)
            tr = _ACTIVE
            if tr is not None:
                tr.instant(f"profile_window.stop@{step}", "profile")
        self._running = False

    @staticmethod
    def _log():
        import logging
        return logging.getLogger("veles.telemetry")


_CONTROLLER: Optional[ProfileController] = None


def profile_controller() -> ProfileController:
    """The process's profile-window controller (created on first use)."""
    global _CONTROLLER
    if _CONTROLLER is None:
        _CONTROLLER = ProfileController()
    return _CONTROLLER


def reset_profile_controller() -> None:
    """Drop the process controller (tests)."""
    global _CONTROLLER
    _CONTROLLER = None
