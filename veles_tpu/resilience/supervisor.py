"""Training supervisor: automated crash/hang recovery from snapshots.

The SPMD fault model (SURVEY.md §5.3) is restart-from-snapshot; the
Supervisor is the process that actually performs the restart:

    spawn ──▶ monitor ──▶ all children exit 0 ──▶ report, exit 0
                │
                ├─ child died (crash / preemption / nonzero exit)
                ├─ heartbeat stale > stall_timeout  ──▶ kill children
                ▼
          budget left AND epoch progress?
                │yes                         │no
                ▼                            ▼
          backoff (exp + jitter)       report, exit EXIT_GIVEUP
          pick newest VALID snapshot
          (roll back one on EXIT_NONFINITE)
          re-spawn with -s <snapshot> ──▶ monitor …

Liveness is a heartbeat FILE per child: the Launcher touches it at
startup and at every epoch boundary (an atomic JSON write carrying the
epoch counter), so the supervisor detects both "process is gone" and
"process is alive but stuck" — and can tell "restarted but not
advancing" (the epoch counter never grows) from real progress.

Multi-process jobs: pass one argv per training process (the `-l`/`-m`
coordinator/worker pair) — a failure of ANY child fails the attempt,
every child is killed, and the whole job restarts from the shared
snapshot directory, which is exactly the SPMD contract (one process
lost = the collective is dead).

Import-light on purpose: no jax, no workflow machinery — the supervisor
must stay a tiny parent process that cannot itself die of a model bug.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Optional, Sequence

from veles_tpu.logger import Logger
from veles_tpu.resilience import EXIT_GIVEUP, EXIT_NONFINITE, EXIT_STALLED
from veles_tpu.resilience.clock import SYSTEM_CLOCK, Clock
from veles_tpu.snapshotter import Snapshotter


# -- heartbeat protocol (writer side lives in the Launcher) -------------------

def write_heartbeat(path: str, epoch: int,
                    feed: Optional[Dict[str, Any]] = None,
                    mem: Optional[Dict[str, Any]] = None,
                    metrics: Optional[Dict[str, Any]] = None,
                    clock: Clock = SYSTEM_CLOCK) -> None:
    """Atomically publish liveness + the epoch counter. Atomic so a
    supervisor read never sees a torn file; the file's mtime is the
    liveness signal, the payload is the progress signal. `feed` is the
    child's device-feed overlap counter dict (loader/device_feed.py),
    `mem` the child's per-device memory snapshot
    (parallel/memstats.py), `metrics` the child's flat telemetry
    snapshot (telemetry/metrics.py snapshot_flat) — the supervisor
    surfaces the last of each in its JSON exit report, and the cluster
    member forwards them so the coordinator's /metrics can aggregate
    the fleet."""
    tmp = f"{path}.{os.getpid()}.tmp"
    payload: Dict[str, Any] = {"epoch": int(epoch), "ts": clock.time()}
    if feed:
        # drop the bulky per-epoch rows: the heartbeat is read every
        # poll interval and only the totals matter to the supervisor
        payload["feed"] = {k: v for k, v in feed.items()
                           if k != "epoch_log"}
    if mem:
        payload["mem"] = mem
    if metrics:
        payload["metrics"] = metrics
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def read_heartbeat(path: str) -> Dict[str, Any]:
    """Parse a heartbeat file; `{"epoch": -1}` when missing/torn."""
    try:
        with open(path) as f:
            data = json.load(f)
        out = {"epoch": int(data.get("epoch", -1)),
               "ts": float(data.get("ts", 0.0))}
        for extra in ("feed", "mem", "metrics"):
            if isinstance(data.get(extra), dict):
                out[extra] = data[extra]
        return out
    except (OSError, ValueError):
        return {"epoch": -1, "ts": 0.0}


def memory_delta(mem: Optional[Dict[str, Any]]
                 ) -> Optional[Dict[str, Any]]:
    """Predicted-vs-measured per-device memory delta from one heartbeat
    `mem` payload (analysis pass 6: the child's pre-flight prediction
    rides the beat next to the memstats snapshot). Pairs like with
    like: the allocator PEAK (TPU) against the predicted high-water,
    else the live-array resident set (CPU meshes) against the predicted
    resident bytes. None when either side is missing — the report must
    never fabricate a comparison."""
    if not isinstance(mem, dict):
        return None
    pred = mem.get("predicted")
    if not isinstance(pred, dict):
        return None
    measured = mem.get("peak_bytes_max")
    predicted = pred.get("highwater_per_device")
    basis = "peak_vs_highwater"
    if measured is None:
        measured = mem.get("live_bytes_max")
        predicted = pred.get("resident_per_device")
        basis = "live_vs_resident"
    if not measured or predicted is None:
        return None
    return {"predicted_per_device": int(predicted),
            "measured_per_device": int(measured),
            "delta_frac": round((int(predicted) - int(measured))
                                / int(measured), 4),
            "basis": basis}


def strip_flags(argv: Sequence[str],
                flags: Dict[str, bool]) -> List[str]:
    """Remove flag occurrences from a command line. `flags` maps flag
    name -> whether it takes a value; both `--flag value` and
    `--flag=value` forms are dropped. Shared by the supervisor's
    snapshot rewrite and the CLI's child-argv/daemon re-exec filters
    (three hand-rolled copies of this loop diverged once already)."""
    out: List[str] = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in flags:
            skip = flags[a]
            continue
        if any(a.startswith(f + "=")
               for f, takes in flags.items() if takes):
            continue
        out.append(a)
    return out


def _with_snapshot(argv: Sequence[str], snapshot: str) -> List[str]:
    """Rewrite a child command line to resume from `snapshot`: any
    existing -s/--snapshot (both `-s X` and `--snapshot=X` forms) is
    dropped, the new one appended."""
    return strip_flags(argv, {"-s": True, "--snapshot": True}) \
        + ["-s", snapshot]


def kill_procs(procs: Sequence[subprocess.Popen],
               term_grace: float = 5.0,
               clock: Clock = SYSTEM_CLOCK) -> None:
    """TERM, short grace, then KILL — every child, idempotent. Shared by
    the per-host Supervisor and the cluster member's gang-kill."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        try:
            p.terminate()
        except OSError:
            pass
    deadline = clock.monotonic() + term_grace
    for p in live:
        try:
            p.wait(timeout=max(0.0, deadline - clock.monotonic()))
        except subprocess.TimeoutExpired:
            try:
                p.send_signal(signal.SIGKILL)
            except OSError:
                pass
            p.wait()


class Supervisor(Logger):
    """Spawn, watch and restart a training job until it finishes or the
    retry budget / progress cutoff says stop."""

    def __init__(self, commands: Sequence[Sequence[str]], *,
                 snapshot_dir: str = ".", snapshot_prefix: str = "",
                 max_restarts: int = 3, stall_timeout: float = 0.0,
                 backoff_base: float = 1.0, backoff_max: float = 30.0,
                 jitter: float = 0.25, no_progress_limit: int = 2,
                 poll_interval: float = 0.2, term_grace: float = 5.0,
                 env: Optional[Dict[str, str]] = None,
                 report_path: str = "", mirror: str = "",
                 clock: Clock = SYSTEM_CLOCK) -> None:
        super().__init__()
        if commands and isinstance(commands[0], str):
            commands = [commands]        # a single argv, not a list of them
        self.commands = [list(c) for c in commands]
        if not self.commands:
            raise ValueError("Supervisor needs at least one command")
        self.snapshot_dir = snapshot_dir
        self.snapshot_prefix = snapshot_prefix
        self.max_restarts = max_restarts
        #: 0 disables stall detection (death-only supervision)
        self.stall_timeout = stall_timeout
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.jitter = jitter
        #: consecutive failed attempts with NO epoch advance before
        #: giving up (a crash loop that always dies in the same place)
        self.no_progress_limit = no_progress_limit
        self.poll_interval = poll_interval
        self.term_grace = term_grace
        #: injectable time source (resilience/clock.py): every wait /
        #: deadline in the restart loop goes through it so tests and
        #: the model checker can own time
        self._clock = clock
        self.env = dict(env) if env is not None else dict(os.environ)
        #: optional JSON exit report (attempt log, outcome, final codes)
        self.report_path = report_path
        # one-registry telemetry (stdlib-only module: the supervisor
        # stays import-light): restart/generation ride the same
        # families the coordinator and /metrics endpoints expose, and
        # VELES_METRICS_JSONL mirrors them for offline analysis
        from veles_tpu.telemetry import metrics as _tmetrics
        self._m_restarts = _tmetrics.default_registry().counter(
            "veles_restart_total")
        self._m_generation = _tmetrics.default_registry().gauge(
            "veles_generation")
        jsonl = os.environ.get("VELES_METRICS_JSONL")
        if jsonl:
            _tmetrics.install_jsonl(jsonl)
        #: snapshot mirror spec (resilience/mirror.py): restart snapshot
        #: resolution restores from it when the local dir cannot satisfy
        #: the request (missing/corrupt) — durable-state rejoin
        self.mirror = mirror
        self.attempts: List[Dict[str, Any]] = []

    # -- lifecycle -------------------------------------------------------------

    def run(self) -> int:
        """Supervise to completion; returns the job's final exit code
        (0 on success, EXIT_GIVEUP when abandoning, 130/143 when the
        supervisor itself is interrupted/terminated — children are
        killed and the exit report still lands)."""
        run_dir = tempfile.mkdtemp(prefix="veles_supervisor_")
        # SIGTERM (scheduler preemption of the SUPERVISOR) must not
        # orphan the training children: convert it to the same teardown
        # path as Ctrl-C for the duration of the run
        def _to_interrupt(*_):
            raise KeyboardInterrupt

        try:        # signal handlers are main-thread-only; embedded
            prev_term = signal.signal(signal.SIGTERM, _to_interrupt)
        except ValueError:
            prev_term = None
        self._procs: List[subprocess.Popen] = []
        try:
            return self._run(run_dir)
        except KeyboardInterrupt:
            self._kill_all(self._procs)
            self.attempts.append({
                "attempt": len(self.attempts) + 1,
                "reason": "supervisor terminated", "exit_codes":
                    [p.returncode for p in self._procs],
                "epoch_reached": -1, "snapshot": None})
            return self._finish(130, "terminated by signal")
        finally:
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            import shutil
            shutil.rmtree(run_dir, ignore_errors=True)

    def _run(self, run_dir: str) -> int:
        restarts = 0
        best_epoch = -1
        stagnant = 0
        snapshot: Optional[str] = None
        # one shared fault state file: a fault that fired in attempt N
        # must not re-fire in attempt N+1 (see faults.py)
        self.env.setdefault("VELES_FAULT_STATE",
                            os.path.join(run_dir, "fault_state.json"))
        while True:
            attempt_no = len(self.attempts) + 1
            hb_paths = [os.path.join(run_dir, f"hb_{attempt_no}_{i}.json")
                        for i in range(len(self.commands))]
            self.info("attempt %d/%d%s", attempt_no, self.max_restarts + 1,
                      f" (resume from {snapshot})" if snapshot else "")
            procs = self._procs = self._spawn(snapshot, hb_paths)
            reason, codes = self._monitor(procs, hb_paths)
            hbs = [read_heartbeat(p) for p in hb_paths]
            epoch = max((h["epoch"] for h in hbs), default=-1)
            attempt = {
                "attempt": attempt_no, "reason": reason,
                "exit_codes": codes, "epoch_reached": epoch,
                "snapshot": snapshot}
            # input-pipeline overlap counters from the child's last
            # heartbeat (loader/device_feed.py via the Launcher's epoch
            # hook): the exit report shows whether the host pipeline
            # kept the device fed, without instrumenting the child
            feed = next((h["feed"] for h in hbs if h.get("feed")), None)
            if feed is not None:
                attempt["feed"] = feed
            # ditto the per-device memory snapshot (parallel/memstats.py
            # via the same Launcher epoch hook): the report shows the
            # measured footprint — e.g. the ZeRO optimizer-state delta —
            # of the child that actually ran
            mem = next((h["mem"] for h in hbs if h.get("mem")), None)
            if mem is not None:
                attempt["mem"] = mem
            # and the child's one-registry snapshot (step counters,
            # loss, feed totals) — same producer as its /metrics
            msnap = next((h["metrics"] for h in hbs
                          if h.get("metrics")), None)
            if msnap is not None:
                attempt["metrics"] = msnap
            self.attempts.append(attempt)
            self._m_generation.set(attempt_no)
            if reason == "ok":
                return self._finish(0, "completed")
            self.warning("attempt %d failed: %s (exit codes %s, "
                         "epoch reached %d)", attempt_no, reason, codes,
                         epoch)
            if epoch > best_epoch:
                best_epoch = epoch
                stagnant = 0
            else:
                stagnant += 1
            if restarts >= self.max_restarts:
                return self._finish(
                    EXIT_GIVEUP,
                    f"retry budget exhausted ({self.max_restarts} "
                    f"restarts)")
            if stagnant >= self.no_progress_limit:
                return self._finish(
                    EXIT_GIVEUP,
                    f"no epoch progress across {stagnant} consecutive "
                    f"failures (stuck at epoch {best_epoch})")
            restarts += 1
            self._m_restarts.inc()
            from veles_tpu.resilience.backoff import backoff_delay
            delay = backoff_delay(restarts - 1, base=self.backoff_base,
                                  cap=self.backoff_max,
                                  jitter=self.jitter)
            self.info("backing off %.2fs before restart %d", delay,
                      restarts)
            self._clock.sleep(delay)
            # EXIT_NONFINITE: the newest snapshot may already embed the
            # divergence (it was written before the guard tripped) —
            # roll back one valid snapshot.
            skip = 1 if EXIT_NONFINITE in codes else 0
            snapshot = Snapshotter.latest(self.snapshot_dir,
                                          prefix=self.snapshot_prefix,
                                          skip=skip, mirror=self.mirror)
            if snapshot is None:
                self.warning("no valid snapshot in %s — restarting from "
                             "scratch", self.snapshot_dir)
            else:
                self.info("restart %d will resume from %s", restarts,
                          snapshot)

    # -- internals -------------------------------------------------------------

    def _spawn(self, snapshot: Optional[str],
               hb_paths: List[str]) -> List[subprocess.Popen]:
        procs = []
        for argv, hb in zip(self.commands, hb_paths):
            if snapshot:
                argv = _with_snapshot(argv, snapshot)
            env = dict(self.env)
            env["VELES_HEARTBEAT_FILE"] = hb
            procs.append(subprocess.Popen(argv, env=env))
        return procs

    def _monitor(self, procs: List[subprocess.Popen],
                 hb_paths: List[str]):
        """Watch one attempt. Returns (reason, exit_codes): reason "ok"
        (all exited 0), "died" (some child exited nonzero), or "stall"
        (a heartbeat went stale; children were killed)."""
        # wall time (clock.time(), not monotonic): staleness compares
        # against heartbeat-file mtimes, which live on the wall axis
        start = self._clock.time()
        while True:
            codes = [p.poll() for p in procs]
            if any(c is not None and c != 0 for c in codes):
                self._kill_all(procs)
                return "died", [p.wait() for p in procs]
            if all(c == 0 for c in codes):
                return "ok", codes
            if self.stall_timeout > 0:
                now = self._clock.time()
                for p, hb, c in zip(procs, hb_paths, codes):
                    if c is not None:
                        continue     # finished children don't heartbeat
                    try:
                        last = os.path.getmtime(hb)
                    except OSError:
                        last = start     # not yet written: startup grace
                    if now - max(last, start) > self.stall_timeout:
                        self.warning(
                            "heartbeat %s stale for %.1fs (> %.1fs) — "
                            "declaring the job hung", hb,
                            now - max(last, start), self.stall_timeout)
                        self._kill_all(procs)
                        # children we just killed report the signal
                        # (-TERM/-KILL); surface those as the documented
                        # EXIT_STALLED so the attempt log says WHY they
                        # died, not just how
                        return "stall", [
                            EXIT_STALLED if c < 0 else c
                            for c in (p.wait() for p in procs)]
            self._clock.sleep(self.poll_interval)

    def _kill_all(self, procs: List[subprocess.Popen]) -> None:
        kill_procs(procs, self.term_grace, clock=self._clock)

    def _finish(self, code: int, outcome: str) -> int:
        """Log the actionable exit report (and mirror it to JSON when
        report_path is set); returns `code`."""
        lines = [f"supervisor: {outcome} after {len(self.attempts)} "
                 f"attempt(s)"]
        for a in self.attempts:
            lines.append(
                f"  attempt {a['attempt']}: {a['reason']}, exit codes "
                f"{a['exit_codes']}, epoch reached {a['epoch_reached']}, "
                f"snapshot {a['snapshot'] or '<fresh>'}")
        if code != 0:
            latest = Snapshotter.latest(self.snapshot_dir,
                                        prefix=self.snapshot_prefix,
                                        mirror=self.mirror)
            lines.append(
                f"  resume manually with: -s {latest}" if latest else
                f"  no valid snapshot found in {self.snapshot_dir!r}")
        report = "\n".join(lines)
        (self.info if code == 0 else self.error)("%s", report)
        print(report, file=sys.stderr, flush=True)
        if self.report_path:
            report_obj = {"outcome": outcome, "exit_code": code,
                          "attempts": self.attempts}
            # the newest AVAILABLE device-feed counters and per-device
            # memory snapshot, promoted to the top level (the scheduler-
            # facing input-pipeline health + memory footprint view).
            # Each carries "from_attempt": the two can come from
            # DIFFERENT attempts (a final attempt may die before its
            # first mem-carrying beat), and a reader must not attribute
            # a stale snapshot to the final attempt's configuration
            for key in ("feed", "mem", "metrics"):
                for a in reversed(self.attempts):
                    if a.get(key):
                        report_obj[key] = dict(a[key])
                        report_obj[key]["from_attempt"] = a.get("attempt")
                        break
            # predicted-vs-measured memory delta (analysis pass 6,
            # ISSUE 14), promoted alongside "mem": the child's beat
            # carries the pre-flight prediction next to the measured
            # snapshot — the scheduler-facing answer to "was the
            # static HBM model right for the run that just ended"
            delta = memory_delta(report_obj.get("mem"))
            if delta is not None:
                report_obj["memory"] = delta
            try:
                # the supervisor's OWN registry view (restarts,
                # generation) — one producer with the child's promoted
                # "metrics" block above; the JSONL sink (if installed)
                # mirrors the final state too
                from veles_tpu.telemetry import metrics as _tmetrics
                report_obj["telemetry"] = _tmetrics.snapshot_flat()
                _tmetrics.flush_installed(
                    extra={"source": "supervisor", "outcome": outcome})
            except Exception:  # noqa: BLE001 — report cosmetics must
                pass           # never mask the exit path
            try:
                # which op lowerings the run was configured to trace.
                # PROVENANCE: this is the supervisor process's view
                # (registry defaults + selections visible here) — a
                # child that ran --autotune or applied a populated
                # VELES_AUTOTUNE_CACHE may have traced cached winners
                # instead; the note keeps the record from misattributing
                # a measured outcome to the wrong lowerings. Guarded
                # import: the variants module itself is jax-free, but
                # its package __init__ is not, and the supervisor must
                # never die on report cosmetics at exit time.
                from veles_tpu.ops.variants import selection_table
                report_obj["variants"] = selection_table(
                    include_defaults=True)
                report_obj["variants_provenance"] = (
                    "supervisor-process registry view (defaults + local "
                    "selections); children using --autotune or "
                    "VELES_AUTOTUNE_CACHE may have traced persisted "
                    "winners not reflected here")
            except Exception:  # noqa: BLE001
                pass
            try:
                # structured analyzer findings for the supervised child
                # config (the non-finite guard left off), landing next
                # to the variant table. Guarded import like `variants`
                # above: analysis.trace pulls jax, and the supervisor
                # must never die on report cosmetics at exit time.
                from veles_tpu.analysis.trace import environment_findings
                finds = []
                for argv in self.commands:
                    for f in environment_findings(argv=argv):
                        if not any(g.rule == f.rule and g.unit == f.unit
                                   for g in finds):
                            finds.append(f)
                report_obj["analysis"] = [f.as_dict() for f in finds]
            except Exception:  # noqa: BLE001
                pass
            with open(self.report_path, "w") as f:
                json.dump(report_obj, f, indent=2)
        return code
