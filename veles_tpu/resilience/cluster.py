"""Cluster supervision: cross-host quorum restart over an HTTP control
plane.

PR 1 gave every HOST a `Supervisor`, but each one guessed alone: a dead
host was invisible (its supervisor died with it), and each supervisor
trusted its own snapshot directory — a host with a stale local dir
could restart "from the newest snapshot" and silently roll the fleet
back. This module closes both gaps (ROADMAP "Still manual" items):

- `ClusterCoordinator` — a tiny HTTP control plane (same
  loopback-testable hardening as task_queue/web_status: shared token,
  bounded bodies) that aggregates per-host heartbeats. It owns the
  restart decision: when any host's children die, it bumps a cluster
  GENERATION counter and picks the restart snapshot by **quorum** —
  the newest snapshot visible to at least `quorum` hosts (default
  majority), so no single stale host can pick the rollback point. A
  host that misses heartbeats past `dead_after` is declared **dead**:
  the run stops with a distinct exit code and the JSON exit report
  carries a machine-readable `dead_hosts` list — exactly what the
  cluster scheduler needs in order to re-place it.
- `ClusterMember` — the per-host agent (runs the coordinator in-process
  on host 0): gang-spawns the host's `-l`/`-m` process set, reports
  liveness/epoch/visible-snapshots every beat, and on a generation bump
  gang-kills + respawns from the directive snapshot — restoring it
  **from the mirror** (resilience/mirror.py) when the local copy is
  missing or corrupt, so a re-placed host rejoins from durable state.

The cluster is ELASTIC (the PR-4 plane fixed N hosts and made host 0 a
control-plane SPOF; this closes both):

- **Coordinator re-election.** Every directive and beat carries a
  monotone election TERM, persisted (with the coordinator's endpoint)
  as a meta record on the mirror store — the shared truth. Members
  that observe the coordinator silent past `dead_after` re-home to a
  newer announced endpoint, or — when this host holds the LOWEST live
  host-id by the mirror's presence beacons — claim term+1, wait a
  jittered settle window for a lower-id claim to override, then bind a
  fresh coordinator and announce it. The promoted coordinator GATHERS
  the re-homed members' reports and bumps the generation with the
  quorum snapshot pick, so promotion can never roll the fleet back
  past what a majority already saw. Directives from a stale term are
  rejected by every member (fencing); a minority-island incumbent
  sweeps its members dead, falls below the floor and fail-stops.
- **Elastic membership.** `n_hosts` is a FLOOR, not a constant. A
  joining host (`--cluster-join`, host-id outside the boot set) is
  admitted at the next generation bump; a host silent past
  `dead_after` is evicted and the quorum denominator SHRINKS with the
  membership — the gang respawn rebuilds the job over the live set
  (children see it via `VELES_CLUSTER_*`; the PR-6 vel-reshard-on-
  restore path carries training state across the data-axis size
  change). Only when the live set would drop below the floor does the
  run fail-stop with exit 84 and the machine-readable `dead_hosts`
  report.

The SPMD contract stays the reference's (SURVEY.md §5.3): one process
lost = the collective is dead = restart the JOB — now cluster-wide,
from an agreed-on snapshot, over whatever hosts are actually alive.

Import-light on purpose: no jax, no workflow machinery — members and
the coordinator are the processes that must outlive any model bug.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
from typing import Any, Dict, List, Optional, Sequence, Set

from veles_tpu.logger import Logger
from veles_tpu.resilience import (EXIT_GIVEUP, EXIT_HOST_DEAD,
                                  EXIT_ISOLATED, EXIT_NONFINITE)
from veles_tpu.resilience.backoff import backoff_delay
from veles_tpu.resilience.clock import SYSTEM_CLOCK, Clock
from veles_tpu.resilience.supervisor import read_heartbeat

#: heartbeats a partition fault suppresses once it fires (long enough
#: to be visible in the coordinator's beat ages, short enough to stay
#: under any sane dead_after so the member REJOINS instead of dying)
PARTITION_BEATS = 3

#: mirror meta record carrying the control plane's shared truth:
#: {"term", "host", "endpoint", "generation", "time"} — written by the
#: live coordinator at start and on every bump, overwritten by an
#: election claim (endpoint "" until the winner binds). Never contains
#: ".pickle", so it can never appear in snapshot votes.
COORD_META = "cluster_coord.json"

#: per-host presence beacon (same store): {"host", "time", "generation",
#: "term"} — the election's liveness view. Wall-clock ages, same
#: NTP-synced-fleet assumption as the quorum rule's snapshot mtimes.
BEACON_META = "cluster_beacon_{host}.json"

#: beats between beacon refreshes while the control plane is reachable
#: (every failover probe also refreshes, so election-time liveness is
#: fresh to within one probe interval)
BEACON_EVERY = 5


def _host_key(host_id: str):
    """Ordering for 'lowest live host-id wins': numeric ids compare
    numerically ("2" < "10"), non-numeric ids sort after, lexically."""
    s = str(host_id)
    return (0, int(s), "") if s.isdigit() else (1, 0, s)


# -- quorum decision (pure function: the unit-testable core) ------------------

def quorum_snapshot(reports: Sequence[Dict[str, Any]],
                    quorum: int) -> Optional[str]:
    """The restart snapshot: the newest (by reported mtime) snapshot
    NAME that at least `quorum` hosts report as visible **with an
    agreeing digest**. Each report carries
    ``{"snapshots": [{"name", "digest", "mtime"}, ...]}``.

    Counting (name, digest) pairs — not bare names — means a host whose
    LOCAL copy rotted to different bytes (local reports re-hash against
    the sidecar) does not count toward the quorum of the good copy, and
    a lone host holding a snapshot nobody else can see (the stale-dir
    rollback hazard, or a half-mirrored newest file) can never drag the
    fleet to it. Mirror-visible entries are counted on their sidecar
    claim; a mirror blob whose bytes rotted under an intact sidecar is
    caught at restore time (fetch re-verifies) and blacklisted from the
    reporting host's future votes. Returns None when nothing reaches
    quorum (restart from scratch)."""
    seen: Dict[tuple, Dict[str, Any]] = {}
    for host_idx, rep in enumerate(reports):
        for snap in rep.get("snapshots") or ():
            try:
                key = (str(snap["name"]), str(snap["digest"]))
                mtime = float(snap.get("mtime", 0.0))
            except (KeyError, TypeError, ValueError):
                continue
            ent = seen.setdefault(key, {"hosts": set(), "mtime": 0.0})
            ent["hosts"].add(host_idx)
            ent["mtime"] = max(ent["mtime"], mtime)
    best: Optional[str] = None
    best_order = None
    for (name, _digest), ent in seen.items():
        if len(ent["hosts"]) < max(1, quorum):
            continue
        order = (ent["mtime"], name)
        if best_order is None or order > best_order:
            best_order = order
            best = name
    return best


class ClusterCoordinator(Logger):
    """The control plane. One per cluster, embedded in host 0's member
    process (or run standalone). Pure state machine + HTTP transport;
    every decision happens under one lock inside `handle_beat`, so the
    logic is directly drivable in-process by tests."""

    def __init__(self, n_hosts: int, host: str = "0.0.0.0",
                 port: int = 0, *, token: Optional[str] = None,
                 quorum: int = 0, dead_after: float = 30.0,
                 join_grace: float = 120.0, max_restarts: int = 3,
                 no_progress_limit: int = 2,
                 backoff_base: float = 1.0, backoff_max: float = 30.0,
                 max_body: int = 1 << 20, term: int = 1,
                 members: Optional[Sequence[str]] = None,
                 mirror: str = "", coord_id: str = "0",
                 advertise: str = "", gather: bool = False,
                 clock: Optional[Clock] = None) -> None:
        super().__init__()
        #: time source for every beat-age / gather-deadline / drain
        #: decision — the model checker injects a VirtualClock here
        self._clock = clock or SYSTEM_CLOCK
        if n_hosts < 1:
            raise ValueError(f"n_hosts must be >= 1 (got {n_hosts})")
        #: the MINIMUM live host count, not an exact size: membership
        #: grows past it on joins and shrinks down to it on deaths;
        #: dropping BELOW it is the fail-stop condition
        self.floor = n_hosts
        self.n_hosts = n_hosts          # back-compat alias of `floor`
        #: current expected membership (host ids). Boot clusters run
        #: hosts 0..floor-1; a promoted coordinator passes the live set
        self.members: Set[str] = (
            {str(m) for m in members} if members
            else {str(i) for i in range(n_hosts)})
        #: majority OF THE CURRENT MEMBERSHIP by default, recomputed on
        #: every membership change; an explicit quorum may be smaller
        #: (2-of-5 when three hosts share no storage) but is then FIXED
        self._quorum_fixed = bool(quorum)
        self.quorum = quorum or (len(self.members) // 2 + 1)
        self.host = host
        self.port = port
        self.token = token
        #: a host silent this long is DEAD (evicted while the live set
        #: stays at/above the floor; fail-stop below it)
        self.dead_after = dead_after
        #: grace for hosts that never reported at all (first contact
        #: includes process scheduling + interpreter start on a fresh VM)
        self.join_grace = join_grace
        self.max_restarts = max_restarts
        self.no_progress_limit = no_progress_limit
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.max_body = max_body
        #: monotone election term: every directive carries it, members
        #: reject anything below the highest term they have seen, and
        #: the mirror meta record persists it across coordinators
        self.term = int(term)
        self.mirror_spec = mirror
        #: the host id this coordinator runs on (the announcement's
        #: "host") and the address peers can reach it at
        self.coord_id = str(coord_id)
        self.advertise = advertise
        #: a PROMOTED coordinator starts in gather mode: the inherited
        #: generation is unknown until the re-homed members report, so
        #: the first bump (generation := max reported + 1, quorum
        #: snapshot pick) waits for all expected members or the gather
        #: deadline — until then directives carry generation 0, which
        #: never triggers a respawn, so surviving children keep
        #: training through the election
        self._gather = bool(gather)
        self._gather_deadline = 0.0
        self._lock = threading.Lock()
        self._started = self._clock.monotonic()
        #: host_id -> {"last_beat": monotonic, "report": {...}}
        self._hosts: Dict[str, Dict[str, Any]] = {}
        self.generation = 0 if gather else 1
        self.snapshot: Optional[str] = None   # directive for current gen
        self.action = "run"
        self.exit_code = 0
        self.outcome = ""
        self.dead_hosts: List[str] = []
        self.restarts = 0
        self._best_epoch = -1
        self._stagnant = 0
        self._superseded = False
        #: pending coordinator announcement (built under _lock, mirror
        #: I/O done by _flush_announce after release)
        self._announce_record: Optional[Dict[str, Any]] = None
        #: per-generation log for the exit report
        self.generations: List[Dict[str, Any]] = [] if gather else [
            {"generation": 1, "snapshot": None, "reason": "initial",
             "members": sorted(self.members, key=_host_key),
             "term": self.term}]
        #: hosts that have RECEIVED a terminal (done/stop) directive —
        #: the embedding member drains on this before tearing the
        #: control plane down, so no peer is left polling a dead port
        self._acked: set = set()
        self._httpd = None
        self._thread = None

    # -- decision core (in-process API; HTTP is transport only) ---------------

    def handle_beat(self, report: Dict[str, Any],
                    joining: bool = False) -> Dict[str, Any]:
        """Ingest one host heartbeat, advance the state machine, return
        the directive the host must follow."""
        now = self._clock.monotonic()
        host_id = str(report.get("host", ""))[:128]
        with self._lock:
            self._hosts[host_id] = {"last_beat": now, "report": report}
            rterm = int(report.get("term", 0) or 0)
            if rterm > self.term and not self._superseded:
                # a successor was elected while this coordinator was on
                # the wrong side of a partition: every member fences
                # its directives out by term anyway; the dead-sweep of
                # its minority island is what actually stops it
                self._superseded = True
                self.error("superseded: beat from host %s carries term "
                           "%d > own %d — a newer coordinator exists; "
                           "this one's directives are fenced out",
                           host_id, rterm, self.term)
            if self.action == "run" and host_id not in self.members:
                if self._gather:
                    # the promoted coordinator's liveness view missed a
                    # host that turned out alive: fold it into the
                    # membership the gather bump will announce
                    self.members.add(host_id)
                    self._recompute_quorum()
                else:
                    # join (or a re-placed dead host rejoining):
                    # admitted at the NEXT generation bump — which this
                    # is, so the whole fleet rebuilds over the new set
                    self._membership_bump(
                        f"host {host_id} "
                        f"{'joined' if joining else 'reappeared'} — "
                        f"membership grows to "
                        f"{len(self.members) + 1}",
                        admit={host_id})
            if self.action == "run" and self._gather and (
                    self.members <= set(self._hosts)
                    or now > self._gather_deadline):
                self._gather = False
                self._membership_bump(
                    f"coordinator re-elected (term {self.term}) — "
                    f"resuming from the quorum snapshot")
            self._sweep_dead(now)
            if self.action == "run" and not self._gather:
                status = report.get("status")
                gen = int(report.get("generation", 0))
                if status == "failed" and gen == self.generation:
                    self._initiate_restart(
                        f"host {host_id} children died "
                        f"(exit codes {report.get('exit_codes')})",
                        nonfinite=EXIT_NONFINITE in (
                            report.get("exit_codes") or ()))
                elif self._all_done():
                    self.action = "done"
                    self.outcome = "completed"
            directive = self._directive()
            if directive["action"] in ("done", "stop"):
                self._acked.add(host_id)
        self._flush_announce()
        return directive

    def handle_join(self, report: Dict[str, Any]) -> Dict[str, Any]:
        """The explicit admission endpoint (`POST /join`): a joining
        host announces itself before its first beat; admission happens
        at the next generation bump, and the returned directive names
        the generation (and membership) it was admitted into."""
        self.info("join request from host %s",
                  str(report.get("host", ""))[:128])
        return self.handle_beat(report, joining=True)

    def _recompute_quorum(self) -> None:
        if not self._quorum_fixed:
            self.quorum = len(self.members) // 2 + 1

    def _sweep_dead(self, now: float) -> None:
        if self.action in ("stop", "done") or self._gather:
            # gather mode: peers are mid-re-home; the gather deadline
            # (not the beat-age sweep) bounds how long we wait for them
            return
        dead = [hid for hid in self.members
                if hid in self._hosts
                and now - self._hosts[hid]["last_beat"] > self.dead_after]
        if now - self._started > max(self.join_grace, self.dead_after):
            dead += sorted(self.members - set(self._hosts))
        dead = sorted(set(dead), key=_host_key)
        if not dead:
            return
        live = self.members - set(dead)
        self.dead_hosts = sorted(set(self.dead_hosts) | set(dead),
                                 key=_host_key)
        if len(live) < self.floor:
            self.action = "stop"
            self.exit_code = EXIT_HOST_DEAD
            self.outcome = (f"host(s) {', '.join(dead)} "
                            f"declared dead after {self.dead_after:.0f}s "
                            f"without a heartbeat and only {len(live)} "
                            f"live host(s) remain — below the "
                            f"--cluster-hosts floor of {self.floor}: "
                            "the scheduler must re-place them")
            self.error("%s", self.outcome)
        else:
            # elastic shrink: the dead hosts leave the membership, the
            # quorum denominator follows, and the gang respawn rebuilds
            # the job over the survivors — no wedge, no fail-stop
            self._membership_bump(
                f"host(s) {', '.join(dead)} dead after "
                f"{self.dead_after:.0f}s — membership shrinks to "
                f"{len(live)}", evict=set(dead))

    def _all_done(self) -> bool:
        return all(hid in self._hosts
                   and self._hosts[hid]["report"].get("status") == "done"
                   and int(self._hosts[hid]["report"]
                           .get("generation", 0)) == self.generation
                   for hid in self.members)

    def _member_reports(self) -> List[Dict[str, Any]]:
        """Current members' latest reports — the quorum electorate.
        A dead (evicted) host's stale report must not keep voting once
        the denominator shrank past it."""
        return [h["report"] for hid, h in self._hosts.items()
                if hid in self.members]

    def _initiate_restart(self, reason: str,
                          nonfinite: bool = False) -> None:
        reports = self._member_reports()
        epoch = max((int(r.get("epoch", -1)) for r in reports),
                    default=-1)
        if epoch > self._best_epoch:
            self._best_epoch = epoch
            self._stagnant = 0
        else:
            self._stagnant += 1
        if self.restarts >= self.max_restarts:
            self.action = "stop"
            self.exit_code = EXIT_GIVEUP
            self.outcome = (f"retry budget exhausted "
                            f"({self.max_restarts} restarts)")
            return
        if self._stagnant >= self.no_progress_limit:
            self.action = "stop"
            self.exit_code = EXIT_GIVEUP
            self.outcome = (f"no epoch progress across {self._stagnant} "
                            f"consecutive failures (stuck at epoch "
                            f"{self._best_epoch})")
            return
        self.restarts += 1
        self.generation += 1
        snap = quorum_snapshot(reports, self.quorum)
        if nonfinite and snap is not None:
            # the newest quorum snapshot may embed the divergence that
            # tripped the guard: drop it from every report and re-run
            # the quorum pick one snapshot back (the cluster analog of
            # Snapshotter.latest(skip=1))
            pruned = [{"snapshots": [s for s in (r.get("snapshots")
                                                 or ())
                                     if s.get("name") != snap]}
                      for r in reports]
            snap = quorum_snapshot(pruned, self.quorum)
        self.snapshot = snap
        self.generations.append({
            "generation": self.generation, "snapshot": snap,
            "reason": reason, "epoch_reached": epoch})
        self.warning(
            "restart -> generation %d from %s (%s; quorum %d/%d)",
            self.generation, snap or "<scratch>", reason, self.quorum,
            len(self.members))
        self._announce()

    def _membership_bump(self, reason: str,
                         admit: Optional[Set[str]] = None,
                         evict: Optional[Set[str]] = None) -> None:
        """Change the membership and bump the generation so the gang
        respawn rebuilds the job (data mesh + ZeRO plan) over the NEW
        live set, resuming from the quorum snapshot. Deliberately does
        NOT consume the failure-restart budget or the no-progress
        counter: a membership change is topology, not a crash loop."""
        self.members = (self.members | (admit or set())) \
            - (evict or set())
        self._recompute_quorum()
        if len(self.members) < self.floor:
            # found by the protocol model checker (analysis pass 8,
            # scenario `election`): a coordinator promoted over a live
            # view that ALREADY shrank below the floor reaches this
            # bump without ever tripping `_sweep_dead`'s floor check —
            # nobody in its (too small) membership is dead. Without
            # this guard the sub-floor fleet resumes and runs
            # indefinitely; the floor contract is one rule shared with
            # the sweep: BELOW the floor always fail-stops.
            self.action = "stop"
            self.exit_code = EXIT_HOST_DEAD
            self.outcome = (
                f"membership would shrink to {len(self.members)} "
                f"host(s) — below the --cluster-hosts floor of "
                f"{self.floor} ({reason}): the scheduler must re-place "
                f"the missing hosts")
            self.error("%s", self.outcome)
            return
        # a re-admitted host is alive again by definition
        self.dead_hosts = [d for d in self.dead_hosts
                           if d not in self.members]
        gens = [int(h["report"].get("generation", 0) or 0)
                for hid, h in self._hosts.items() if hid in self.members]
        self.generation = max([self.generation, *gens]) + 1
        self.snapshot = quorum_snapshot(self._member_reports(),
                                        self.quorum)
        self.generations.append({
            "generation": self.generation, "snapshot": self.snapshot,
            "reason": reason,
            "members": sorted(self.members, key=_host_key),
            "term": self.term})
        self.warning(
            "membership bump -> generation %d over %d host(s) [%s] "
            "from %s (%s; quorum %d)", self.generation,
            len(self.members),
            ", ".join(sorted(self.members, key=_host_key)),
            self.snapshot or "<scratch>", reason, self.quorum)
        self._announce()

    def _announce(self) -> None:
        """Queue the control-plane record (term, endpoint, current
        generation) for persistence through the mirror store — the
        shared truth members re-home from and election candidates
        fence against. Called with _lock held; the actual mirror I/O
        happens in `_flush_announce` AFTER the lock is released — a
        slow or unreachable mirror must never freeze the control plane
        (every heartbeat handler queues on _lock). Best-effort: a
        mirror-less cluster simply has no re-election (members
        fail-stop EXIT_ISOLATED as before)."""
        if not self.mirror_spec:
            return
        self._announce_record = {
            "term": self.term, "host": self.coord_id,
            "endpoint": f"{self.advertise or self.host}:{self.port}",
            "generation": self.generation, "time": self._clock.time()}

    def _flush_announce(self) -> None:
        """Publish the queued announcement (lock released: mirror I/O
        only ever blocks the one handler thread that triggered the
        bump). Concurrent flushes may land out of order in rare
        interleavings — self-healing, since every later bump
        re-announces and adoption keys on the monotone term."""
        with self._lock:
            record = self._announce_record
            self._announce_record = None
        if record is None:
            return
        try:
            self._mirror().put_meta(COORD_META, record)
        except Exception as e:  # noqa: BLE001 — announcement is
            # best-effort durability, never the control path
            self.warning("could not persist control-plane record to "
                         "%s: %s", self.mirror_spec, e)

    def _mirror(self):
        """The mirror client announcements go through (overridable
        seam: the model checker substitutes an in-memory SimMirror)."""
        from veles_tpu.resilience.mirror import get_mirror
        return get_mirror(self.mirror_spec, token=self.token)

    def _directive(self) -> Dict[str, Any]:
        delay = 0.0
        if self.action == "run" and self.restarts:
            delay = backoff_delay(self.restarts - 1,
                                  base=self.backoff_base,
                                  cap=self.backoff_max, jitter=0.0)
        return {"generation": self.generation, "action": self.action,
                "snapshot": self.snapshot,
                "term": self.term,
                "members": sorted(self.members, key=_host_key),
                "floor": self.floor,
                "dead_hosts": self.dead_hosts,
                "exit_code": self.exit_code,
                "backoff": delay,
                "reason": self.outcome}

    def drain(self, timeout: float = 10.0) -> bool:
        """Block until every live host that ever reported has received
        the terminal directive (dead hosts cannot ack), or `timeout`.
        Returns whether the drain completed."""
        deadline = self._clock.monotonic() + timeout
        while self._clock.monotonic() < deadline:
            with self._lock:
                waiting = (set(self._hosts) - self._acked
                           - set(self.dead_hosts))
                if not waiting:
                    return True
            self._clock.sleep(0.05)
        return False

    def summary(self) -> Dict[str, Any]:
        """The cluster block of the exit report."""
        with self._lock:
            return {
                "n_hosts": self.n_hosts, "floor": self.floor,
                "quorum": self.quorum,
                "term": self.term,
                "members": sorted(self.members, key=_host_key),
                "generation": self.generation,
                "restarts": self.restarts,
                "dead_hosts": list(self.dead_hosts),
                "outcome": self.outcome or self.action,
                "exit_code": self.exit_code,
                "generations": [dict(g) for g in self.generations],
                "hosts": {hid: {
                    "status": h["report"].get("status"),
                    "generation": h["report"].get("generation"),
                    "epoch": h["report"].get("epoch"),
                    "beat_age_s": round(
                        self._clock.monotonic() - h["last_beat"], 3)}
                    for hid, h in sorted(self._hosts.items())}}

    def metrics_exposition(self) -> str:
        """Fleet-aggregated Prometheus exposition, built fresh per
        scrape from the member heartbeats (no stale per-host children
        survive a membership change): the coordinator's own
        restart/generation counters, counters SUMMED across hosts from
        each child's forwarded registry snapshot, gauges labeled per
        host, and the feed/mem heartbeat payloads as fallback
        producers for jax-free or pre-telemetry children."""
        from veles_tpu.telemetry import metrics as tmetrics
        reg = tmetrics.MetricsRegistry()
        # the presence contract (step/feed/mem/restart families on
        # every scrape endpoint), declared fleet-shaped: counters sum
        # across hosts (unlabeled), per-host gauges carry a host label
        # — so a child gauge name can never collide with an unlabeled
        # standard registration
        for name, h in (
                ("veles_step_total", "training steps (fleet sum)"),
                ("veles_examples_total",
                 "training examples (fleet sum)"),
                ("veles_feed_h2d_bytes_total",
                 "feed H2D bytes (fleet sum)"),
                ("veles_feed_loader_block_seconds_total",
                 "loader-blocked seconds (fleet sum)"),
                ("veles_feed_device_sync_seconds_total",
                 "device-sync seconds (fleet sum)"),
                ("veles_feed_on_demand_total",
                 "on-demand feed pops (fleet sum)"),
                ("veles_restart_total", "cluster gang restarts")):
            reg.counter(name, h)
        reg.histogram("veles_step_seconds",
                      "per-step wall time (fleet totals; bucket "
                      "detail lives on each host's own scrape)")
        reg.gauge("veles_mem_live_bytes",
                  "newest live-bytes-max per host",
                  labelnames=("device",))
        reg.gauge("veles_mem_live_bytes_max",
                  "live bytes on the fleet's fullest host")
        #: child gauges the coordinator itself owns fleet-wide — never
        #: re-exposed per host
        reserved = {"veles_generation", "veles_mem_live_bytes_max",
                    "veles_restart_total", "veles_cluster_term",
                    "veles_cluster_members", "veles_cluster_floor"}
        with self._lock:
            reg.counter("veles_restart_total").set_total(self.restarts)
            reg.gauge("veles_generation").set(float(self.generation))
            reg.gauge("veles_cluster_term",
                      "control-plane election term").set(
                float(self.term))
            reg.gauge("veles_cluster_members",
                      "current expected membership").set(
                float(len(self.members)))
            reg.gauge("veles_cluster_floor",
                      "minimum live host count").set(float(self.floor))
            reg.gauge("veles_cluster_hosts",
                      "hosts that ever reported").set(
                float(len(self._hosts)))
            reg.gauge("veles_cluster_dead_hosts",
                      "hosts declared dead").set(
                float(len(self.dead_hosts)))
            epoch_g = reg.gauge("veles_cluster_host_epoch",
                                "newest child epoch per host",
                                labelnames=("host",))
            sums: Dict[str, float] = {}
            for hid, h in sorted(self._hosts.items()):
                rep = h["report"]
                epoch = rep.get("epoch")
                epoch_g.labels(host=hid).set(
                    float(epoch) if isinstance(epoch, (int, float))
                    and not isinstance(epoch, bool) else -1.0)
                msnap = rep.get("metrics")
                if isinstance(msnap, dict):
                    for k, v in msnap.items():
                        if not isinstance(v, (int, float)) \
                                or isinstance(v, bool):
                            continue
                        if k.endswith(("_total", "_sum", "_count")):
                            sums[k] = sums.get(k, 0.0) + float(v)
                        elif k not in reserved \
                                and tmetrics._NAME_RE.match(str(k)):
                            try:
                                reg.gauge(k, labelnames=("host",)) \
                                    .labels(host=hid).set(float(v))
                            except ValueError:
                                continue   # shape collision: skip the
                                # child key, never the whole scrape
                elif isinstance(rep.get("feed"), dict):
                    # pre-telemetry child on THIS host (mixed fleet
                    # during a rolling upgrade): derive its feed family
                    # from the raw heartbeat feed dict instead — per
                    # host, never BOTH, since a child snapshot already
                    # mirrors its own feed counters
                    feed = rep["feed"]
                    for src, dst in (
                            ("bytes_h2d", "veles_feed_h2d_bytes_total"),
                            ("loader_block_s",
                             "veles_feed_loader_block_seconds_total"),
                            ("device_sync_s",
                             "veles_feed_device_sync_seconds_total"),
                            ("on_demand",
                             "veles_feed_on_demand_total")):
                        v = feed.get(src)
                        if isinstance(v, (int, float)) \
                                and not isinstance(v, bool):
                            sums[dst] = sums.get(dst, 0.0) + float(v)
                mem = rep.get("mem")
                if isinstance(mem, dict):
                    reg.gauge("veles_mem_live_bytes",
                              labelnames=("device",)).labels(
                        device=f"host{hid}").set(
                        float(mem.get("live_bytes_max", 0) or 0))
            mem_max = max(
                (float((h["report"].get("mem") or {})
                       .get("live_bytes_max", 0) or 0)
                 for h in self._hosts.values()), default=0.0)
            reg.gauge("veles_mem_live_bytes_max").set(mem_max)
        hist: Dict[str, Dict[str, float]] = {}
        for name, total in sorted(sums.items()):
            if name.endswith("_sum"):
                hist.setdefault(name[:-4], {})["sum"] = total
            elif name.endswith("_count"):
                hist.setdefault(name[:-6], {})["count"] = total
            elif tmetrics._NAME_RE.match(name):
                try:
                    reg.counter(name).set_total(total)
                except ValueError:
                    continue    # a child key colliding with a gauge
        for base, legs in hist.items():
            # flattened child histograms fold back into the histogram
            # family (bucket detail stays with the child's own scrape)
            if not tmetrics._NAME_RE.match(base):
                continue
            try:
                reg.histogram(base).set_histogram_totals(
                    legs.get("sum", 0.0), legs.get("count", 0.0))
            except (ValueError, TypeError):
                continue
        return reg.exposition()

    # -- HTTP transport -------------------------------------------------------

    def _bind_http(self):
        """Bind (but do not serve) the HTTP transport; returns the
        server. Overridable seam: the model checker's coordinator
        returns None here — peers reach it synchronously through the
        scheduler's transport instead — while everything above this
        line (the decision core) runs unmodified."""
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        from veles_tpu.http_util import check_shared_token
        outer = self
        token = self.token

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):  # noqa: N802 (http.server API)
                if self.path.startswith("/hb"):
                    handle = outer.handle_beat
                elif self.path.startswith("/join"):
                    # the explicit admission endpoint: a joining host's
                    # first contact (same token/body contract as /hb)
                    handle = outer.handle_join
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                if not check_shared_token(self, token):
                    return
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                except ValueError:
                    length = -1
                if not 0 <= length <= outer.max_body:
                    self.send_response(413 if length > outer.max_body
                                       else 400)
                    self.end_headers()
                    return
                try:
                    report = json.loads(self.rfile.read(length)
                                        or b"{}")
                    directive = handle(dict(report))
                except (ValueError, TypeError):
                    self.send_response(400)
                    self.end_headers()
                    return
                body = json.dumps(directive).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — observability endpoints
                if self.path.startswith("/metrics"):
                    # fleet-aggregated Prometheus exposition (one scrape
                    # for the whole cluster), token-guarded like /status
                    # — the control plane binds non-loopback
                    if not check_shared_token(self, token):
                        return
                    from veles_tpu.telemetry.metrics import CONTENT_TYPE
                    body = outer.metrics_exposition().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if not self.path.startswith("/status"):
                    self.send_response(404)
                    self.end_headers()
                    return
                if not check_shared_token(self, token):
                    return
                body = json.dumps(outer.summary()).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        return ThreadingHTTPServer((self.host, self.port), Handler)

    def start(self) -> "ClusterCoordinator":
        self._httpd = self._bind_http()
        if self._httpd is not None:
            self.port = self._httpd.server_address[1]
        self._started = self._clock.monotonic()
        self._gather_deadline = self._started + max(self.dead_after,
                                                    5.0)
        self.info("cluster control plane on %s:%d (term %d, members "
                  "[%s], floor %d, quorum %d, dead after %.0fs)",
                  self.host, self.port, self.term,
                  ", ".join(sorted(self.members, key=_host_key)),
                  self.floor, self.quorum, self.dead_after)
        # announce BEFORE serve_forever spawns: the socket is already
        # bound+listening (connections queue in the backlog). Taken
        # under the lock like every other _announce call site so the
        # coordinator-state reads inside are uniformly guarded
        with self._lock:
            self._announce()
        self._flush_announce()
        if self._httpd is not None:
            self._thread = threading.Thread(
                target=lambda: self._httpd.serve_forever(poll_interval=0.05),
                daemon=True, name="cluster-coordinator")
            self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


class ClusterMember(Logger):
    """Per-host agent: supervises this host's training process set under
    the coordinator's directives. `host_id` "0" also hosts the
    coordinator (pass one in via `coordinator=`)."""

    def __init__(self, commands: Sequence[Sequence[str]], *,
                 host_id: str, coordinator_addr: str,
                 coordinator: Optional[ClusterCoordinator] = None,
                 snapshot_dir: str = ".", snapshot_prefix: str = "",
                 mirror: str = "", token: Optional[str] = None,
                 beat_s: float = 1.0, coord_timeout: float = 60.0,
                 stall_timeout: float = 0.0,
                 term_grace: float = 5.0,
                 env: Optional[Dict[str, str]] = None,
                 report_path: str = "", floor: int = 1,
                 dead_after: float = 30.0, max_restarts: int = 3,
                 join: bool = False, advertise: str = "",
                 clock: Optional[Clock] = None) -> None:
        super().__init__()
        #: time source for the beat loop, silence windows and election
        #: settles — the model checker injects a VirtualClock here
        self._clock = clock or SYSTEM_CLOCK
        if commands and isinstance(commands[0], str):
            commands = [commands]
        self.commands = [list(c) for c in commands]
        if not self.commands:
            raise ValueError("ClusterMember needs at least one command")
        self.host_id = str(host_id)
        host, _, port = coordinator_addr.rpartition(":")
        if not port.isdigit():
            raise ValueError(f"coordinator address needs host:port "
                             f"(got {coordinator_addr!r})")
        self.coord_host = host or "127.0.0.1"
        self.coord_port = int(port)
        self.coordinator = coordinator
        self.snapshot_dir = snapshot_dir
        self.snapshot_prefix = snapshot_prefix
        self.mirror_spec = mirror
        self.token = token
        self.beat_s = beat_s
        #: a member that cannot reach the control plane this long is on
        #: the wrong side of a partition: fail-stop (kill children, exit
        #: EXIT_ISOLATED) rather than train a zombie collective
        self.coord_timeout = coord_timeout
        #: hang detection, same contract as Supervisor.stall_timeout: a
        #: child whose heartbeat file goes stale this long is killed and
        #: the host reports "failed" (EXIT_STALLED codes) so the
        #: coordinator gang-restarts the job; 0 disables
        self.stall_timeout = stall_timeout
        self.term_grace = term_grace
        self.env = dict(env) if env is not None else dict(os.environ)
        self.report_path = report_path
        #: the cluster's minimum live host count (--cluster-hosts): a
        #: promoted coordinator inherits it
        self.floor = max(1, int(floor))
        #: how long coordinator silence must last before this member
        #: starts the mirror-rendezvous failover (re-home / election) —
        #: the same bound the coordinator applies to silent members
        self.dead_after = dead_after
        #: restart budget a promoted coordinator inherits
        self.max_restarts = max_restarts
        #: True = this host's id is OUTSIDE the boot membership and it
        #: announces itself via POST /join before its first beat
        self.join = bool(join)
        self._join_pending = bool(join)
        #: the address peers can reach THIS host on if it is promoted
        #: (the announced endpoint's host part; port is bound fresh)
        self.advertise = advertise or "127.0.0.1"
        #: highest election term seen (directives + announcements);
        #: directives below it are fenced out as a stale coordinator's
        self.term = 1
        #: membership as of the last accepted directive — the election
        #: electorate, and the child env's VELES_CLUSTER_* view. A boot
        #: host starts from the implied 0..floor-1 set so an election
        #: works even if the coordinator died before first contact
        self.cluster_members: List[str] = (
            [] if join else [str(i) for i in range(self.floor)])
        #: (term, endpoint) last adopted from the mirror announcement —
        #: never re-adopt the same record, so a successor that died too
        #: cannot pin the member in a re-home loop
        self._adopted: tuple = (0, "")
        #: highest term seen on any peer's presence beacon — a lower
        #: bound on the highest term bound anywhere, folded into the
        #: claim target so lossy announcement reads cannot lead this
        #: member to claim a term that is already live (model checker
        #: invariant 2)
        self._beacon_term = 0
        self._reconnect_streak = 0
        self._stale_terms_seen: set = set()
        self.generation = 0           # nothing spawned yet
        self.attempts: List[Dict[str, Any]] = []
        self._procs: List[subprocess.Popen] = []
        self._hb_paths: List[str] = []
        self._beats_sent = 0
        self._suppress_beats = 0
        self._respawns = 0
        #: highest generation a gang kill was already issued FOR (flap
        #: damping): a member whose stall detection tore the children
        #: down, then rejoins mid-generation-bump and receives the
        #: directive for that same bump, must not log/issue a second
        #: TERM round — one kill per generation transition
        self._killed_gen = 0
        self._snap_cache: Dict[str, tuple] = {}
        #: monotonic stamp of the last accepted directive — the silence
        #: window `step()` measures failover/isolation against
        self._last_contact = self._clock.monotonic()
        #: mirror entries whose FETCH failed digest verification: their
        #: sidecar claim is a lie (bit rot in the store), so this host
        #: stops reporting them as visible — the next quorum pick can't
        #: re-elect a snapshot this host has proven unrestorable
        self._bad_mirror: set = set()

    # -- snapshot visibility --------------------------------------------------

    def _local_snapshots(self) -> List[Dict[str, Any]]:
        """Valid local snapshots as (name, digest, mtime), verified via
        the sha256 sidecar, cached on (mtime, size) so a beat never
        re-hashes an unchanged file."""
        from veles_tpu.resilience.mirror import (_read_sidecar,
                                                 _sha256_file)
        try:
            names = [n for n in os.listdir(self.snapshot_dir)
                     if ".pickle" in n
                     and n.startswith(self.snapshot_prefix)
                     and not n.endswith((".tmp", ".sha256"))]
        except OSError:
            return []
        out = []
        for name in names:
            path = os.path.join(self.snapshot_dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            key = (st.st_mtime, st.st_size)
            cached = self._snap_cache.get(name)
            if cached is None or cached[0] != key:
                digest = _read_sidecar(path)
                valid = (digest is not None
                         and _sha256_file(path) == digest)
                cached = (key, digest, valid)
                self._snap_cache[name] = cached
            _, digest, valid = cached
            if valid:
                out.append({"name": name, "digest": digest,
                            "mtime": st.st_mtime})
        return out

    def _visible_snapshots(self) -> List[Dict[str, Any]]:
        """What this host reports to the quorum: locally held valid
        snapshots (sidecar digest re-verified by hashing) plus what it
        can see on the durable mirror — a host with an empty local dir
        but healthy mirror access still votes for the newest durable
        snapshot; only a host cut off from BOTH is left voting for its
        stale view. Mirror entries are counted on their SIDECAR claim
        (hashing every remote blob per beat would be prohibitive);
        restores re-verify the bytes, and an entry that ever fails that
        check lands in `_bad_mirror` and stops being reported."""
        snaps = {s["name"]: s for s in self._local_snapshots()}
        if self.mirror_spec:
            try:
                for e in self._mirror().entries():
                    name = str(e["name"])
                    if name in self._bad_mirror:
                        continue
                    if self.snapshot_prefix and not name.startswith(
                            self.snapshot_prefix):
                        continue
                    snaps.setdefault(name, {
                        "name": name, "digest": str(e["digest"]),
                        "mtime": float(e["mtime"])})
            except Exception as e:  # noqa: BLE001 — mirror visibility
                self.warning("mirror %s unreadable: %s",
                             self.mirror_spec, e)
        return sorted(snaps.values(), key=lambda s: -s["mtime"])

    def _resolve_snapshot(self, name: Optional[str]) -> Optional[str]:
        """Directive snapshot name -> local path, restoring from the
        mirror when the local copy is missing or corrupt; falls back to
        the newest local valid snapshot, then to older mirror entries,
        then to a fresh start — a failed restore must degrade, not fail
        the attempt."""
        from veles_tpu.snapshotter import Snapshotter
        if name:
            local = os.path.join(self.snapshot_dir, name)
            if os.path.exists(local) and Snapshotter.verify(local):
                return local
            if self.mirror_spec:
                try:
                    got = self._mirror().fetch(name, self.snapshot_dir)
                except Exception as e:  # noqa: BLE001
                    self.warning("mirror fetch of %s failed: %s",
                                 name, e)
                    got = None
                if got is not None:
                    self.info("restored %s from mirror", name)
                    return got
                # the mirror's sidecar claimed this name but the bytes
                # did not verify (or the fetch died): stop voting for
                # it so the NEXT quorum pick excludes it
                self._bad_mirror.add(name)
            self.warning("directive snapshot %s is unavailable locally "
                         "AND on the mirror — degrading (and no longer "
                         "reporting it as visible)", name)
        return Snapshotter.latest(self.snapshot_dir,
                                  prefix=self.snapshot_prefix,
                                  mirror=self.mirror_spec)

    # -- child lifecycle ------------------------------------------------------

    def _spawn(self, run_dir: str, snapshot: Optional[str]) -> None:
        from veles_tpu.resilience.supervisor import _with_snapshot
        self._respawns += 1
        plan = self._plan()
        if plan is not None and plan.stale_local_dir_at_restart(
                self._respawns - 1):
            self.warning("FAULT INJECTION: emptying local snapshot dir "
                         "%s before respawn (re-placed host)",
                         self.snapshot_dir)
            for s in list(self._local_snapshots()):
                for victim in (s["name"], s["name"] + ".sha256"):
                    try:
                        os.remove(os.path.join(self.snapshot_dir,
                                               victim))
                    except OSError:
                        pass
            self._snap_cache.clear()
            snapshot = self._resolve_snapshot(
                os.path.basename(snapshot) if snapshot else None)
        self._hb_paths = [
            os.path.join(run_dir,
                         f"hb_g{self.generation}_{i}.json")
            for i in range(len(self.commands))]
        self._procs = []
        for argv, hb in zip(self.commands, self._hb_paths):
            if snapshot:
                argv = _with_snapshot(argv, snapshot)
            env = dict(self.env)
            env["VELES_HEARTBEAT_FILE"] = hb
            # the elastic-membership view for the children: the gang
            # respawn rebuilds the data mesh + ZeRO plan over the LIVE
            # host set (the PR-6 vel-reshard-on-restore path carries
            # the optimizer state across the data-axis size change)
            env["VELES_CLUSTER_GENERATION"] = str(self.generation)
            env["VELES_CLUSTER_TERM"] = str(self.term)
            if self.cluster_members:
                env["VELES_CLUSTER_HOSTS"] = str(
                    len(self.cluster_members))
                env["VELES_CLUSTER_HOST_IDS"] = ",".join(
                    self.cluster_members)
            if self._is_writer():
                # the coordinator's host is the snapshot WRITER: a
                # promoted host drops the single-writer dry-run pin it
                # may have been launched with, so the fleet keeps
                # producing durable snapshots after the original
                # writer host died
                env.pop("VELES_SNAPSHOT_DRY_RUN", None)
            elif self.coordinator is not None:
                # this host still embeds a control plane but is homed
                # to a SUCCESSOR's: its coordinator was deposed, and
                # the successor's host owns the writer role now — the
                # pin must come BACK even if this host was launched
                # without one. Found by the protocol model checker
                # (analysis pass 8, scenario `partition`): without the
                # re-pin, a re-homed ex-coordinator host and the new
                # coordinator's host both write snapshots for the same
                # generation, racing their pushes on the mirror.
                env["VELES_SNAPSHOT_DRY_RUN"] = "1"
            self._procs.append(subprocess.Popen(argv, env=env))
        self.attempts.append({
            "generation": self.generation,
            "snapshot": snapshot, "pids":
                [p.pid for p in self._procs]})
        self._spawned_at = self._clock.time()  # wall: vs hb mtimes
        self.info("generation %d: spawned %d process(es)%s",
                  self.generation, len(self._procs),
                  f" from {snapshot}" if snapshot else " fresh")

    def _kill_children(self) -> None:
        from veles_tpu.resilience.supervisor import kill_procs
        kill_procs(self._procs, self.term_grace)  # TERM→grace→KILL

    def _is_writer(self) -> bool:
        """Whether this host's children produce durable snapshots:
        true iff the control plane this member is CURRENTLY homed to
        is its own embedded coordinator. Merely holding a coordinator
        object is not enough — after re-homing to a successor, the
        embedded one is deposed (it keeps running only to drain its
        remaining peers) and the successor's host owns the writer
        role."""
        return (self.coordinator is not None
                and self.coord_port == self.coordinator.port
                and self.term == self.coordinator.term)

    def _gang_kill(self, gen: int) -> None:
        """Kill this host's children at most ONCE per generation
        transition (dedupe on the generation counter — ROADMAP PR-4
        flap damping). Both of an incident's kill sites route here: the
        member-side stall detection (which fires at the CURRENT
        generation, anticipating the coordinator's bump to gen+1) and
        the directive handler (which learns the bump's actual target);
        whichever fires first wins, the other becomes a no-op instead
        of a second logged TERM round against already-dead children."""
        if gen <= self._killed_gen:
            return
        self._killed_gen = gen
        self.info("gang kill for generation %d", gen)
        self._kill_children()

    def _children_status(self) -> tuple:
        """(status, exit_codes): "running" | "done" | "failed". With
        stall_timeout set, a running child whose heartbeat file went
        stale (mtime older than the bound, spawn time as startup grace —
        the Supervisor._monitor contract) is killed here and the whole
        set reports "failed" with EXIT_STALLED codes, so the
        coordinator treats a cluster-wide hang like any other death."""
        from veles_tpu.resilience import EXIT_STALLED
        codes = [p.poll() for p in self._procs]
        if any(c is not None and c != 0 for c in codes):
            return "failed", codes
        if codes and all(c == 0 for c in codes):
            return "done", codes
        if self.stall_timeout > 0 and self._procs:
            wall_now = self._clock.time()
            spawned = getattr(self, "_spawned_at", wall_now)
            for hb, c in zip(self._hb_paths, codes):
                if c is not None:
                    continue     # finished children don't heartbeat
                try:
                    last = os.path.getmtime(hb)
                except OSError:
                    last = spawned        # not yet written: startup
                stale = wall_now - max(last, spawned)
                if stale > self.stall_timeout:
                    self.warning(
                        "heartbeat %s stale for %.1fs (> %.1fs) — "
                        "declaring this host's job hung", hb, stale,
                        self.stall_timeout)
                    # anticipates the coordinator's bump to gen+1: the
                    # directive for that bump then skips its kill
                    self._gang_kill(self.generation + 1)
                    return "failed", [
                        EXIT_STALLED if (c2 is not None and c2 < 0)
                        else c2 for c2 in
                        (p.poll() for p in self._procs)]
        return "running", codes

    def _child_payload(self) -> Dict[str, Any]:
        """The children's newest heartbeat payload: epoch plus the
        feed/mem/metrics telemetry the Launcher's epoch hook writes —
        forwarded in the cluster beat so the coordinator's /metrics
        aggregates the fleet from one producer (the child registry)."""
        hbs = [read_heartbeat(p) for p in self._hb_paths]
        out: Dict[str, Any] = {
            "epoch": max((h["epoch"] for h in hbs), default=-1)}
        for key in ("feed", "mem", "metrics"):
            v = next((h[key] for h in hbs if h.get(key)), None)
            if v is not None:
                out[key] = v
        return out

    # -- control-plane client -------------------------------------------------

    def _mirror(self):
        """The mirror client for every rendezvous read/write
        (overridable seam: the model checker substitutes an in-memory
        SimMirror so elections run against simulated shared truth)."""
        from veles_tpu.resilience.mirror import get_mirror
        return get_mirror(self.mirror_spec, token=self.token)

    def _plan(self):
        from veles_tpu.resilience.faults import active_plan
        return active_plan()

    def _report(self, status: str, codes: List[Any]) -> Dict[str, Any]:
        report = {"host": self.host_id, "generation": self.generation,
                  "term": self.term, "status": status,
                  "exit_codes": [c for c in codes],
                  "snapshots": self._visible_snapshots()}
        report.update(self._child_payload())
        return report

    def _post(self, path: str, report: Dict[str, Any]
              ) -> Optional[Dict[str, Any]]:
        from veles_tpu.http_util import http_post_json
        from veles_tpu.telemetry import tracer as _tracer
        try:
            with _tracer.span("cluster.beat", "cluster"):
                return http_post_json(self.coord_host, self.coord_port,
                                      path, report, token=self.token,
                                      timeout=max(5.0, self.beat_s * 3))
        except OSError:
            return None

    def _beat(self, status: str, codes: List[Any]
              ) -> Optional[Dict[str, Any]]:
        """Send one heartbeat; returns the directive, or None when the
        coordinator is unreachable OR a partition fault is suppressing
        this beat."""
        self._beats_sent += 1
        plan = self._plan()
        if plan is not None and plan.partition_at_beat(self._beats_sent):
            self._suppress_beats = PARTITION_BEATS
            self.warning("FAULT INJECTION: partition — dropping %d "
                         "heartbeat(s)", PARTITION_BEATS)
        if self._suppress_beats > 0:
            self._suppress_beats -= 1
            return None
        if self._beats_sent % BEACON_EVERY == 1:
            self._publish_beacon()
        return self._post("/hb", self._report(status, codes))

    def _join_cluster(self, status: str, codes: List[Any]
                      ) -> Optional[Dict[str, Any]]:
        """First contact for a joining host: announce via the explicit
        POST /join admission endpoint (admission = the next generation
        bump). Falls back to retrying — with the same backoff/failover
        path as a lost beat — until a control plane answers."""
        self._publish_beacon()
        directive = self._post("/join", self._report(status, codes))
        if directive is not None:
            self._join_pending = False
            self.info("admitted to the cluster (directive generation "
                      "%s, members %s)", directive.get("generation"),
                      directive.get("members"))
        else:
            self.warning("join request to %s:%d got no answer — "
                         "retrying", self.coord_host, self.coord_port)
        return directive

    # -- failover: mirror-rendezvous re-home / re-election --------------------

    def _publish_beacon(self, mirror=None) -> None:
        """Refresh this host's presence beacon on the mirror store (the
        election's liveness view)."""
        if not self.mirror_spec:
            return
        try:
            (mirror or self._mirror()).put_meta(
                BEACON_META.format(host=self.host_id),
                {"host": self.host_id, "time": self._clock.time(),
                 "generation": self.generation, "term": self.term})
        except Exception as e:  # noqa: BLE001 — liveness is best-effort
            self.warning("presence beacon publish failed: %s", e)

    def _live_hosts(self, mirror) -> List[str]:
        """Host ids (of the known membership plus self) whose presence
        beacon is fresher than dead_after — who is still standing for
        election purposes. Wall-clock ages: the same NTP-synced-fleet
        assumption the quorum rule makes for snapshot mtimes."""
        now = self._clock.time()
        live = {self.host_id}
        for hid in set(self.cluster_members) | {self.host_id}:
            if hid == self.host_id:
                continue
            try:
                beacon = mirror.get_meta(BEACON_META.format(host=hid))
            except Exception:  # noqa: BLE001
                beacon = None
            if beacon is None:
                continue
            try:
                # terms are monotone per host, so even a STALE beacon's
                # term is a valid lower bound on the highest term bound
                # anywhere — remembered so a claim can never target a
                # term this member has indirect evidence of. Found by
                # the protocol model checker (analysis pass 8, scenario
                # `partition`): with the announcement record unreadable
                # (lossy NFS reads degrade to None), a candidate that
                # never observed term T+1 directly would claim it OVER
                # a live term-T+1 coordinator and double-bind the term.
                self._beacon_term = max(
                    self._beacon_term, int(beacon.get("term", 0) or 0))
            except (TypeError, ValueError):
                pass
            try:
                age = now - float(beacon.get("time", 0.0))
            except (TypeError, ValueError):
                continue
            if age < self.dead_after:
                live.add(str(beacon.get("host", hid)))
        return sorted(live, key=_host_key)

    def _try_adopt(self, ann: Optional[Dict[str, Any]]) -> bool:
        """Re-home to an announced successor coordinator. Adopts only a
        record that moves this member FORWARD: a newer term, or the
        current term at an endpoint we have not already adopted (so a
        successor that died too cannot pin us in a re-home loop — the
        next silence window escalates to an election instead)."""
        if not isinstance(ann, dict):
            return False
        try:
            term = int(ann.get("term", 0) or 0)
        except (TypeError, ValueError):
            return False
        endpoint = str(ann.get("endpoint") or "")
        host, _, port = endpoint.rpartition(":")
        if not port.isdigit():
            return False          # claim without a bound endpoint yet
        if term < self.term or (term, endpoint) == self._adopted:
            return False
        if str(ann.get("host")) == self.host_id \
                and self.coordinator is None:
            # our own earlier claim that never finished promoting:
            # nothing to re-home to — the election path retries
            return False
        if term == self.term \
                and endpoint == f"{self.coord_host}:{self.coord_port}":
            return False          # already homed exactly there
        self.coord_host = host or "127.0.0.1"
        self.coord_port = int(port)
        self._adopted = (term, endpoint)
        self.term = max(self.term, term)
        self.info("re-homing to coordinator %s (term %d, announced by "
                  "host %s)", endpoint, term, ann.get("host"))
        return True

    def _seek_coordinator(self) -> bool:
        """The failover path, entered once the control plane has been
        silent past dead_after: consult the mirror's shared record and
        either RE-HOME to a successor's announced endpoint, or — when
        this host holds the lowest live host-id — claim the next term,
        wait a jittered settle window for a lower-id claim to override,
        and PROMOTE self. Returns True when the member has a control
        plane to talk to again."""
        mirror = self._mirror()
        self._publish_beacon(mirror)
        try:
            ann = mirror.get_meta(COORD_META)
        except Exception as e:  # noqa: BLE001
            self.warning("mirror %s unreachable during failover: %s",
                         self.mirror_spec, e)
            return False
        if self._try_adopt(ann):
            return True
        if self._join_pending:
            # a joining host that was never admitted has no membership
            # to inherit: it may re-home to an announced successor
            # (above) but must NOT stand for election — promoting here
            # would fork a one-host rival cluster instead of joining
            # the real one (or failing stop when it is gone)
            self.info("not yet admitted — a joining host cannot stand "
                      "for election; retrying /join")
            return False
        live = self._live_hosts(mirror)
        if live[0] != self.host_id:
            self.info("coordinator silent; host %s (lowest live of %s) "
                      "owns the promotion — waiting for its "
                      "announcement", live[0], live)
            return False
        # deterministic anti-collision bias: a believed-lowest
        # candidate with a HIGHER id waits longer before claiming, so
        # when stale beacons make two hosts each believe they are the
        # lowest live, the true lowest claims first and the other
        # adopts its announcement on the re-read below
        rank = _host_key(self.host_id)[1]
        if rank:
            self._clock.sleep(min(rank, 8) * max(self.beat_s, 0.25))
            try:
                ann = mirror.get_meta(COORD_META)
            except Exception:  # noqa: BLE001
                return False
            if self._try_adopt(ann):
                return True
        target = max(self.term, self._beacon_term,
                     int((ann or {}).get("term", 0) or 0)) + 1
        claim = {"term": target, "host": self.host_id, "endpoint": "",
                 "time": self._clock.time()}
        for attempt in range(3):
            if not mirror.put_meta(COORD_META, dict(claim)):
                return False
            # jittered settle: a racing lower-id candidate's rewrite
            # must get the chance to land before we commit
            self._clock.sleep(backoff_delay(attempt,
                                            base=max(self.beat_s, 0.25),
                                            cap=2.0))
            try:
                now_ann = mirror.get_meta(COORD_META)
            except Exception:  # noqa: BLE001
                return False
            if now_ann is None:
                continue
            a_host = str(now_ann.get("host", ""))
            a_term = int(now_ann.get("term", 0) or 0)
            if a_host == self.host_id and a_term == target:
                return self._promote(target, live)
            if self._try_adopt(now_ann):
                return True
            if _host_key(a_host) < _host_key(self.host_id):
                # a lower id claimed: defer; adopt once it announces
                return False
            # a higher id raced us: rewrite our claim and settle again
            target = max(target, a_term)
            claim = {"term": target, "host": self.host_id,
                     "endpoint": "", "time": self._clock.time()}
        return False

    def _promote(self, term: int, live: List[str]) -> bool:
        """Become the coordinator: bind a fresh control plane over the
        live membership, announce its endpoint at the claimed term, and
        re-home to it. The new coordinator starts in GATHER mode, so
        its first directive bump resumes every host from the quorum
        snapshot the re-homed members report — promotion can never roll
        the fleet back (the pick needs a majority of the live set)."""
        members = sorted(set(live) | {self.host_id}, key=_host_key)
        try:
            coord = self._bind_coordinator(term, members)
        except OSError as e:
            self.error("could not bind the promoted control plane: %s",
                       e)
            return False
        self.coordinator = coord
        self.coord_host = self.advertise
        self.coord_port = coord.port
        self._adopted = (term, f"{self.advertise}:{coord.port}")
        self.term = term
        self.warning("promoted self to coordinator (term %d) at %s:%d "
                     "over live hosts [%s]", term, self.advertise,
                     coord.port, ", ".join(members))
        plan = self._plan()
        if plan is not None and plan.coord_loss_at_term(term):
            # deterministic re-elected-coordinator loss: the whole host
            # vanishes right after the announcement peers will re-home
            # to — the survivors must elect a THIRD coordinator
            self._kill_children()
            import logging as _logging
            _logging.shutdown()
            os.kill(os.getpid(), signal.SIGKILL)
        return True

    def _bind_coordinator(self, term: int,
                          members: List[str]) -> ClusterCoordinator:
        """Construct and start the promoted control plane (overridable
        seam: the model checker binds a transport-free coordinator into
        its simulated world instead of an HTTP server). Raises OSError
        when the bind fails."""
        loopback = self.advertise in ("127.0.0.1", "localhost", "::1")
        coord = ClusterCoordinator(
            self.floor, host="127.0.0.1" if loopback else "0.0.0.0",
            port=0, token=self.token, dead_after=self.dead_after,
            max_restarts=self.max_restarts, members=members,
            mirror=self.mirror_spec, term=term, coord_id=self.host_id,
            advertise=self.advertise, gather=True, clock=self._clock,
            # a live member re-homes within ~one seek interval; a host
            # whose beacon was borderline-fresh at promotion but is
            # actually dead must not get the default two-minute
            # first-contact grace before the membership can shrink
            join_grace=self.dead_after * 2)
        coord.start()
        return coord

    # -- main loop ------------------------------------------------------------

    def step(self, run_dir: str) -> Optional[int]:
        """ONE beat-loop iteration: probe the children, beat (or join),
        fence stale terms, handle silence (failover / isolation
        fail-stop) and the accepted directive's actions. Returns the
        process exit code when the member is finished, None to keep
        looping. Extracted from `run()` so the model checker can drive
        the REAL loop logic one schedulable action at a time."""
        status, codes = (self._children_status()
                         if self._procs else ("joining", []))
        directive = (self._join_cluster(status, codes)
                     if self._join_pending
                     else self._beat(status, codes))
        if directive is not None:
            dterm = int(directive.get("term", self.term) or 0)
            if dterm < self.term:
                # term fencing: a stale coordinator (the
                # pre-partition incumbent coming back, or one
                # this member already moved past) must not
                # steer this host — treat its directive as
                # silence so the failover path takes over
                if dterm not in self._stale_terms_seen:
                    self._stale_terms_seen.add(dterm)
                    self.warning(
                        "rejecting directive from stale term "
                        "%d (this member has seen term %d)",
                        dterm, self.term)
                directive = None
        if directive is None:
            now = self._clock.monotonic()
            silent = now - self._last_contact
            if self.mirror_spec and silent > self.dead_after:
                if self._seek_coordinator():
                    # re-homed (or promoted): fresh window
                    self._last_contact = self._clock.monotonic()
                    self._reconnect_streak = 0
                    return None
            elif self.mirror_spec:
                # stay visibly ALIVE to electors while cut off:
                # a beacon that goes stale during the silence
                # window would let a higher host-id believe it
                # is the lowest live and double-promote
                self._publish_beacon()
            if silent > self.coord_timeout:
                self.error(
                    "no control-plane contact for %.0fs: this "
                    "host is partitioned — killing children "
                    "and exiting (fail-stop, the quorum side "
                    "owns the job)", self.coord_timeout)
                self._kill_children()
                return self._finish(EXIT_ISOLATED,
                                    "isolated from the control "
                                    "plane")
            # jittered exponential reconnect backoff (shared
            # resilience/backoff.py policy), capped well under
            # coord_timeout so the isolation check stays live
            self._clock.sleep(backoff_delay(
                self._reconnect_streak, base=self.beat_s,
                cap=max(self.beat_s,
                        min(5.0, self.coord_timeout / 4))))
            self._reconnect_streak += 1
            return None
        self._last_contact = self._clock.monotonic()
        self._reconnect_streak = 0
        self.term = max(self.term,
                        int(directive.get("term", 0) or 0))
        members = directive.get("members")
        if isinstance(members, list) and members:
            self.cluster_members = [str(m) for m in members]
        action = directive.get("action")
        if action in ("done", "stop"):
            self._kill_children()   # "done": no-op, exited 0
            if self.coordinator is not None:
                # keep the control plane up until every live
                # peer has received the terminal directive too
                self.coordinator.drain(
                    timeout=max(5.0, self.beat_s * 10))
            if action == "done":
                return self._finish(0, "completed")
            code = int(directive.get("exit_code")
                       or EXIT_GIVEUP)
            return self._finish(
                code, directive.get("reason") or "stopped",
                dead_hosts=directive.get("dead_hosts"))
        gen = int(directive.get("generation", 1))
        if gen > self.generation:
            # gang restart on the coordinated generation counter
            # (deduped: a stall kill or a replayed directive for
            # this same bump already tore the children down)
            self._gang_kill(gen)
            backoff = float(directive.get("backoff") or 0.0)
            if backoff:
                self._clock.sleep(backoff)
            self.generation = gen
            # no directive snapshot = run the argv as-is: the
            # initial generation, or a quorum that agreed on
            # NOTHING (scratch restart — resolving a local
            # latest() unilaterally here would reintroduce the
            # stale-dir rollback hazard the quorum exists for)
            name = directive.get("snapshot")
            self._spawn(run_dir,
                        self._resolve_snapshot(name)
                        if name else None)
        self._clock.sleep(self.beat_s)
        return None

    def run(self) -> int:
        run_dir = tempfile.mkdtemp(
            prefix=f"veles_cluster_h{self.host_id}_")
        self.env.setdefault("VELES_FAULT_STATE",
                            os.path.join(run_dir, "fault_state.json"))
        self._last_contact = self._clock.monotonic()

        # SIGTERM (scheduler preempting the AGENT) must not orphan the
        # training children: convert to the Ctrl-C teardown path (same
        # contract as Supervisor.run; no-op off the main thread)
        def _to_interrupt(*_):
            raise KeyboardInterrupt

        try:
            prev_term = signal.signal(signal.SIGTERM, _to_interrupt)
        except ValueError:
            prev_term = None
        try:
            while True:
                code = self.step(run_dir)
                if code is not None:
                    return code
        except KeyboardInterrupt:
            self._kill_children()
            return self._finish(130, "terminated by signal")
        finally:
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            shutil.rmtree(run_dir, ignore_errors=True)

    def _finish(self, code: int, outcome: str,
                dead_hosts: Optional[List[str]] = None) -> int:
        report: Dict[str, Any] = {
            "outcome": outcome, "exit_code": code,
            "host": self.host_id, "generation": self.generation,
            "term": self.term,
            "members": list(self.cluster_members),
            "dead_hosts": list(dead_hosts or []),
            "attempts": self.attempts}
        if self.coordinator is not None:
            cluster = self.coordinator.summary()
            report["cluster"] = cluster
            report["dead_hosts"] = cluster["dead_hosts"]
        (self.info if code == 0 else self.error)(
            "cluster member %s: %s (exit %d, generation %d%s)",
            self.host_id, outcome, code, self.generation,
            f", dead hosts {report['dead_hosts']}"
            if report["dead_hosts"] else "")
        print(f"cluster member {self.host_id}: {outcome} "
              f"(generation {self.generation})", file=sys.stderr,
              flush=True)
        if self.report_path:
            with open(self.report_path, "w") as f:
                json.dump(report, f, indent=2)
        if self.coordinator is not None:
            self.coordinator.stop()
        return code
